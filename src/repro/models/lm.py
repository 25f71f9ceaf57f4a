"""Decoder LM assembly: embeddings -> scanned blocks -> head(s) + losses.

One class covers all five assigned families:
- dense / MoE / hybrid / SSM backbones via the block pattern in ModelConfig,
- VLM: precomputed patch embeddings (stub frontend) prepended to token
  embeddings, loss masked to text positions,
- audio: ``n_codebooks`` parallel token streams (summed input embeddings,
  one output head per codebook; the delay pattern lives in the data layer).

Layers are scanned over ``n_layers / pattern_period`` repeats of the pattern
(period 1 for homogeneous stacks; e.g. 8 for Jamba's 7:1 mamba:attn
interleave with MoE on alternate layers). Remat wraps the scan body.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models.blocks import block_apply, block_init
from repro.models.layers import rmsnorm, rmsnorm_init
from repro.models.module import Scope, stacked_init, _fold
from repro.parallel.sharding import AXIS_MODEL, batch_axes

AUX_KEYS = ("moe_lb_loss", "moe_z_loss")


@dataclass
class Runtime:
    """Static execution context threaded through apply fns."""
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    mesh: Mesh | None = None

    def moe_mesh(self):
        return self.mesh

    def padded_heads(self, n_heads: int) -> int:
        """Heads padded up to a multiple of the model axis so attention
        activations shard cleanly. With H % model != 0 (qwen2's 28,
        qwen3's 40, arctic's 56 over a 16-way axis) GSPMD otherwise shards
        the *contracting* dims and emits an all-reduce inside every
        (q-chunk, kv-chunk) iteration — measured 3x total wire bytes on
        qwen2-14b train_4k. Zero-padded heads are sliced off before w_o."""
        if self.mesh is None or AXIS_MODEL not in self.mesh.axis_names:
            return n_heads
        m = self.mesh.shape[AXIS_MODEL]
        return -(-n_heads // m) * m

    def shard_heads(self, t):
        """Constrain (B, S, H, hd) attention activations to batch x heads."""
        if self.mesh is None:
            return t
        baxes = batch_axes(self.mesh)
        btotal = math.prod(self.mesh.shape[a] for a in baxes) if baxes else 1
        b = baxes if (baxes and t.shape[0] % btotal == 0) else None
        m = (AXIS_MODEL if AXIS_MODEL in self.mesh.axis_names
             and t.shape[2] % self.mesh.shape[AXIS_MODEL] == 0 else None)
        spec = jax.sharding.PartitionSpec(b, None, m, None)
        return jax.lax.with_sharding_constraint(
            t, jax.sharding.NamedSharding(self.mesh, spec))

    def shard_activations(self, x):
        """Pin the residual stream to (batch over data axes, replicated,
        replicated): without this GSPMD happily replicates the batch dim
        inside the layer scan and the saved-for-backward buffers blow up
        16x (measured on qwen3-14b train_4k: 25.6 -> ~3 GiB per device)."""
        if self.mesh is None:
            return x
        baxes = batch_axes(self.mesh)
        if not baxes or x.shape[0] % math.prod(
                self.mesh.shape[a] for a in baxes):
            return x
        spec = jax.sharding.PartitionSpec(baxes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    def decode_kv_shard(self, cfg) -> str:
        mode = self.parallel.decode_kv_shard
        if mode != "auto":
            return mode
        if self.mesh is None or AXIS_MODEL not in self.mesh.axis_names:
            return "heads"
        return ("heads" if cfg.n_kv_heads >= self.mesh.shape[AXIS_MODEL]
                else "seq")


def _zero_aux():
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def _acc_aux(a, b):
    return {k: a[k] + b.get(k, 0.0) for k in AUX_KEYS}


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------- init
    def init(self, key, abstract: bool = False):
        """Returns (params, axes). abstract=True -> ShapeDtypeStruct leaves."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        scope = Scope(key, dtype, abstract)
        ncb = max(1, cfg.n_codebooks)
        scope.param("embed", (ncb, cfg.vocab_padded, cfg.d_model),
                    ("codebooks", "vocab", "embed"), init="normal", scale=0.02)
        scope.param("head", (ncb, cfg.d_model, cfg.vocab_padded),
                    ("codebooks", "embed", "vocab"))
        rmsnorm_init(scope, "final_norm", cfg.d_model)
        period = cfg.pattern_period
        repeats = cfg.n_layers // period
        blocks_p, blocks_a = {}, {}
        for i in range(period):
            k_i = None if abstract else _fold(key, f"blocks{i}")
            p_i, a_i = stacked_init(
                lambda s, i=i: block_init(s, cfg, i), k_i, repeats,
                dtype=dtype, abstract=abstract)
            blocks_p[f"pos{i}"], blocks_a[f"pos{i}"] = p_i, a_i
        params, axes = scope.done()
        params["blocks"], axes["blocks"] = blocks_p, blocks_a
        return params, axes

    def runtime(self, parallel=None, mesh=None):
        return Runtime(parallel or ParallelConfig(), mesh)

    # ------------------------------------------------------------ embed
    def embed(self, params, batch):
        cfg = self.cfg
        emb = params["embed"]  # (ncb, Vp, d)
        tokens = batch["tokens"]
        if cfg.n_codebooks > 1:  # (B,S,ncb)
            x = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), emb.dtype)
            for c in range(cfg.n_codebooks):
                x = x + emb[c][tokens[..., c]]
        else:
            x = emb[0][tokens]
        if cfg.vision_stub and "patches" in batch:
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        return x

    def logits(self, params, x):
        cfg = self.cfg
        if cfg.n_codebooks > 1:
            return jnp.einsum("bsd,cdv->bscv", x, params["head"])
        return jnp.einsum("bsd,dv->bsv", x, params["head"][0])

    # ---------------------------------------------------------- backbone
    def backbone(self, params, rt: Runtime, x, positions, *, collect_cache=False,
                 remat=True):
        cfg = self.cfg
        period = cfg.pattern_period

        def body(carry, layer_params):
            x, aux = carry
            caches = {}
            for i in range(period):
                x, cache_i, aux_i = block_apply(
                    layer_params[f"pos{i}"], cfg, rt, x, positions, i)
                x = rt.shard_activations(x)
                caches[f"pos{i}"] = cache_i
                aux = _acc_aux(aux, aux_i)
            return (x, aux), (caches if collect_cache else None)

        if remat and rt.parallel.remat != "none":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), caches = jax.lax.scan(body, (x, _zero_aux()), params["blocks"])
        return x, aux, caches

    def decode_backbone(self, params, rt: Runtime, x, lengths, caches,
                        page_table=None):
        """One-token step through all layers.

        The stacked caches ride in the layer scan's carry, and each layer
        writes only what it changed (one token of K/V, or its SSM state)
        at its own index, in place: the step makes no copy of the caches.
        With ``page_table`` (B, pages_per_row) int32, attention cache
        leaves are a shared page pool (R, n_pages, page_size, KVH, hd)
        (see ``paged_cache_shapes``); SSM caches stay slot-indexed.
        """
        cfg = self.cfg
        period = cfg.pattern_period
        positions = lengths[:, None]
        if page_table is not None and rt.decode_kv_shard(cfg) == "seq":
            raise ValueError(
                "paged decode requires decode_kv_shard != 'seq' "
                "(page tables gather across the sequence axis)")

        def body(carry, layer_params):
            x, caches, li = carry
            caches = dict(caches)
            for i in range(period):
                x, caches[f"pos{i}"], _ = block_apply(
                    layer_params[f"pos{i}"], cfg, rt, x, positions, i,
                    cache=caches[f"pos{i}"], lengths=lengths,
                    decode=True, page_table=page_table, layer=li)
            return (x, caches, li + 1), None

        (x, new_caches, _), _ = jax.lax.scan(
            body, (x, caches, jnp.int32(0)), params["blocks"])
        return x, new_caches

    # ------------------------------------------------------------- train
    def loss(self, params, rt: Runtime, batch):
        """batch: tokens (B,S[,ncb]) int32, targets (same), mask (B,S) f32,
        optional patches (B,Np,d). Returns (loss, metrics)."""
        cfg = self.cfg
        x = rt.shard_activations(self.embed(params, batch))
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, _ = self.backbone(params, rt, x, positions)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.vision_stub and "patches" in batch:
            x = x[:, batch["patches"].shape[1]:]  # loss on text positions only
        logits = self.logits(params, x).astype(jnp.float32)
        targets = batch["targets"]
        mask = batch["mask"].astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        ce = lse - tgt  # (B,S[,ncb])
        if cfg.n_codebooks > 1:
            ce = jnp.mean(ce, axis=-1)
            lse = jnp.mean(lse, axis=-1)
        mask3 = mask
        denom = jnp.maximum(jnp.sum(mask3), 1.0)
        ce_loss = jnp.sum(ce * mask3) / denom
        loss = (ce_loss + 0.01 * aux["moe_lb_loss"] + 1e-3 * aux["moe_z_loss"])
        metrics = {"ce": ce_loss, **aux,
                   "z": jnp.sum(jnp.square(lse) * mask3) / denom}
        return loss, metrics

    # ------------------------------------------------------------- serve
    def prefill(self, params, rt: Runtime, batch):
        """Full-sequence forward; returns (last_logits, caches, aux)."""
        cfg = self.cfg
        x = self.embed(params, batch)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, caches = self.backbone(params, rt, x, positions,
                                       collect_cache=True, remat=False)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = self.logits(params, x[:, -1:])
        return logits[:, 0], caches, aux

    def decode(self, params, rt: Runtime, tokens, lengths, caches,
               page_table=None):
        """tokens: (B,1[,ncb]); lengths: (B,) current cache fill.
        Returns (logits (B,[ncb,]V), new_caches)."""
        x = self.embed(params, {"tokens": tokens})
        x, new_caches = self.decode_backbone(params, rt, x, lengths, caches,
                                             page_table=page_table)
        x = rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        logits = self.logits(params, x)
        return logits[:, 0], new_caches

    # ------------------------------------------------- cache construction
    def cache_shapes(self, batch_size: int, max_len: int):
        """Abstract cache pytree (ShapeDtypeStructs) for decode cells."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        period = cfg.pattern_period
        R = cfg.n_layers // period
        caches = {}
        for i in range(period):
            if cfg.block_kind(i) == "attn":
                kv = jax.ShapeDtypeStruct(
                    (R, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim), dtype)
                caches[f"pos{i}"] = (kv, kv)
            else:
                ch_x = cfg.d_inner
                ch_bc = cfg.ssm_groups * cfg.d_state
                conv = {
                    "x": jax.ShapeDtypeStruct(
                        (R, batch_size, cfg.conv_dim - 1, ch_x), dtype),
                    "B": jax.ShapeDtypeStruct(
                        (R, batch_size, cfg.conv_dim - 1, ch_bc), dtype),
                    "C": jax.ShapeDtypeStruct(
                        (R, batch_size, cfg.conv_dim - 1, ch_bc), dtype),
                }
                state = jax.ShapeDtypeStruct(
                    (R, batch_size, cfg.n_ssm_heads, cfg.ssm_head_dim,
                     cfg.d_state), jnp.float32)
                caches[f"pos{i}"] = (conv, state)
        return caches

    def init_cache(self, batch_size: int, max_len: int):
        shapes = self.cache_shapes(batch_size, max_len)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def paged_cache_shapes(self, batch_size: int, n_pages: int,
                           page_size: int):
        """Like ``cache_shapes`` but attention KV lives in a shared page
        pool (R, n_pages, page_size, KVH, hd) addressed via a per-row page
        table. SSM state is O(1) per row (no sequence axis), so it stays
        slot-indexed — paging it would buy nothing."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        period = cfg.pattern_period
        R = cfg.n_layers // period
        caches = {}
        for i in range(period):
            if cfg.block_kind(i) == "attn":
                kv = jax.ShapeDtypeStruct(
                    (R, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim),
                    dtype)
                caches[f"pos{i}"] = (kv, kv)
            else:
                caches[f"pos{i}"] = self.cache_shapes(
                    batch_size, page_size)[f"pos{i}"]
        return caches

    def init_paged_cache(self, batch_size: int, n_pages: int, page_size: int):
        shapes = self.paged_cache_shapes(batch_size, n_pages, page_size)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
