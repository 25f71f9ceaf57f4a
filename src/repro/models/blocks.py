"""Transformer / Mamba / MoE block assembly (pre-norm residual)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models.attention import (
    attn_init, chunked_attention, decode_attention, qkv_proj, repeat_kv,
)
from repro.models.layers import mlp_apply, mlp_init, rmsnorm, rmsnorm_init
from repro.models.ssm import mamba_apply, mamba_init
from repro.parallel.collectives import seq_sharded_decode_attention
from repro.parallel.sharding import AXIS_MODEL


def block_init(scope, cfg, i: int):
    """Init one block at pattern position i."""
    d = cfg.d_model
    rmsnorm_init(scope, "norm1", d)
    if cfg.block_kind(i) == "attn":
        attn_init(scope.sub("attn"), cfg)
    else:
        mamba_init(scope.sub("mamba"), cfg)
    has_ffn = cfg.d_ff > 0 or cfg.is_moe_layer(i)
    if has_ffn:
        rmsnorm_init(scope, "norm2", d)
    if cfg.is_moe_layer(i):
        moe_lib.moe_init(scope.sub("moe"), cfg)
        if cfg.dense_residual and cfg.d_ff > 0:
            mlp_init(scope.sub("dense_mlp"), cfg, cfg.d_ff)
        if cfg.n_shared_experts > 0:
            mlp_init(scope.sub("shared_mlp"), cfg,
                     cfg.n_shared_experts * cfg.d_ff_expert)
    elif cfg.d_ff > 0:
        mlp_init(scope.sub("mlp"), cfg, cfg.d_ff)


def attn_block(p, cfg, rt, x, positions, cache=None, lengths=None,
               decode=False, page_table=None, layer=None):
    """Returns (out (B,S,d), new_cache (k,v)).

    Decode takes the whole stacked cache, leaves (R, B, S, KVH, hd), and
    ``layer``, this block's index into R. It writes the new token's K/V
    at ``layer`` in place and returns the stack; attention reads the
    layer by indexing the stack, so no per-layer copy of the cache is
    made. With ``page_table`` (B, pages_per_row) the leaves are a shared
    page pool (R, n_pages, page_size, KVH, hd): the new token's K/V
    scatter through the table and attention runs over the gathered
    per-row view. Gathered masked positions contribute exactly 0
    probability, so the result is bit-identical to the contiguous path
    over the same tokens.
    """
    B, S, _ = x.shape
    q, k, v = qkv_proj(p, cfg, x, positions)
    if decode:
        assert S == 1
        qd = q[:, 0]  # (B,H,hd)
        k_cache, v_cache = cache
        bidx = jnp.arange(B)
        if page_table is not None:
            ps = k_cache.shape[2]
            page = page_table[bidx, lengths // ps]
            off = lengths % ps
            k_cache = k_cache.at[layer, page, off].set(k[:, 0])
            v_cache = v_cache.at[layer, page, off].set(v[:, 0])
            n_pt = page_table.shape[1]
            # index the stack, not a slice of it: ``k_cache[layer]`` first
            # would materialize the layer's whole pool
            k_view = k_cache[layer, page_table].reshape(
                B, n_pt * ps, *k_cache.shape[3:])
            v_view = v_cache[layer, page_table].reshape(
                B, n_pt * ps, *v_cache.shape[3:])
            o = decode_attention(qd, k_view, v_view, lengths + 1)
        elif rt.decode_kv_shard(cfg) == "seq":
            o, k_l, v_l = seq_sharded_decode_attention(
                qd, k_cache[layer], v_cache[layer], lengths, k[:, 0],
                v[:, 0], rt.mesh, AXIS_MODEL)
            k_cache = jax.lax.dynamic_update_index_in_dim(
                k_cache, k_l, layer, 0)
            v_cache = jax.lax.dynamic_update_index_in_dim(
                v_cache, v_l, layer, 0)
        else:
            k_cache = k_cache.at[layer, bidx, lengths].set(k[:, 0])
            v_cache = v_cache.at[layer, bidx, lengths].set(v[:, 0])
            o = decode_attention(qd, k_cache[layer], v_cache[layer],
                                 lengths + 1)
        o = o[:, None]  # (B,1,H,hd)
        new_cache = (k_cache, v_cache)
    else:
        kf = repeat_kv(k, cfg.n_heads)
        vf = repeat_kv(v, cfg.n_heads)
        # pad heads to the model-axis multiple so the chunked scans stay
        # collective-free (padded heads are dead weight, sliced off below)
        H = cfg.n_heads
        Hp = rt.padded_heads(H)
        if Hp != H:
            pad = ((0, 0), (0, 0), (0, Hp - H), (0, 0))
            q, kf, vf = (jnp.pad(t, pad) for t in (q, kf, vf))
        q, kf, vf = rt.shard_heads(q), rt.shard_heads(kf), rt.shard_heads(vf)
        o = chunked_attention(
            q, kf, vf, causal=True,
            q_chunk=rt.parallel.attn_q_chunk,
            kv_chunk=rt.parallel.attn_kv_chunk,
            impl=rt.parallel.attn_impl)
        o = rt.shard_heads(o)[:, :, :H] if Hp != H else o
        new_cache = (k, v)
    out = jnp.einsum("bsq,qd->bsd", o.reshape(B, S, cfg.q_dim), p["wo"])
    return out, new_cache


def block_apply(p, cfg, rt, x, positions, i, *, cache=None, lengths=None,
                decode=False, page_table=None, layer=None):
    """One block. cache: kind-dependent pytree (or None for training).

    Decode takes the stacked cache of pattern position ``i`` (leading axis
    R) and ``layer``, the index into it; it writes what this layer changed
    at ``layer`` in place and returns the stack.

    Returns (x, new_cache, aux_losses dict).
    """
    aux = {}
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if cfg.block_kind(i) == "attn":
        out, new_cache = attn_block(p["attn"], cfg, rt, h, positions,
                                    cache=cache, lengths=lengths,
                                    decode=decode, page_table=page_table,
                                    layer=layer)
    elif decode:
        # SSM state is O(1) per row: read the layer's, write its new one
        conv_state, ssm_state = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0, False), cache)
        out, new_l = mamba_apply(p["mamba"], cfg, h, conv_state=conv_state,
                                 ssm_state=ssm_state, decode=True)
        new_cache = jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, layer, 0),
            cache, new_l)
    else:
        out, new_cache = mamba_apply(p["mamba"], cfg, h)
    x = x + out
    if cfg.is_moe_layer(i):
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        ids, wts, aux = moe_lib.route(p["moe"], cfg, h)
        y = moe_lib.moe_apply(p["moe"], cfg, h, ids, wts, mesh=rt.moe_mesh())
        if cfg.dense_residual and cfg.d_ff > 0:
            y = y + mlp_apply(p["dense_mlp"], h, cfg.mlp_act)
        if cfg.n_shared_experts > 0:
            y = y + mlp_apply(p["shared_mlp"], h, cfg.mlp_act)
        x = x + y
    elif cfg.d_ff > 0:
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], h, cfg.mlp_act)
    return x, new_cache, aux
