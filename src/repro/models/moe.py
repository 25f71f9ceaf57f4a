"""Mixture-of-Experts: expert-parallel capacity-based dispatch.

Experts are sharded over the ``model`` mesh axis (expert parallelism). The
baseline dispatch is *token-replicated*: activations entering the MoE block
are replicated over ``model`` (standard in a TP transformer), so each chip
simply gathers the tokens routed to its local experts, runs a batched expert
GEMM, scatter-adds the weighted outputs, and one ``psum`` over ``model``
combines — the same collective cost as a dense Megatron MLP block, with no
all-to-all. An a2a variant is a §Perf alternative.

Routing (softmax -> top-k -> renorm) and the load-balancing/z losses are
computed outside the shard_map in plain pjit ops.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import AXIS_MODEL, batch_axes


def moe_init(scope, cfg):
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    scope.param("router", (d, e), ("embed", "experts"), dtype=jnp.float32)
    scope.param("w_in", (e, d, f), ("experts", "embed", "expert_mlp"))
    scope.param("w_out", (e, f, d), ("experts", "expert_mlp", "embed"))
    if cfg.mlp_act == "swiglu":
        scope.param("w_gate", (e, d, f), ("experts", "embed", "expert_mlp"))


def route(p, cfg, x):
    """x: (B,S,d) -> ids (B,S,K) int32, weights (B,S,K) f32, aux dict."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    wts, ids = jax.lax.top_k(probs, cfg.top_k)
    wts = wts / jnp.maximum(jnp.sum(wts, axis=-1, keepdims=True), 1e-9)
    # load-balance loss (Switch): E * sum_e mean_prob_e * frac_assign_e
    counts = jnp.zeros((cfg.n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
    mean_prob = jnp.mean(probs, axis=(0, 1))
    lb_loss = cfg.n_experts * jnp.sum(mean_prob * frac)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return ids, wts, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}


def _capacity(tokens: int, cfg) -> int:
    return max(1, math.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))


def _moe_local(x, ids, wts, w_in, w_gate, w_out, *, cfg, n_local, axis):
    """Per-chip dispatch/compute/combine. x: (Bl,S,d); weights: (E_loc,d,f)."""
    Bl, S, d = x.shape
    K = cfg.top_k
    T = Bl * S
    C = _capacity(T, cfg)
    lo = (jax.lax.axis_index(axis) if axis else 0) * n_local
    xf = x.reshape(T, d)
    idf = ids.reshape(T * K)
    wtf = wts.reshape(T * K).astype(jnp.float32)
    tok = jnp.arange(T * K, dtype=jnp.int32) // K

    local = (idf >= lo) & (idf < lo + n_local)
    e_loc = jnp.where(local, idf - lo, n_local)          # n_local = drop bucket
    onehot = jax.nn.one_hot(e_loc, n_local, dtype=jnp.int32)   # (TK, E_loc)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)  # slot in expert

    # dispatch tables (E_loc, C); OOB rows/cols (drops, remote experts) fall away
    tok_tbl = jnp.full((n_local, C), T, jnp.int32).at[e_loc, pos].set(
        tok, mode="drop")
    g_tbl = jnp.zeros((n_local, C), jnp.float32).at[e_loc, pos].set(
        wtf, mode="drop")

    valid = (tok_tbl < T)[..., None]
    xe = jnp.where(valid, xf[jnp.clip(tok_tbl, 0, T - 1)], 0)    # (E_loc,C,d)
    h = jnp.einsum("ecd,edf->ecf", xe, w_in)
    if cfg.mlp_act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = jnp.square(jax.nn.relu(h))
    ye = jnp.einsum("ecf,efd->ecd", h, w_out)
    ye = (ye.astype(jnp.float32) * g_tbl[..., None]).astype(x.dtype)

    y = jnp.zeros((T, d), x.dtype).at[tok_tbl.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    if axis:
        y = jax.lax.psum(y, axis)
    return y.reshape(Bl, S, d)


def moe_apply(p, cfg, x, ids, wts, mesh=None):
    """Expert-parallel MoE. Returns (B,S,d)."""
    w_gate = p.get("w_gate", p["w_in"])  # placeholder when not gated
    n_model = mesh.shape.get(AXIS_MODEL, 1) if mesh is not None else 1
    if mesh is None or n_model == 1 or cfg.n_experts % n_model != 0:
        return _moe_local(x, ids, wts, p["w_in"], w_gate, p["w_out"],
                          cfg=cfg, n_local=cfg.n_experts, axis=None)
    n_local = cfg.n_experts // n_model
    bax = batch_axes(mesh)
    btotal = 1
    for a in bax:
        btotal *= mesh.shape[a]
    # replicate batch when it cannot shard (e.g. long-context decode B=1)
    bspec = P(bax if (bax and x.shape[0] % btotal == 0) else None)
    fn = jax.shard_map(
        lambda xx, ii, ww, wi, wg, wo: _moe_local(
            xx, ii, ww, wi, wg, wo, cfg=cfg, n_local=n_local, axis=AXIS_MODEL),
        mesh=mesh,
        in_specs=(P(*bspec, None, None), P(*bspec, None, None), P(*bspec, None, None),
                  P(AXIS_MODEL, None, None), P(AXIS_MODEL, None, None),
                  P(AXIS_MODEL, None, None)),
        out_specs=P(*bspec, None, None),
        check_vma=False,
    )
    return fn(x, ids, wts, p["w_in"], w_gate, p["w_out"])
