"""Explicit-collective building blocks (shard_map).

``seq_sharded_decode_attention`` is the TPU-native analogue of GPU
flash-decoding: the KV cache is sharded along *sequence* over a mesh axis,
each chip computes a partial softmax over its KV slice, and the partials are
combined with one tiny ``psum`` (per-head scalars + one head-dim vector).
This is what lets a 524k-token cache decode on a 16-way axis, and lets GQA
archs with kv_heads < axis size shard their cache at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import AXIS_MODEL, batch_axes

NEG_INF = -1e30


def _partial_decode(q, k, v, lengths, new_k, new_v, axis, seq_total):
    """Per-shard body. q: (B,H,hd); k/v: (B,S_loc,KVH,hd) local slice;
    new_k/new_v: (B,KVH,hd) token to insert at position ``lengths``."""
    B, S_loc, KVH, hd = k.shape
    H = q.shape[1]
    G = H // KVH
    idx = jax.lax.axis_index(axis) if axis else 0
    offset = idx * S_loc
    # ---- insert the new token's KV if it lands in this shard ----
    local_pos = lengths - offset  # (B,)
    in_range = (local_pos >= 0) & (local_pos < S_loc)
    safe_pos = jnp.clip(local_pos, 0, S_loc - 1)
    bidx = jnp.arange(B)
    k = k.at[bidx, safe_pos].set(
        jnp.where(in_range[:, None, None], new_k, k[bidx, safe_pos]))
    v = v.at[bidx, safe_pos].set(
        jnp.where(in_range[:, None, None], new_v, v[bidx, safe_pos]))
    # ---- partial attention over the local slice ----
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32) / (hd ** 0.5)
    pos = offset + jnp.arange(S_loc)
    valid = pos[None, :] <= lengths[:, None]  # (B,S_loc) — includes new token
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B,KVH,G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v).astype(jnp.float32)
    if axis:
        mx = jax.lax.pmax(m, axis)
        alpha = jnp.exp(m - mx)
        o = jax.lax.psum(o * alpha[..., None], axis)
        l = jax.lax.psum(l * alpha, axis)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(B, H, hd)
    return out.astype(q.dtype), k, v


def seq_sharded_decode_attention(q, k_cache, v_cache, lengths, new_k, new_v,
                                 mesh, axis=AXIS_MODEL):
    """Decode attention with the cache sharded on seq over ``axis``.

    q: (B,H,hd); caches: (B,S,KVH,hd); lengths: (B,); new_k/new_v: (B,KVH,hd).
    Returns (out (B,H,hd), new_k_cache, new_v_cache).
    """
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        return _partial_decode(q, k_cache, v_cache, lengths, new_k, new_v,
                               None, k_cache.shape[1])
    bax = batch_axes(mesh)
    btotal = 1
    for a in bax:
        btotal *= mesh.shape[a]
    # replicate the batch dim when it cannot shard (e.g. long-context B=1)
    b = bax if (bax and q.shape[0] % btotal == 0) else None
    fn = jax.shard_map(
        lambda qq, kk, vv, ll, nk, nv: _partial_decode(
            qq, kk, vv, ll, nk, nv, axis, k_cache.shape[1]),
        mesh=mesh,
        in_specs=(P(b, None, None), P(b, axis, None, None), P(b, axis, None, None),
                  P(b), P(b, None, None), P(b, None, None)),
        out_specs=(P(b, None, None), P(b, axis, None, None), P(b, axis, None, None)),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, lengths, new_k, new_v)
