"""GQA attention: projections + memory-efficient chunked softmax.

Three execution paths:
- ``masked``      double scan over (q-chunk, kv-chunk) with causal masking —
                  computes the full S^2 pair grid (2x causal waste, baseline).
- ``triangular``  scan over the *static lower-triangular list* of chunk pairs
                  — true causal FLOPs in pure JAX (beyond-paper §Perf opt).
- Pallas flash kernel (repro.kernels) on real TPUs; the jnp paths double as
  its oracle and as the model-side implementation.

Decode uses grouped-query einsums against the KV cache without materializing
repeated KV heads; the sequence-sharded combine lives in
repro.parallel.collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rmsnorm, rmsnorm_init

NEG_INF = -1e30


def attn_init(scope, cfg):
    d = cfg.d_model
    scope.param("wq", (d, cfg.q_dim), ("embed", "heads"))
    scope.param("wk", (d, cfg.kv_dim), ("embed", "kv_heads"))
    scope.param("wv", (d, cfg.kv_dim), ("embed", "kv_heads"))
    scope.param("wo", (cfg.q_dim, d), ("heads", "embed"))
    if cfg.qkv_bias:
        scope.param("bq", (cfg.q_dim,), ("heads",), init="zeros")
        scope.param("bk", (cfg.kv_dim,), ("kv_heads",), init="zeros")
        scope.param("bv", (cfg.kv_dim,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        rmsnorm_init(scope, "q_norm", cfg.head_dim)
        rmsnorm_init(scope, "k_norm", cfg.head_dim)


def qkv_proj(p, cfg, x, positions):
    """x: (B,S,d) -> q (B,S,H,hd), k/v (B,S,KVH,hd) with rope (+qk-norm)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def repeat_kv(k, n_heads: int):
    """(B,S,KVH,hd) -> (B,S,H,hd)."""
    B, S, KVH, hd = k.shape
    if KVH == n_heads:
        return k
    rep = n_heads // KVH
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KVH, rep, hd)).reshape(
        B, S, n_heads, hd
    )


def _block_attn(qb, kb, vb, mask, scale):
    """One (Bq x Bk) block: returns (o_acc, m, l) in fp32.

    The running max ``m`` only shifts the exponent and cancels in o / l, so
    it carries no gradient. Differentiating ``max`` would divide by the
    count of entries equal to it; under remat on the TPU the recomputed
    scores may keep excess precision in one fusion and not in another, so
    no entry equals the max, the count is 0, and dQ/dK become NaN."""
    s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1))  # (B,H,Q)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                      # (B,H,Q)
    o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb.dtype), vb).astype(jnp.float32)
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def chunked_attention(q, k, v, *, causal=True, q_chunk=512, kv_chunk=1024,
                      impl="masked"):
    """Memory-efficient attention. q,k,v: (B,S,H,hd) (kv already repeated).

    Returns (B,S,H,hd). Never materializes more than (Bq x Bk) scores.
    """
    B, S, H, hd = q.shape
    Sk = k.shape[1]
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, Sk)
    if (impl == "triangular" and causal and S == Sk and q_chunk == kv_chunk
            and S % q_chunk == 0):
        return _triangular_attention(q, k, v, q_chunk)
    # pad ragged sequences up to chunk multiples; pads are masked below
    S_real, Sk_real = S, Sk
    pad_q = (-S) % q_chunk
    pad_k = (-Sk) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        S += pad_q
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        Sk += pad_k
    scale = 1.0 / (hd ** 0.5)
    nq, nk = S // q_chunk, Sk // kv_chunk
    qs = q.reshape(B, nq, q_chunk, H, hd)
    ks = k.reshape(B, nk, kv_chunk, H, hd)
    vs = v.reshape(B, nk, kv_chunk, H, hd)

    def q_step(_, qi):
        qb = qs[:, qi]

        def kv_step(carry, kj):
            o, m, l = carry
            kb, vb = ks[:, kj], vs[:, kj]
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)
            kv_valid = kpos < Sk_real
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk)
                mask = (qpos[:, None] >= kpos[None, :]) & kv_valid[None, :]
            else:
                mask = jnp.broadcast_to(kv_valid[None, :], (q_chunk, kv_chunk))
            ob, mb, lb = _block_attn(qb, kb, vb, mask, scale)
            return _merge(o, m, l, ob, mb, lb), None

        o0 = jnp.zeros((B, H, q_chunk, hd), jnp.float32)
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        (o, m, l), _ = jax.lax.scan(kv_step, (o0, m0, l0), jnp.arange(nk))
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return None, out.transpose(0, 2, 1, 3)  # (B,q_chunk,H,hd)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))  # (nq,B,qc,H,hd)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)
    return out[:, :S_real] if pad_q else out


def _triangular_attention(q, k, v, chunk):
    """Causal attention scanning only the lower-triangular chunk pairs.

    The (qi, kj) pair list with kj <= qi is static, so the scan trip count is
    nq(nq+1)/2 and no upper-triangle FLOPs are spent (the `masked` impl
    spends 2x). Accumulators for all q rows stay live: (S,H,hd) fp32.
    """
    B, S, H, hd = q.shape
    scale = 1.0 / (hd ** 0.5)
    nq = S // chunk
    pairs = jnp.array([(i, j) for i in range(nq) for j in range(i + 1)],
                      dtype=jnp.int32)  # (npair, 2)
    qs = q.reshape(B, nq, chunk, H, hd)
    ks = k.reshape(B, nq, chunk, H, hd)
    vs = v.reshape(B, nq, chunk, H, hd)

    def step(carry, pair):
        o, m, l = carry  # (B,H,nq,chunk,hd), (B,H,nq,chunk), (B,H,nq,chunk)
        qi, kj = pair[0], pair[1]
        qb = jax.lax.dynamic_index_in_dim(qs, qi, 1, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(ks, kj, 1, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vs, kj, 1, keepdims=False)
        pos = jnp.arange(chunk)
        mask = jnp.where(qi == kj, pos[:, None] >= pos[None, :],
                         jnp.ones((chunk, chunk), bool))
        ob, mb, lb = _block_attn(qb, kb, vb, mask, scale)
        oi = jax.lax.dynamic_index_in_dim(o, qi, 2, keepdims=False)
        mi = jax.lax.dynamic_index_in_dim(m, qi, 2, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, qi, 2, keepdims=False)
        on, mn, ln = _merge(oi, mi, li, ob, mb, lb)
        o = jax.lax.dynamic_update_index_in_dim(o, on, qi, 2)
        m = jax.lax.dynamic_update_index_in_dim(m, mn, qi, 2)
        l = jax.lax.dynamic_update_index_in_dim(l, ln, qi, 2)
        return (o, m, l), None

    o0 = jnp.zeros((B, H, nq, chunk, hd), jnp.float32)
    m0 = jnp.full((B, H, nq, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, nq, chunk), jnp.float32)
    (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), pairs)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 3, 1, 4).reshape(B, S, H, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lengths):
    """Single-token grouped-query attention against a cache.

    q: (B,H,hd); k_cache/v_cache: (B,Sk,KVH,hd); lengths: (B,) valid prefix.
    Returns (B,H,hd). No KV repetition is materialized. Rows with
    ``lengths == 0`` are zero-filled (never a softmax over an all-masked
    row) — the same contract as kernels/ref.py and the pallas kernels.
    """
    B, H, hd = q.shape
    KVH = k_cache.shape[2]
    G = H // KVH
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32)
    s = s / (hd ** 0.5)
    valid = jnp.arange(k_cache.shape[1])[None, :] < lengths[:, None]  # (B,Sk)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0)
    return o.reshape(B, H, hd)
