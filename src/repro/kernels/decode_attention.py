"""Flash-decoding as a Pallas TPU kernel (single-token GQA vs KV cache).

GPU flash-decoding splits the KV cache across SMs and combines partial
softmaxes. The TPU analogue: the grid is (batch, kv_blocks) with the
kv-block axis innermost/sequential; the (KVH, G, hd) output tile for one
row plus its fp32 (m, l) accumulators stay resident in VMEM across the
sweep. GQA is exploited directly — queries arrive grouped per kv head, so
no repeated-KV materialization ever touches HBM. Length masking uses the
per-row cache fill (continuous batching: every row differs), which rides
in as a scalar-prefetch operand.

A K/V block holds every KV head of ``block_s`` positions, (block_s, KVH,
hd): its last two dimensions are the array's own, which is what the TPU's
(8, 128) tiling rule accepts for any KVH and head_dim. A per-head block
(KVH extent 1) is refused by the compiler.

Across-chip sequence sharding of the same computation lives in
repro.parallel.collectives (shard_map + psum combine); this kernel is the
per-shard body's TPU-optimal form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def decode_block(length, sj, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                 acc_scr, *, block_s: int, scale: float):
    """Online-softmax step over one (block_s, KVH, hd) K/V block for every
    KV head of one row. Shared by the contiguous and the paged kernel, so
    the two run the same float op sequence over the same blocks."""
    ns = pl.num_programs(1)
    n_kv = k_ref.shape[2]

    @pl.when(sj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(sj * block_s < length)
    def _block():
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)            # (G, hd)
            k = k_ref[0, :, h].astype(jnp.float32)         # (bs, hd)
            v = v_ref[0, :, h].astype(jnp.float32)         # (bs, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            pos = sj * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1)
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(sj == ns - 1)
    def _fin():
        for h in range(n_kv):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, h] = (acc_scr[h] / l[:, None]).astype(o_ref.dtype)


def decode_scratch(n_kv: int, groups: int, hd: int):
    return [pltpu.VMEM((n_kv, groups), jnp.float32),
            pltpu.VMEM((n_kv, groups), jnp.float32),
            pltpu.VMEM((n_kv, groups, hd), jnp.float32)]


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, block_s: int, scale: float):
    decode_block(len_ref[pl.program_id(0)], pl.program_id(1), q_ref, k_ref,
                 v_ref, o_ref, m_scr, l_scr, acc_scr, block_s=block_s,
                 scale=scale)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, block_s: int = 512,
                     interpret: bool = False):
    """q: (B,H,hd); k_cache/v_cache: (B,S,KVH,hd); lengths: (B,) valid fill.

    Returns (B,H,hd). H must be a multiple of KVH (GQA groups).
    """
    B, H, hd = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    block_s = min(block_s, max(S, 8))
    pad_s = (-S) % block_s
    if pad_s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    Sp = S + pad_s
    qg = q.reshape(B, KVH, G, hd)
    kv_spec = pl.BlockSpec((1, block_s, KVH, hd), lambda b, j, ln: (b, j, 0, 0))
    row_spec = pl.BlockSpec((1, KVH, G, hd), lambda b, j, ln: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s,
                          scale=1.0 / (hd ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Sp // block_s),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=decode_scratch(KVH, G, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, H, hd)
