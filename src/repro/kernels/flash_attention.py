"""Flash attention (prefill/train) as a Pallas TPU kernel.

TPU adaptation of the GPU flash-attention idea: instead of warp-level
softmax reductions, we tile for the MXU — (block_q x head_dim) @
(head_dim x block_k) score tiles with fp32 running-max/denominator scratch
in VMEM. The grid is (batch*heads, q_blocks, kv_blocks) with the kv axis
innermost and marked "arbitrary" (sequential), so the output tile and the
(m, l) accumulators persist in VMEM across the kv sweep — the classic
revisiting trick that keeps HBM traffic at O(S) per row instead of O(S^2).

Causality is handled two ways at once:
  - whole (q, kv) blocks strictly above the diagonal are *skipped*
    (``pl.when`` guard: no MXU work, no VMEM write),
  - the diagonal block applies an element mask.

The jnp oracle lives in kernels/ref.py; repro.models.attention is the
model-side equivalent used under jit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (not causal) or (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0].astype(jnp.float32)            # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k                         # kv padding
        if causal:
            mask &= qpos >= kpos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                          # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False):
    """q, k, v: (BH, S, hd) with kv heads already repeated. Returns (BH, S, hd).

    S is padded to the block size internally; hd should be a multiple of 128
    on real TPUs (any value works in interpret mode).
    """
    BH, S, hd = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, max(S, 8))
    block_k = min(block_k, max(Sk, 8))
    pad_q = (-S) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    Sp, Skp = S + pad_q, Sk + pad_k
    grid = (BH, Sp // block_q, Skp // block_k)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / (hd ** 0.5),
                          block_q=block_q, block_k=block_k, causal=causal,
                          seq_k=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :S]
