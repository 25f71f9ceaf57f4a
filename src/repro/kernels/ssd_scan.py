"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

The SSD dual form maps perfectly onto the MXU: within a chunk of Q tokens
the recurrence is an attention-like pair of (Q x ds) @ (ds x Q) and
(Q x Q) @ (Q x hp) matmuls under a causal decay mask L; across chunks only
an (hp x ds) state matrix flows. We tile the grid as
(batch, head blocks, chunks) with chunks innermost/sequential: the running
states of a block's heads live in a VMEM scratch across the chunk sweep —
the inter-chunk pass costs no HBM traffic at all (vs. the GPU
implementation's inter-block state materialization), while every
intra-chunk op is MXU-shaped.

Layout for the TPU's (8, 128) tiling: x and y blocks carry ``HEAD_BLOCK``
heads, (Q, heads, hp); dt is passed head-major (B, nh, S) and B/C
group-major (B, ng, S, ds), so every block's last two dimensions are
tileable or whole, and A sits whole in SMEM. The prefix sum of the decay
is a lower-triangular matmul, since the kernel language has no cumsum.

fp32 throughout the state path (matching the model's ssd_chunked), bf16
tolerated on the x/B/C inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: heads per grid step: a multiple of 8 keeps the x/y blocks' (heads, hp)
#: tail tileable; configs whose head count it does not divide take all heads
HEAD_BLOCK = 8


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr,
                *, chunk: int, rep: int):
    hj = pl.program_id(1)
    cj = pl.program_id(2)
    nc = pl.num_programs(2)
    hb = x_ref.shape[2]

    @pl.when(cj == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = idx >= jdx
    tril = causal.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    for i in range(hb):
        h = hj * hb + i
        x = x_ref[0, :, i].astype(jnp.float32)        # (Q, hp)
        dt = dt_ref[0, i:i + 1].T                     # (Q, 1)
        A = a_ref[h]                                  # scalar for this head
        Bm = b_ref[0, h // rep].astype(jnp.float32)   # (Q, ds)
        Cm = c_ref[0, h // rep].astype(jnp.float32)   # (Q, ds)

        dA = dt * A                                   # (Q, 1) <= 0
        cs = jnp.dot(tril, dA, precision=hi,
                     preferred_element_type=jnp.float32)   # (Q, 1)
        cs_last = cs[chunk - 1:]                      # (1, 1)
        # intra-chunk: attention-like dual form with decay mask
        seg = (jnp.broadcast_to(cs, (chunk, chunk))
               - jnp.broadcast_to(cs.T, (chunk, chunk)))
        L = jnp.where(causal, jnp.exp(seg), 0.0)
        scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32) * L
        xdt = x * dt                                  # (Q, hp)
        y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # inter-chunk: contribution of the carried state
        state = state_scr[i]                          # (hp, ds)
        y = y + jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32
                                    ) * jnp.exp(cs)
        y_ref[0, :, i] = y.astype(y_ref.dtype)
        # state update: decay + B^T (decay_out * xdt)
        decay_out = jnp.exp(cs_last - cs)             # (Q, 1)
        # the chunk's total decay as a (1, ds) row, by a matmul: Mosaic
        # cannot broadcast a (1, 1) value along both axes
        total = jax.lax.dot_general(
            dA, jnp.ones((chunk, state.shape[1]), jnp.float32),
            (((0,), (0,)), ((), ())), precision=hi,
            preferred_element_type=jnp.float32)       # (1, ds)
        state_scr[i] = state * jnp.exp(total) + jax.lax.dot_general(
            xdt * decay_out, Bm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(cj == nc - 1)
    def _fin():
        st_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bg, Cg, *, chunk: int = 128, interpret: bool = False):
    """x: (B,S,nh,hp); dt: (B,S,nh) f32; A: (nh,) f32; Bg/Cg: (B,S,ng,ds).

    Returns (y (B,S,nh,hp) fp32, final_state (B,nh,hp,ds) fp32).
    S must be a multiple of ``chunk``; ng must divide nh.
    """
    B, S, nh, hp = x.shape
    ng, ds = Bg.shape[-2:]
    assert S % chunk == 0 and nh % ng == 0
    nc = S // chunk
    hb = HEAD_BLOCK if nh % HEAD_BLOCK == 0 else nh
    # dt, B and C are small beside x: head- and group-major copies give
    # them blocks whose last two dims are (8, 128)-tileable
    dt_t = dt.astype(jnp.float32).transpose(0, 2, 1)      # (B, nh, S)
    b_t = Bg.transpose(0, 2, 1, 3)                        # (B, ng, S, ds)
    c_t = Cg.transpose(0, 2, 1, 3)
    head_spec = pl.BlockSpec((1, chunk, hb, hp), lambda b, h, c: (b, c, h, 0))
    group_spec = pl.BlockSpec((1, ng, chunk, ds), lambda b, h, c: (b, 0, c, 0))
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, rep=nh // ng),
        grid=(B, nh // hb, nc),
        in_specs=[
            head_spec,
            pl.BlockSpec((1, hb, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            group_spec,
            group_spec,
        ],
        out_specs=[
            head_spec,
            pl.BlockSpec((1, hb, hp, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, nh, hp), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, hp, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, hp, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt_t, A.astype(jnp.float32), b_t, c_t)
    return y, state
