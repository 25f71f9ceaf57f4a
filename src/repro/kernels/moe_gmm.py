"""Grouped expert GEMM (MoE) as a Pallas TPU kernel.

After capacity-based dispatch, each chip holds (E_local, C, d) activations
and (E_local, d, f) expert weights. The kernel runs one tiled matmul per
expert with the grid (E, C/bc, f/bf, d/bd): the d axis is innermost and
sequential with an fp32 VMEM accumulator, so every (bc x bd) @ (bd x bf)
tile is a single MXU op and partial products never touch HBM. Tile sizes
default to the MXU-native 128 and clamp to small shapes for tests.

This is the TPU replacement for a GPU "grouped GEMM" library call; the
dense-batched jnp einsum in repro.models.moe is its oracle (ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr):
    dk = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(dk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0]           # (bc, bd)
    w = w_ref[0]           # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(dk == nd - 1)
    def _fin():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret"))
def moe_gmm(x, w, *, block_c: int = 128, block_f: int = 128,
            block_d: int = 128, interpret: bool = False):
    """x: (E, C, d) dispatched tokens; w: (E, d, f) expert weights.

    Returns (E, C, f) in x.dtype (fp32 accumulation).
    """
    E, C, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert C % block_c == 0 and f % block_f == 0 and d % block_d == 0
    grid = (E, C // block_c, f // block_f, d // block_d)
    return pl.pallas_call(
        _gmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_d, block_f), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
