"""Paged flash-decoding as a Pallas TPU kernel (KV gathered via page table).

Same online-softmax sweep as kernels/decode_attention.py, but the KV cache
is a shared pool of fixed-size pages — (n_pages, page_size, KVH, hd) — and
each batch row reads its blocks *through* a per-row page table instead of a
contiguous (B, S, KVH, hd) slab. The page table and row lengths ride in as
scalar-prefetch operands (PrefetchScalarGridSpec), so the block index map
itself performs the gather: grid step (b, j) fetches physical page
``page_table[b, j]``, all KV heads at once. No gathered copy of the cache
ever materializes in HBM — the DMA engine walks the table.

The block body is ``decode_attention.decode_block`` itself, so with
``page_size`` equal to the contiguous kernel's ``block_s`` outputs are
bit-identical to ``decode_attention`` over the equivalent contiguous cache
(pinned in tests/test_paged.py, interpret mode). Rows with ``length == 0``
skip every block and emit exact zeros — the same zero-fill contract
kernels/ref.py defines.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import decode_block, decode_scratch


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, page_size: int,
                         scale: float):
    del pt_ref  # consumed by the index maps; the body only needs lengths
    decode_block(len_ref[pl.program_id(0)], pl.program_id(1), q_ref, k_ref,
                 v_ref, o_ref, m_scr, l_scr, acc_scr, block_s=page_size,
                 scale=scale)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           interpret: bool = False):
    """q: (B,H,hd); k_pages/v_pages: (P, page_size, KVH, hd);
    page_table: (B, pages_per_row) int32 physical page ids;
    lengths: (B,) valid fill in tokens.

    Returns (B,H,hd). H must be a multiple of KVH (GQA groups). A row's
    logical cache is its table's pages concatenated in order; positions at
    or beyond ``lengths[b]`` are masked, so garbage in partially-filled or
    null pages never contributes. ``length == 0`` rows return exact zeros.
    """
    B, H, hd = q.shape
    page_size, KVH = k_pages.shape[1], k_pages.shape[2]
    G = H // KVH
    n_pt = page_table.shape[1]
    qg = q.reshape(B, KVH, G, hd)
    kv_spec = pl.BlockSpec((1, page_size, KVH, hd),
                           lambda b, j, pt, ln: (pt[b, j], 0, 0, 0))
    row_spec = pl.BlockSpec((1, KVH, G, hd),
                            lambda b, j, pt, ln: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=page_size,
                          scale=1.0 / (hd ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_pt),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=decode_scratch(KVH, G, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, H, hd)
