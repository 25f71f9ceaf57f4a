"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

Each test compiles one program for one chip of a described ``v5e:2x2``
topology, at real widths, with the TPU compiler installed beside JAX. A
kernel that interpret mode runs but the chip's compiler refuses (a block
not aligned to the (8, 128) tiling, too much fast memory) fails here, at
no chip time. Nothing runs, so nothing here says anything about results
or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a decision made at
import would give pytest-xdist's workers different tests to collect.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.granite_3_8b import CONFIG as GRANITE
from repro.configs.mamba2_1_3b import CONFIG as MAMBA2
from repro.models.lm import LM

#: granite-3-8b at its published widths; 2 of its 40 layers keep each
#: engine compile to seconds (the scanned layer body is the same program)
GRANITE_2L = dataclasses.replace(GRANITE, n_layers=2)
SLOTS, MAX_LEN, PAGE = 16, 2048, 128
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` / ``spec(tree)``: abstract values placed on
    one described chip."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def make(shape_or_tree, dtype=None):
        if dtype is not None:
            return jax.ShapeDtypeStruct(shape_or_tree, dtype, sharding=one)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            shape_or_tree)
    return make


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _is_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------- kernels
def test_flash_attention_compiles(spec):
    from repro.kernels.flash_attention import flash_attention
    hd = GRANITE.head_dim
    qkv = spec((GRANITE.n_heads, MAX_LEN, hd), BF16)
    c = _compile(flash_attention, qkv, qkv, qkv)
    assert _is_kernel(c)


def test_decode_attention_compiles(spec):
    from repro.kernels.decode_attention import decode_attention
    H, KVH, hd = GRANITE.n_heads, GRANITE.n_kv_heads, GRANITE.head_dim
    cache = spec((SLOTS, MAX_LEN, KVH, hd), BF16)
    c = _compile(decode_attention, spec((SLOTS, H, hd), BF16), cache, cache,
                 spec((SLOTS,), I32))
    assert _is_kernel(c)


def test_paged_decode_attention_compiles(spec):
    from repro.kernels.paged_decode_attention import paged_decode_attention
    H, KVH, hd = GRANITE.n_heads, GRANITE.n_kv_heads, GRANITE.head_dim
    pool = spec((1 + SLOTS * MAX_LEN // PAGE, PAGE, KVH, hd), BF16)
    c = _compile(paged_decode_attention, spec((SLOTS, H, hd), BF16), pool,
                 pool, spec((SLOTS, MAX_LEN // PAGE), I32),
                 spec((SLOTS,), I32))
    assert _is_kernel(c)


def test_ssd_scan_compiles(spec):
    """mamba2-1.3b: 64 heads of 64, state 128, chunk 256."""
    from repro.kernels.ssd_scan import ssd_scan
    cfg = MAMBA2
    nh, hp, ds = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.d_state
    assert (nh, hp, ds, cfg.ssm_chunk) == (64, 64, 128, 256)
    S = 8 * cfg.ssm_chunk
    bc = spec((1, S, cfg.ssm_groups, ds), BF16)
    c = _compile(lambda x, dt, a, b, cc: ssd_scan(x, dt, a, b, cc,
                                                  chunk=cfg.ssm_chunk),
                 spec((1, S, nh, hp), BF16), spec((1, S, nh), F32),
                 spec((nh,), F32), bc, bc)
    assert _is_kernel(c)


def test_moe_gmm_compiles(spec):
    """One tile set: 8 experts x 512 tokens, 4096 -> 1536."""
    from repro.kernels.moe_gmm import moe_gmm
    c = _compile(moe_gmm, spec((8, 512, 4096), BF16),
                 spec((8, 4096, 1536), BF16))
    assert _is_kernel(c)


# ------------------------------------------------- the engine's programs
@pytest.fixture(scope="module")
def granite(spec):
    lm = LM(GRANITE_2L)
    return lm, lm.runtime(), spec(lm.init(None, abstract=True)[0])


def _whole_cache_writes(compiled, caches):
    """Instructions of ``compiled`` that copy, or update a slice of, a
    buffer shaped as a whole stacked cache leaf: each one rewrites every
    layer's cache, where a decode step changes one token per row."""
    shapes = {f"bf16[{','.join(map(str, x.shape))}]"
              for x in jax.tree.leaves(caches)}
    op = re.compile(r"=\s*(\S+?\])(\{[^}]*\})?\s+"
                    r"(copy|copy-start|dynamic-update-slice)\(")
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if (m := op.search(line)) and m.group(1) in shapes]


def _check_in_place(compiled, caches):
    """The step's temporaries stay under a quarter of the cache (measured
    at 2 layers: 0.034 GB against 0.27 GB, logits and per-layer
    activations; writing a second cache beside the first took 0.40-0.67
    GB), and no instruction rewrites a whole cache leaf."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(caches))
    assert temp < pool_bytes / 4, (temp, pool_bytes)
    assert not _whole_cache_writes(compiled, caches)


def test_engine_contiguous_decode_compiles(granite, spec):
    """The contiguous step writes one token per row and layer into the
    donated cache, in place."""
    lm, rt, params = granite
    caches = lm.cache_shapes(SLOTS, MAX_LEN)
    c = _compile(lambda p, t, l, c: lm.decode(p, rt, t, l, c),
                 params, spec((SLOTS, 1), I32), spec((SLOTS,), I32),
                 spec(caches), donate_argnums=(3,))
    logits = c.out_info[0]
    assert logits.shape == (SLOTS, GRANITE.vocab_padded)
    _check_in_place(c, caches)


def test_engine_paged_decode_compiles_within_memory(granite, spec):
    """The paged step scatters one token per row and layer into the
    donated page pool, in place."""
    lm, rt, params = granite
    n_pages = 1 + SLOTS * MAX_LEN // PAGE
    pool = lm.paged_cache_shapes(SLOTS, n_pages, PAGE)
    c = _compile(lambda p, t, l, c, pt: lm.decode(p, rt, t, l, c,
                                                  page_table=pt),
                 params, spec((SLOTS, 1), I32), spec((SLOTS,), I32),
                 spec(pool), spec((SLOTS, MAX_LEN // PAGE), I32),
                 donate_argnums=(3,))
    _check_in_place(c, pool)


def test_engine_prefill_compiles(granite, spec):
    lm, rt, params = granite
    c = _compile(lambda p, b: lm.prefill(p, rt, b), params,
                 {"tokens": spec((4, 1024), I32)})
    logits = c.out_info[0]
    assert logits.shape == (4, GRANITE.vocab_padded)
