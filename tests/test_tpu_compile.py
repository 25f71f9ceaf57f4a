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
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest

from repro.configs.granite_3_8b import CONFIG as GRANITE
from repro.configs.mamba2_1_3b import CONFIG as MAMBA2
from repro.models.lm import LM
from repro.serve.engine import decode_formats, decode_program, relaid_bytes

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


class Decode(NamedTuple):
    default: object    # compiled with the parameters' default layouts
    held: object       # the parameters' specs, in their default formats
    formats: object    # what ``decode_formats`` chose for them
    engine: object     # compiled against ``formats``, as the engine jits it
    caches: object


@pytest.fixture(scope="module")
def decode(granite, spec):
    """Per cache kind, the engine's decode step, its greedy tokens taken
    inside it, compiled as the engine compiles it: the formats the
    compiler chooses for the parameters, then the program against them;
    beside it the program with every default layout."""
    lm, rt, params = granite
    toks, lengths = spec((SLOTS,), I32), spec((SLOTS,), I32)
    cache = spec(lm.cache_shapes(SLOTS, MAX_LEN))
    pool = spec(lm.paged_cache_shapes(SLOTS, 1 + SLOTS * MAX_LEN // PAGE,
                                      PAGE))
    kinds = {
        "contiguous": (decode_program(lm, rt, paged=False),
                       (toks, lengths, cache)),
        "paged": (decode_program(lm, rt, paged=True),
                  (toks, lengths, pool, spec((SLOTS, MAX_LEN // PAGE), I32))),
    }
    out = {}
    for kind, (fn, args) in kinds.items():
        default = _compile(fn, params, *args, donate_argnums=(3,))
        held = jax.tree.map(
            lambda x, f: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=f),
            params, default.input_formats[0][0])
        formats = decode_formats(fn, held, *args)
        engine = _compile(fn, params, *args, donate_argnums=(3,),
                          in_shardings=(formats,) + (None,) * len(args))
        out[kind] = Decode(default, held, formats, engine, args[2])
    return out


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


def _check_outputs(compiled):
    """The step returns each row's next token beside its logits."""
    toks, logits = compiled.out_info[:2]
    assert (toks.shape, toks.dtype) == ((SLOTS,), I32)
    assert logits.shape == (SLOTS, GRANITE.vocab_padded)


def test_engine_contiguous_decode_compiles(decode):
    """The contiguous step writes one token per row and layer into the
    donated cache, in place."""
    c = decode["contiguous"]
    _check_outputs(c.engine)
    _check_in_place(c.engine, c.caches)


def test_engine_paged_decode_compiles_within_memory(decode):
    """The paged step scatters one token per row and layer into the
    donated page pool, in place."""
    c = decode["paged"]
    _check_outputs(c.engine)
    _check_in_place(c.engine, c.caches)


_INSTRUCTION = re.compile(r"%\S+\s*=\s*(.*?)\s([a-z][\w-]*)\(")


def _weight_relayouts(compiled, params):
    """Instructions of ``compiled`` that copy or transpose a buffer shaped
    as one layer's slice of a stacked weight matrix: each moves weights
    and computes nothing."""
    hlo = {"bfloat16": "bf16", "float32": "f32"}
    shapes = {f"{hlo[x.dtype.name]}[1,{','.join(map(str, x.shape[1:]))}]"
              for x in jax.tree.leaves(params["blocks"]) if x.ndim == 3}
    found = []
    for line in compiled.as_text().splitlines():
        m = _INSTRUCTION.search(line)
        if (m and m.group(2) in ("copy", "copy-start", "transpose")
                and shapes & set(re.findall(r"\w+\[[0-9,]*\]", m.group(1)))):
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("kind", ["contiguous", "paged"])
def test_engine_decode_reads_weights_where_they_lie(decode, kind):
    """With the parameters in their default layouts the step slices each
    layer's q, k and v projections and copies them into the layout its
    head-major dots read; the engine holds exactly those three in that
    layout instead (100,663,296 bytes at 2 layers), and its step then
    copies no layer's weights."""
    c = decode[kind]
    assert len(_weight_relayouts(c.default, c.held)) == 3
    assert _weight_relayouts(c.engine, c.held) == []
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(c.held)[0]]
    moved = [path for path, x, f in zip(paths, jax.tree.leaves(c.held),
                                        jax.tree.leaves(c.formats))
             if x.format != f]
    assert moved == [f"['blocks']['pos0']['attn']['{w}']"
                     for w in ("wk", "wq", "wv")]
    assert relaid_bytes(c.held, c.formats) == 100_663_296


@pytest.fixture(scope="module")
def prefill(granite, spec, decode):
    """Prefill, 4 x 1024, compiled with the default layouts and against
    the formats the paged decode step chose, as the engine compiles it."""
    lm, rt, params = granite
    batch = {"tokens": spec((4, 1024), I32)}

    def fn(p, b):
        return lm.prefill(p, rt, b)
    return (_compile(fn, params, batch),
            _compile(fn, params, batch,
                     in_shardings=(decode["paged"].formats, None)))


def test_engine_prefill_compiles(prefill):
    logits = prefill[1].out_info[0]
    assert logits.shape == (4, GRANITE.vocab_padded)


def test_engine_prefill_takes_the_decode_formats_at_no_memory_cost(
        granite, prefill):
    """Prefill compiled against the decode step's formats needs no more
    temporaries, and copies no layer's weights either."""
    default, engine = prefill
    assert _weight_relayouts(engine, granite[2]) == []
    assert (engine.memory_analysis().temp_size_in_bytes
            <= default.memory_analysis().temp_size_in_bytes)
