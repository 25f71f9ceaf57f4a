"""The program's spans and compile counter (``repro.obs``), and the serve
engine's spans, counters and program names, on a tiny granite on the CPU:
off by default and inert, on they nest as the engine's phases do, and
nothing the engine computes changes either way."""
from __future__ import annotations

import contextlib
import re
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import smoke_reduce
from repro.configs.granite_3_8b import CONFIG
from repro.models.lm import LM
from repro.serve.engine import Engine, Request

#: each span the engine opens, with the span it runs inside
PARENT = {"engine.admit": None, "engine.prefill": "engine.admit",
          "engine.splice": "engine.admit", "engine.step": None,
          "engine.decode": "engine.step", "engine.tokens": "engine.step",
          "engine.account": "engine.step"}
LAYOUTS = {"contiguous": None, "paged": 8}
CHUNK = 4
#: two prompt lengths in one window: groups of 3 and 2 rows, both padded
LENS = (8, 16, 8, 16, 8)


@pytest.fixture(scope="module")
def tiny():
    lm = LM(smoke_reduce(CONFIG))
    params = jax.jit(lambda k: lm.init(k)[0])(jax.random.key(0))
    return lm, params, lm.runtime()


@pytest.fixture(scope="module", params=list(LAYOUTS))
def engine(request, tiny):
    return build(tiny, request.param)


@pytest.fixture(autouse=True)
def spans_off():
    yield
    obs.enable(False)


def requests(lens, seed: int = 0, budget: int = 5) -> list[Request]:
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new_tokens=budget,
                    tokens=rng.integers(1, CONFIG.vocab_size, n,
                                        dtype=np.int32))
            for i, n in enumerate(lens)]


def build(tiny, layout):
    lm, params, rt = tiny
    return Engine(lm, params, rt, max_batch=8, max_len=48,
                  prefill_chunk=CHUNK, page_size=LAYOUTS[layout])


def served(engine, reqs) -> dict[int, list[int]]:
    done = engine.run(reqs)
    assert len(done) == len(reqs) and not engine.active
    return {r.rid: [int(t) for t in r.out_tokens] for r in done}


def test_spans_off_record_nothing_and_open_no_annotation(engine,
                                                         monkeypatch):
    def refuse(name):
        raise AssertionError(f"TraceAnnotation({name!r}) with spans off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    before = {k: list(v) for k, v in obs.spans().items()}
    served(engine, requests(LENS))
    assert obs.spans() == before
    assert obs.span("engine.step") is obs.span("anything")


def test_spans_on_nest_as_the_engine_phases(engine, monkeypatch):
    opened = []

    def annotation(name):
        opened.append(name)
        return contextlib.nullcontext()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    steps0 = engine.steps
    obs.enable(True)
    served(engine, requests(LENS))
    rec = obs.spans()
    assert set(rec) == set(PARENT)
    assert set(opened) == {f"repro.{n}" for n in PARENT}
    for child, parent in PARENT.items():
        for a, b in rec[child]:
            assert a <= b
            if parent:
                assert any(p0 <= a and b <= p1 for p0, p1 in rec[parent]), \
                    (child, a, b)
    steps = engine.steps - steps0
    for name in ("engine.step", "engine.decode", "engine.tokens",
                 "engine.account"):
        assert len(rec[name]) == steps
    # one prefill and one splice per group: 3 and 2 rows, one group each
    assert len(rec["engine.prefill"]) == len(rec["engine.splice"]) == 2


def test_tokens_are_the_same_with_spans_on_and_off(engine):
    off = served(engine, requests(LENS, seed=1))
    obs.enable(True)
    on = served(engine, requests(LENS, seed=1))
    assert on == off


@pytest.mark.parametrize("lens, rows, padded", [((8,), 4, 3),
                                                 ((16,) * 5, 8, 3)])
def test_counters_count_prefill_rows_and_padding(engine, lens, rows,
                                                 padded):
    before = dict(engine.counters)
    served(engine, requests(lens, seed=2))
    assert engine.counters["prefill_rows"] - before["prefill_rows"] == rows
    assert (engine.counters["prefill_rows_padded"]
            - before["prefill_rows_padded"]) == padded


def test_weights_stay_where_they_are_when_the_compiler_keeps_defaults(
        tiny, engine):
    """On the CPU the decode program keeps every parameter's default
    layout: no weight is placed anew, and the engine serves from the
    caller's own arrays."""
    _, params, _ = tiny
    assert engine.counters["weights_relaid_bytes"] == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(engine.params),
                                      jax.tree.leaves(params)))


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_callers_params_serve_a_second_engine(tiny, layout):
    """An engine takes nothing from the caller's weights: after one has
    served, a second engine built from the same ``params`` serves the
    same tokens."""
    first = served(build(tiny, layout), requests(LENS, seed=4))
    assert not any(x.is_deleted() for x in jax.tree.leaves(tiny[1]))
    assert served(build(tiny, layout), requests(LENS, seed=4)) == first


#: the q, k and v projections, which a TPU's decode dots read with the
#: contraction dim minor
QKV = ("wq", "wk", "wv")


@pytest.fixture
def transposed_qkv(monkeypatch):
    """Make the engine's decode program choose the q, k and v projections
    transposed, as a TPU's compiler lays them out, where the CPU's keeps
    every default."""
    from jax.experimental.layout import Format, Layout
    from repro.serve import engine as engine_mod
    chosen = engine_mod.decode_formats

    def transposed(*args):
        formats = chosen(*args)
        attn = formats["blocks"]["pos0"]["attn"]
        for w in QKV:
            attn[w] = Format(Layout((0, 2, 1)), attn[w].sharding)
        return formats
    monkeypatch.setattr(engine_mod, "decode_formats", transposed)


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_weights_held_in_the_layout_decode_chose_serve_the_same_tokens(
        tiny, layout, request):
    """Where the decode program chooses another layout for some weights,
    the engine holds copies of those in it, counts their bytes, compiles
    decode and prefill against it, and serves the tokens an engine with
    every default serves; the caller's arrays keep their layout."""
    want = served(build(tiny, layout), requests(LENS, seed=5))
    request.getfixturevalue("transposed_qkv")
    eng = build(tiny, layout)
    mine = tiny[1]["blocks"]["pos0"]["attn"]
    held = eng.params["blocks"]["pos0"]["attn"]
    assert eng.counters["weights_relaid_bytes"] == sum(
        mine[w].nbytes for w in QKV)
    for w in QKV:
        assert held[w].format.layout.major_to_minor == (0, 2, 1)
        assert mine[w].format.layout.major_to_minor == (0, 1, 2)
        np.testing.assert_array_equal(held[w], mine[w])
    assert held["wo"] is mine["wo"]
    assert served(eng, requests(LENS, seed=5)) == want


def test_weights_held_in_another_layout_survive_the_compilation_cache(
        tiny, transposed_qkv, tmp_path):
    """An engine built when every program it needs is in JAX's persistent
    compilation cache still holds its weights in the chosen layout and
    serves the same tokens (a copying program loaded from the cache
    returns the default layout, and decode then refuses the weights)."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0.0)
    compilation_cache.reset_cache()
    try:
        jax.clear_caches()                  # every program compiles, and
        first = served(build(tiny, "paged"), requests(LENS, seed=6))
        jax.clear_caches()                  # then comes from the disk
        eng = build(tiny, "paged")
        assert served(eng, requests(LENS, seed=6)) == first
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert any(tmp_path.iterdir())
    held = eng.params["blocks"]["pos0"]["attn"]
    assert all(held[w].format.layout.major_to_minor == (0, 2, 1)
               for w in QKV)


def _module_name(jitted, *args) -> str:
    specs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         args)
    return re.search(r"module @(\w+)", jitted.lower(*specs).as_text())[1]


def test_programs_are_named_engine_decode_and_engine_prefill(engine):
    decode, seen = engine._decode, []

    def spy(*args):
        seen.append(args[:3] + args[4:])       # not the donated caches
        return decode(*args)
    engine._decode = spy
    try:
        served(engine, requests((8,), seed=3))
    finally:
        engine._decode = decode
    args = seen[0][:3] + (engine.caches,) + seen[0][3:]
    assert _module_name(decode, *args) == "jit_engine_decode"
    prefill = engine._prefill_fn(8, False)
    tokens = np.zeros((CHUNK, 8), np.int32)
    assert (_module_name(prefill, engine.params, {"tokens": tokens})
            == "jit_engine_prefill")


def test_compile_counter_names_a_freshly_jitted_function():
    counter = obs.compile_counter()
    assert obs.compile_counter() is counter
    n0, s0 = counter.programs, counter.seconds

    def obs_probe_fn(x):
        return x * 3 + 1
    t0 = time.perf_counter()
    jax.block_until_ready(jax.jit(obs_probe_fn)(np.arange(7.0)))
    new = [e for e in counter.events[n0:]
           if e.fun_name == "jit(obs_probe_fn)"]
    assert len(new) == 1 and not new[0].cached and new[0].seconds > 0
    assert t0 <= new[0].end <= time.perf_counter()
    assert counter.seconds >= s0 + new[0].seconds


def test_compile_counter_marks_the_compile_a_cache_hit_served():
    c = obs.CompileCounter()
    c._duration(c.EVENT, 0.5, fun_name="compiled")
    c._event(c.HIT)
    c._duration(c.EVENT, 0.1, fun_name="loaded")
    c._duration("/jax/some/other_event", 9.0, fun_name="ignored")
    assert [(e.fun_name, e.cached) for e in c.events] == [
        ("compiled", False), ("loaded", True)]
    assert (c.programs, c.cache_hits, c.seconds) == (2, 1, 0.6)
