"""The chip benchmark's own checks, on the CPU at small sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

- the traffic generator: deterministic per seed, the same work in another
  order for every seed, and the stated distributions, buckets and bursts;
- the metric arithmetic on records whose answers are known;
- the trace reduction, on events recorded on a TPU v5e (``trace_sample``);
- ``run.py`` exits non-zero without a TPU and prints no result;
- the serving runner end to end at a tiny size with the chip check
  skipped: a sound run is correct, and a run with the timed path broken
  (a token altered where it is produced, a decode step that returns the
  cache unchanged, half of the batch decoded from the other half's tokens)
  is not; and the control, the reference in float8, fails the limit that
  the system passes.
"""
from __future__ import annotations

import gzip
import json
import math
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import bench  # noqa: E402
import devtrace  # noqa: E402
import flops  # noqa: E402
import traffic  # noqa: E402

CHAT = json.loads((HERE / "traffic" / "chat.json").read_text())
SERVE_CFG = json.loads(
    (HERE / "configs" / "granite-3-8b-20L-paged.json").read_text())


def metric(name, rec):
    return bench.load_module(HERE / "metrics" / f"{name}.py").read(rec)


# ------------------------------------------------------------- traffic
def test_traffic_is_fixed_by_seed_and_the_work_by_the_mix():
    a = traffic.arrivals(CHAT, 51, 2 ** 31 + 12345, 49155)
    b = traffic.arrivals(CHAT, 51, 2 ** 31 + 12345, 49155)
    c = traffic.arrivals(CHAT, 51, 7, 49155)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    # another seed: the same gaps and lengths in another order, other prompts
    assert len(a) == len(c) > 10
    gaps = lambda arr: np.sort(np.diff([0.0] + [x.due_s for x in arr]))  # noqa
    assert np.allclose(gaps(a), gaps(c))
    sizes = lambda arr: sorted((len(x.tokens), x.max_new_tokens) for x in arr)  # noqa
    assert sizes(a) == sizes(c)
    assert [len(x.tokens) for x in a] != [len(x.tokens) for x in c]
    assert [x.due_s for x in a] != [x.due_s for x in c]
    assert all(0 <= x.due_s < 51 for x in a)
    assert all(np.diff([x.due_s for x in a]) >= 0)


def test_traffic_has_the_stated_distributions():
    mix = dict(CHAT, arrivals={"rate_per_s": 40.0})
    arr = traffic.arrivals(mix, 100, 3, 49155)
    n = len(arr)
    assert abs(n - 4000) < 4 * math.sqrt(4000)
    plens = np.array([len(x.tokens) for x in arr])
    olens = np.array([x.max_new_tokens for x in arr])
    spec = CHAT["prompt_tokens"]
    buckets = sorted(spec["buckets"])
    assert set(plens) <= set(buckets)
    # lognormal rounded to the nearest bucket: each bucket takes the mass
    # between the midpoints to its neighbours
    from math import erf, log, sqrt
    cdf = lambda x: 0.5 * (1 + erf(log(x / spec["median"])  # noqa
                                   / (spec["sigma"] * sqrt(2))))
    edges = [1e-9] + [(a + b) / 2 for a, b in zip(buckets, buckets[1:])] \
        + [1e12]
    for b, lo, hi in zip(buckets, edges, edges[1:]):
        assert abs(np.mean(plens == b) - (cdf(hi) - cdf(lo))) < 0.03, b
    out = CHAT["output_tokens"]
    assert olens.min() >= out["min"] and olens.max() <= out["max"]
    assert abs(np.median(olens) - out["median"]) <= 5
    assert all(x.tokens.min() >= 1 and x.tokens.max() < 49155 for x in arr)
    assert (plens + olens).max() <= SERVE_CFG["engine"]["max_len"]


def test_traffic_bursts_keep_the_mean_rate():
    mix = dict(CHAT, arrivals={"rate_per_s": 10.0, "burst": {
        "every_s": 10, "for_s": 2, "factor": 4}})
    due = np.array([x.due_s for x in traffic.arrivals(mix, 200, 5, 100)])
    assert abs(len(due) - 2000) < 4 * math.sqrt(2000)
    in_burst = (due % 10) < 2
    base = 10 * 10 / (10 + 3 * 2)
    assert abs(in_burst.mean() - 4 * base * 2 / 100) < 0.04


# ------------------------------------------------------------- metrics
def _serve_record():
    # window [100, 110]; three requests, one never served
    return {
        "window": (100.0, 110.0), "ended": 170.0, "setup_s": 42.5,
        "requests": [
            {"due": 101.0, "sent": 101.01, "times": [101.2, 101.3, 101.5]},
            {"due": 105.0, "sent": 105.1, "times": [105.4, 109.9, 110.2]},
            {"due": 109.0, "sent": 109.5, "times": []},
        ],
        "spans": {"admit": [(101.0, 101.2), (105.1, 105.4)],
                  "step": [(101.25, 101.3), (101.4, 101.5), (109.0, 109.9)]},
        "steps": [(101.3, 1, 129, 2), (101.5, 1, 130, 2), (109.9, 1, 257, 3)],
        "compiled_in_window": 0,
        "config": SERVE_CFG,
        "device": {"kind": "TPU v5 lite"},
        "trace": None,
    }


def test_tails_are_taken_over_all_requests_and_gaps_in_the_window():
    rec = _serve_record()
    # ttft: 0.2, 0.4, and the unserved one at 170 - 109 = 61 s
    ttft = [0.2, 0.4, 61.0]
    for name, q in (("ttft_p90_ms", 90), ("ttft_p95_ms.serve", 95)):
        assert metric(name, rec) == pytest.approx(
            1000 * np.percentile(ttft, q)), name
    # gaps ending inside the window: 0.1, 0.2, 4.5 (110.2 is outside)
    for name, q in (("itl_p50_ms", 50), ("itl_p99_ms.serve", 99)):
        assert metric(name, rec) == pytest.approx(
            1000 * np.percentile([0.1, 0.2, 4.5], q)), name
    assert metric("setup_s", rec) == 42.5
    assert metric("gen_late_ms.serve", rec) == pytest.approx(
        1000 * np.percentile([0.01, 0.1, 0.5], 95))
    assert metric("admit_ms.serve", rec) == pytest.approx(1000 * 0.5 / 2)
    assert metric("decode_step_ms.serve", rec) == pytest.approx(
        1000 * (0.05 + 0.1 + 0.9) / 3)
    assert metric("compiles_in_window.serve", rec) == 0
    for name in ("decode_device_ms.serve", "decode_mfu.serve",
                 "decode_roofline.serve", "device_idle.serve"):
        assert metric(name, rec) is None      # nothing traced: no reading


def test_mfu_and_roofline_from_shapes():
    cfg = SERVE_CFG
    d, L, H, KVH, hd, ff, V = 4096, 20, 32, 8, 128, 12800, 49155
    n_mm = L * (d * (H + 2 * KVH) * hd + H * hd * d + 3 * d * ff) + d * V
    assert flops.matmul_params(cfg) == n_mm
    rows, attended = 16, 16 * 700
    ops = 2 * n_mm * rows + 4 * L * H * hd * attended
    wbytes = 2 * (n_mm + (2 * L + 1) * d)
    kv = 2 * L * KVH * hd * 2 * (attended + rows)
    assert flops.decode_step(cfg, rows, attended) == (ops, wbytes + kv)
    rec = _serve_record()
    rec["steps"] = [(105.0, rows, attended, 90), (106.0, rows, attended, 90)]
    rec["trace"] = {"busy_s": 8.0, "window_s": 10.0,
                    "busy_in_s": {"step": 0.05}, "span_count": {"step": 2}}
    assert metric("decode_device_ms.serve", rec) == pytest.approx(25.0)
    assert metric("decode_mfu.serve", rec) == pytest.approx(
        100 * 2 * ops / (0.05 * 197e12))
    least = max(ops / 197e12, (wbytes + kv) / 819e9)
    assert metric("decode_roofline.serve", rec) == pytest.approx(
        100 * 2 * least / 0.05)
    assert metric("device_idle.serve", rec) == pytest.approx(20.0)


def test_peaks_refuse_an_unknown_chip():
    import peaks
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# --------------------------------------------------------------- trace
def _mask_busy(ab, lo, hi):
    """Busy time of intervals ``ab`` (n, 2) by a 10 ns grid: independent of
    devtrace's merging."""
    grid = np.zeros(int((hi - lo) // 10) + 1, bool)
    for a, b in ab[(ab[:, 1] > lo) & (ab[:, 0] < hi)].clip(lo, hi):
        grid[int((a - lo) // 10):int((b - lo) // 10)] = True
    return grid.sum() * 10


def test_trace_reduction_on_a_chip_trace():
    raw = json.loads(gzip.decompress(
        (HERE / "tests" / "trace_sample.json.gz").read_bytes()))
    raw["ops"] = {int(k): v for k, v in raw["ops"].items()}
    raw["modules"] = {int(k): v for k, v in raw["modules"].items()}
    got = devtrace.reduce(raw)
    (w0, w1), = [(a, b) for k, a, b in raw["spans"] if k == "window"]
    assert got["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    ops = {c: np.array([iv[:2] for iv in ev], float).reshape(-1, 2)
           for c, ev in raw["ops"].items()}
    chips = [c for c, ab in ops.items()
             if np.any((ab[:, 1] > w0) & (ab[:, 0] < w1))]
    busy = np.mean([_mask_busy(ops[c], w0, w1) for c in chips])
    assert got["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-3, abs=1e-6)
    steps = [(a, b) for k, a, b in raw["spans"] if k == "step"]
    inside = 0.0
    for c in chips:
        for a, b in steps:
            inside += _mask_busy(ops[c], max(a, w0), min(b, w1))
    assert got["busy_in_s"]["step"] == pytest.approx(
        inside / len(chips) * 1e-9, rel=1e-3, abs=1e-6)
    idle = sum(v for _, v in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
    assert 0 < got["busy_s"] <= got["window_s"]


def test_reduction_finds_nothing_without_a_window_or_a_chip():
    assert devtrace.reduce({"ops": {}, "modules": {}, "spans": []}) is None
    assert devtrace.reduce({"ops": {}, "modules": {},
                            "spans": [("window", 0, 10)]}) is None


# ------------------------------------------------------------- run.py
def test_run_exits_nonzero_without_a_tpu():
    r = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "granite.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "Nothing was run" in r.stderr


# --------------------------------------------- the serving runner, tiny
# a small model at which the control (the reference in float8) reads far
# above the cell's limit while the system stays far below it (CPU
# readings: system 0.008-0.016, control 0.42-0.64 over four seeds)
TINY = dict(SERVE_CFG, hidden_size=256, intermediate_size=768,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=2, vocab_size=1024,
            engine={"max_batch": 8, "max_len": 128, "page_size": 16,
                    "prefill_chunk": 2})
TINY_MIX = {"arrivals": {"rate_per_s": 20.0},
            "prompt_tokens": {"median": 40, "sigma": 0.7,
                              "buckets": [32, 64]},
            "output_tokens": {"median": 24, "sigma": 0.3, "min": 16,
                              "max": 32},
            "sizes_seed": 1}
TINY_SEED = 2 ** 31 + 11
TINY_SECONDS = 0.5             # seven requests, as the seed draws them


@pytest.fixture(scope="module")
def tiny():
    """One engine for every tiny run, built from ``TINY_SEED`` as a run
    builds it, and the reference's judges made once: the runs differ only
    in what breaks the timed path, so their programs compile once."""
    import jax
    import serve
    cfg = dict(TINY, check=SERVE_CFG["check"])
    ref = bench.load_module(HERE / "configs" / TINY["reference"])
    engine = serve.build(cfg, ref, bench.prng_key(TINY_SEED),
                         jax.devices()[0], TINY_MIX["prompt_tokens"]["buckets"])
    judges = {}
    make = serve.judge_fn

    def judge_fn(cfg, ref, quant=None):
        if quant not in judges:
            judges[quant] = make(cfg, ref, quant)
        return judges[quant]
    return cfg, ref, engine, judge_fn


def _decode_fault(engine, fault):
    import jax
    lm, rt = engine.lm, engine.rt
    good = engine._decode
    if fault == "token":
        # a token altered where it is produced: every row's logits favour
        # one fixed token
        def bad(*args):
            logits, caches = good(*args)
            return logits.at[:, 7].add(100.0), caches
    elif fault == "state":
        # the step returns the cache it was given: no new key or value
        bad = jax.jit(lambda p, t, l, c, pt: (
            lm.decode(p, rt, t, l, c, page_table=pt)[0], c))
    else:
        # half of the batch left out: its rows decode the first half's
        # tokens
        def bad(p, t, l, c, pt):
            half = t.shape[0] // 2
            return good(p, t.at[half:].set(t[:half]), l, c, pt)
    engine._decode = bad


def _tiny_run(monkeypatch, tiny, fault=None):
    import jax
    import serve
    cfg, _, engine, judge_fn = tiny
    good = engine._decode
    if fault:
        _decode_fault(engine, fault)
    monkeypatch.setattr(serve, "build", lambda *a, **k: engine)
    monkeypatch.setattr(serve, "judge_fn", judge_fn)
    cell = bench.Cell(name="tiny", chips=1, config=cfg,
                      config_dir=HERE / "configs", traffic=TINY_MIX,
                      seed=TINY_SEED, seconds=TINY_SECONDS, trace=False,
                      devices=jax.devices()[:1],
                      started=bench.process_start())
    try:
        return serve.run(cell)
    finally:
        engine._decode = good


def test_a_sound_run_is_correct(monkeypatch, tiny):
    rec = _tiny_run(monkeypatch, tiny)
    assert rec["failed"] == 0 and rec["attempted"] > 3
    assert rec["correct"], rec["checks"]


@pytest.mark.parametrize("fault", ["token", "state", "half"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, tiny, fault):
    rec = _tiny_run(monkeypatch, tiny, fault)
    assert not rec["correct"], rec["checks"]


def test_the_control_fails_the_limit_the_system_passes(monkeypatch, tiny):
    import serve
    cfg, ref, engine, judge_fn = tiny
    monkeypatch.setattr(serve, "judge_fn", judge_fn)
    t0 = time.perf_counter()
    served = serve.requests(TINY_MIX, TINY_SECONDS, TINY_SEED,
                            cfg["vocab_size"], t0)
    serve.open_loop(engine, served, t0, TINY_SECONDS, bench.Spans())
    key = bench.prng_key(TINY_SEED)
    limit = SERVE_CFG["check"]["max_logit_gap"]
    sound = serve.widest_gap(cfg, ref, key, served, TINY_MIX, TINY_SEED)
    control = serve.widest_gap(cfg, ref, key, served, TINY_MIX, TINY_SEED,
                               "fp8")
    assert sound <= limit < control, (sound, limit, control)
