"""The serving runner: an open loop of requests over the system's
continuous-batching ``Engine``, and the check of what it served.

Set-up makes the weights on the chip from the seed in one jitted call,
builds the engine the configuration names, and warms up exactly the shapes
the traffic uses: one prefill per prompt bucket at the engine's prefill
batch, every admission group size, the per-page splices and the decode
step. Then the window opens.

The window is one thread: requests become due on the mix's schedule
(``traffic.py``) whether or not earlier ones are done; between engine
calls the loop moves every due request to the queue, admits as many as
there are free slots (``Engine.admit_many``, which prefills and returns the
first token to the host), and runs one decode step (``Engine.step``, which
returns every active row's next token to the host). Each request is timed
from when it was due. After the window closes no request arrives; the loop
goes on until every request that was due has all its tokens, at most a
minute, so that a late answer is counted late and not lost.

Then the peak memory is read, the system's state is freed, and the plain
reference (``configs/<...>.reference.py``) is run over a sample of served
requests, drawn from the seed with the longest among them: at every
position of prompt plus served tokens, the gap by which the served token's
reference logit lies below the reference's best. The widest gap is held to
the configuration's limit.
"""
from __future__ import annotations

import gc
import math
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

import bench
import flops
import traffic as traffic_gen

#: rows of the reference per call, so that its scores and logits fit
REF_ROWS = 2


def model_config(cfg: dict):
    """The system's ``ModelConfig`` for a dense GQA configuration file."""
    from repro.configs.base import ModelConfig
    if (cfg["hidden_act"] != "silu" or cfg.get("attention_bias")
            or cfg.get("mlp_bias")):
        raise ValueError("the system runs SwiGLU decoders without biases")
    return ModelConfig(
        name=cfg.get("name", "model"), family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", 0), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"])


def program_params(cfg: dict, w: dict, vocab_padded: int) -> dict:
    """The reference's weights ``w`` in the system's layout, with the
    scalar multipliers folded into the matrices they scale: the system
    computes no multiplier, and each is linear, so the system then computes
    the published model up to the rounding of the folded weights to the
    served dtype. The padded vocabulary rows are zero (their logits 0)."""
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["torch_dtype"])
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    res = cfg["residual_multiplier"]

    def scaled(a, c):
        return (a.astype(jnp.float32) * c).astype(dtype)

    pad = vocab_padded - cfg["vocab_size"]
    head = w["embed"].T if cfg["tie_word_embeddings"] else w["head"]
    lw = w["layers"]
    return {
        "embed": jnp.pad(scaled(w["embed"], cfg["embedding_multiplier"]),
                         ((0, pad), (0, 0)))[None],
        "head": jnp.pad(scaled(head, 1.0 / cfg["logits_scaling"]),
                        ((0, 0), (0, pad)))[None],
        "final_norm": w["final_norm"],
        "blocks": {"pos0": {
            "norm1": lw["norm1"],
            # the system scales scores by 1/sqrt(hd); the model by the
            # attention multiplier
            "attn": {"wq": scaled(lw["wq"],
                                  cfg["attention_multiplier"] * math.sqrt(hd)),
                     "wk": lw["wk"], "wv": lw["wv"],
                     "wo": scaled(lw["wo"], res)},
            "norm2": lw["norm2"],
            "mlp": {"w_in": lw["w_up"], "w_gate": lw["w_gate"],
                    "w_out": scaled(lw["w_down"], res)},
        }},
    }


def make_params(cfg: dict, ref, lm, key):
    """The system's weights, made on the chip in one jitted call."""
    import jax
    make = jax.jit(lambda k: program_params(cfg, ref.init(cfg, k),
                                            lm.cfg.vocab_padded))
    want = jax.eval_shape(lambda k: lm.init(k)[0], key)
    got = jax.eval_shape(make, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the weights do not fit the system's layout")
    params = make(key)
    jax.block_until_ready(params)
    return params


@dataclass
class Served:
    """One request of the window, and what the engine did with it."""
    due: float                      # perf_counter at which it is due
    plen: int
    budget: int
    req: object                     # the engine's Request
    sent: float | None = None       # when the loop queued it
    times: list = field(default_factory=list)   # each token on the host


def warm_up(engine, buckets, vocab: int) -> None:
    """Every shape the window will use: one prefill per bucket at the full
    prefill batch, each smaller admission group once, and decode steps."""
    from repro.serve.engine import Request
    chunk = engine.prefill_chunk or 1
    groups = [[b] * chunk for b in buckets]
    groups += [[buckets[0]] * k for k in range(1, chunk)]
    rid = -1
    for lens in groups:
        reqs = []
        for n in lens:
            reqs.append(Request(rid=rid, max_new_tokens=3,
                                tokens=np.arange(n, dtype=np.int32) % vocab))
            rid -= 1
        if len(engine.admit_many(reqs)) != len(reqs):
            raise RuntimeError("warm-up requests were not admitted")
        while engine.active:
            engine.step()


def open_loop(engine, served: list[Served], t0: float, seconds: float,
              spans: bench.Spans, window_note=None,
              drain_s: float = 60.0) -> list:
    """Drive the engine over ``served`` (sorted by due); returns the decode
    steps as (end time, rows, positions attended, cache pages held)."""
    todo = deque(served)
    queue: deque[Served] = deque()
    live: dict[int, Served] = {}
    steps = []
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if window_note is not None and now >= t_end:
            window_note()
            window_note = None
        if now > t_end + drain_s:
            break
        while todo and todo[0].due <= now:
            s = todo.popleft()
            s.sent = now
            queue.append(s)
        if queue and engine.free:
            take = [queue.popleft()
                    for _ in range(min(len(queue), len(engine.free)))]
            with spans.span("admit"):
                admitted = engine.admit_many([s.req for s in take])
            t = time.perf_counter()
            got = {id(r) for r in admitted}
            for s in take:
                if id(s.req) in got:
                    s.times.append(t)
                    live[id(s.req)] = s
        if engine.active:
            before = [live[id(r)] for r in engine.active.values()]
            attended = sum(s.plen + len(s.times) for s in before)
            with spans.span("step"):
                finished = engine.step()
            t = time.perf_counter()
            for s in before:
                s.times.append(t)
            steps.append((t, len(before), attended, pages_held(engine)))
            for r in finished:
                live.pop(id(r))
        elif not queue:
            if not todo:
                break
            wait = todo[0].due - time.perf_counter()
            if wait > 0:
                with spans.span("wait"):
                    time.sleep(wait)
    if window_note is not None:
        window_note()
    return steps


def pages_held(engine) -> int:
    """Pages of the paged cache that requests hold (0 for a contiguous
    cache)."""
    return engine.pager.used_pages if engine.pager is not None else 0


def judge_fn(cfg: dict, ref, quant=None):
    """jit(weights, tokens (B,S), picked (B,S)) -> the reference's best
    logit minus its logit of ``picked`` at every position, in units of the
    standard deviation of the reference's logits over the vocabulary there
    (random weights set no natural scale for a logit). With ``quant``,
    ``picked`` is ignored and the tokens the lowered-precision reference
    puts first are judged instead (the control)."""
    import jax
    import jax.numpy as jnp

    def judge(w, tokens, picked):
        logits = ref.forward(cfg, w, tokens)
        if quant is not None:
            picked = jnp.argmax(ref.forward(cfg, w, tokens, quant), -1)
        got = jnp.take_along_axis(logits, picked[..., None], -1)[..., 0]
        return (jnp.max(logits, -1) - got) / jnp.std(logits, -1)
    return jax.jit(judge)


def sample(done: list[Served], n: int, seed: int) -> list[Served]:
    """``n`` finished requests drawn from the seed, the longest first."""
    longest = max(range(len(done)), key=lambda i: len(done[i].req.out_tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, 1])
    picked = rng.choice(rest, size=min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[i] for i in picked]


def reference_gaps(cfg: dict, ref, key, reqs: list[Served], length: int,
                   quant=None) -> list[np.ndarray]:
    """Per request, the gap of each served token (or, with ``quant``, of
    the token the control puts first) at its position."""
    import jax
    judge = judge_fn(cfg, ref, quant)
    w = jax.jit(lambda k: ref.init(cfg, k))(key)
    out = []
    for i0 in range(0, len(reqs), REF_ROWS):
        part = reqs[i0:i0 + REF_ROWS]
        toks = np.zeros((REF_ROWS, length), np.int32)
        picked = np.zeros((REF_ROWS, length), np.int32)
        spans = []
        for row, s in enumerate(part):
            outs = np.asarray(s.req.out_tokens, np.int64).reshape(-1)
            seq = np.concatenate([s.req.tokens, outs[:-1]])
            toks[row, :len(seq)] = seq
            # the token served after position p is judged at p
            pos = slice(s.plen - 1, s.plen - 1 + len(outs))
            picked[row, pos] = np.minimum(outs, cfg["vocab_size"] - 1)
            spans.append((pos, outs))
        gaps = np.asarray(judge(w, toks, picked))
        for row, (pos, outs) in enumerate(spans):
            g = gaps[row, pos].astype(np.float64)
            # a token outside the vocabulary is wrong whatever its logit
            g[outs >= cfg["vocab_size"]] = np.inf
            out.append(g)
    del w
    return out


def build(cfg: dict, ref, key, device, buckets):
    """Weights from ``key``, the engine the configuration names, warmed up
    on every shape the traffic's ``buckets`` need."""
    import jax
    from repro.models.lm import LM
    from repro.serve.engine import Engine
    lm = LM(model_config(cfg))
    eng = cfg["engine"]
    with jax.default_device(device):
        params = make_params(cfg, ref, lm, key)
        engine = Engine(lm, params, lm.runtime(), max_batch=eng["max_batch"],
                        max_len=eng["max_len"],
                        prefill_chunk=eng.get("prefill_chunk"),
                        page_size=eng.get("page_size"))
        warm_up(engine, buckets, cfg["vocab_size"])
    return engine


def requests(mix: dict, seconds: float, seed: int, vocab: int,
             t0: float) -> list[Served]:
    from repro.serve.engine import Request
    return [Served(t0 + a.due_s, len(a.tokens), a.max_new_tokens,
                   Request(rid=i, tokens=a.tokens,
                           max_new_tokens=a.max_new_tokens))
            for i, a in enumerate(traffic_gen.arrivals(mix, seconds, seed,
                                                       vocab))]


def finished(served: list[Served]) -> list[Served]:
    """The requests that got every token they asked for."""
    return [s for s in served
            if s.req.done and len(s.req.out_tokens) == s.budget]


def widest_gap(cfg: dict, ref, key, served: list[Served], mix: dict,
               seed: int, quant=None) -> float:
    """The widest gap over the sample of finished requests (inf if none
    finished)."""
    done = finished(served)
    if not done:
        return math.inf
    length = max(mix["prompt_tokens"]["buckets"]) + mix["output_tokens"]["max"]
    gaps = reference_gaps(cfg, ref, key,
                          sample(done, cfg["check"]["sample_requests"], seed),
                          length, quant)
    return max(float(np.max(g)) for g in gaps)


def quantiles_ms(values) -> str:
    """Median, 80th, 90th, 95th and 99th percentiles of seconds, in ms."""
    if not values:
        return "none"
    q = np.percentile(np.asarray(values, float), [50, 80, 90, 95, 99]) * 1000
    return "p50 {:.3f}, p80 {:.3f}, p90 {:.3f}, p95 {:.3f}, p99 {:.3f}".format(*q)


def ttft_s(rec: dict) -> list[float]:
    """Time to first token of every request due in the window, from when it
    was due; a request that never got one counts until the run stopped
    waiting for it."""
    t0, t1 = rec["window"]
    return [(r["times"][0] if r["times"] else rec["ended"]) - r["due"]
            for r in rec["requests"] if t0 <= r["due"] < t1]


def gaps_s(rec: dict) -> list[float]:
    """Every gap between consecutive output tokens that ends in the window;
    a gap that spans an admission stall counts as the user sees it."""
    t0, t1 = rec["window"]
    gaps = []
    for r in rec["requests"]:
        t = np.asarray(r["times"])
        if len(t) > 1:
            d = np.diff(t)
            gaps.extend(d[(t[1:] >= t0) & (t[1:] <= t1)])
    return gaps


def run(cell: bench.Cell) -> dict:
    import jax

    cfg, mix = cell.config, cell.traffic
    ref = bench.load_module(cell.config_dir / cfg["reference"])
    clock = bench.CompileClock()
    spans = bench.Spans(traced=cell.trace)
    key = bench.prng_key(cell.seed)
    engine = build(cfg, ref, key, cell.devices[0],
                   sorted(mix["prompt_tokens"]["buckets"]))
    trace_dir = None
    if cell.trace:
        import tempfile
        trace_dir = cell.trace_dir or tempfile.mkdtemp(prefix="trace-")
        jax.profiler.start_trace(trace_dir)
    window = jax.profiler.TraceAnnotation("bench.window")
    t0 = time.perf_counter()
    window.__enter__()
    setup_s = t0 - cell.started
    served = requests(mix, cell.seconds, cell.seed, cfg["vocab_size"], t0)
    with jax.default_device(cell.devices[0]):
        steps = open_loop(engine, served, t0, cell.seconds, spans,
                          window_note=lambda: window.__exit__(None, None, None))
    t_end = t0 + cell.seconds
    ended = time.perf_counter()
    if cell.trace:
        jax.profiler.stop_trace()
    device = bench.device_info(cell.devices[:cell.chips])
    del engine
    gc.collect()
    with jax.default_device(cell.devices[0]):
        gap = widest_gap(cfg, ref, key, served, mix, cell.seed)
    unserved = len(served) - len(finished(served))
    limit = cfg["check"]["max_logit_gap"]
    reduced = None
    if cell.trace:
        import devtrace
        reduced = devtrace.reduce(devtrace.read(devtrace.xplane(trace_dir)))
        if cell.trace_dir is None:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    ttft = [s.times[0] - s.due for s in served if s.times]
    itl = [b - a for s in served for a, b in zip(s.times, s.times[1:])]
    page_gb = flops.kv_bytes(cfg, cfg["engine"].get("page_size") or 0) / 1e9
    held = [p for *_, p in steps] or [0]
    return {
        "correct": bool(unserved == 0 and gap <= limit),
        "attempted": len(served),
        "failed": unserved,
        "device": device,
        "checks": {"max_logit_gap": {"value": gap, "limit": limit},
                   "unserved": {"value": unserved, "limit": 0}},
        "notes": [f"{len(served)} requests due, {len(steps)} decode steps, "
                  f"{sum(len(s.times) for s in served)} tokens",
                  f"time to first token: {quantiles_ms(ttft)} ms over "
                  f"{len(ttft)} requests",
                  f"gap between tokens: {quantiles_ms(itl)} ms over "
                  f"{len(itl)} gaps",
                  f"cache pages held: at most {max(held)}, mean "
                  f"{float(np.mean(held)):.1f} ({max(held) * page_gb:.3f} "
                  f"and {float(np.mean(held)) * page_gb:.3f} GB)"],
        # what the metric readers read
        "setup_s": setup_s,
        "window": (t0, t_end),
        "ended": ended,
        "requests": [{"due": s.due, "sent": s.sent, "times": s.times}
                     for s in served],
        "spans": spans.times,
        "steps": steps,
        "compiled_in_window": clock.compiled_between(t0, t_end),
        "config": cfg,
        "trace": reduced,
    }
