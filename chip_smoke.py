"""Bring-up smoke run of the serve engine and the elastic trainer on a TPU.

    python chip_smoke.py            # one chip: phases E, A-D
    python chip_smoke.py --chips 4  # four chips: the elastic trainer only

The model is granite-3-8b (``repro/configs/granite_3_8b.py``) at its
published widths: d_model 4096, 32 query and 8 KV heads of 128, d_ff
12800, vocab 49155. Only depth is cut. Serving keeps 20 of the 40 layers:
the other 20 would be a second pipeline stage on another chip, since 40
layers of bf16 weights (17.6 GB) alone exceed one v5e's 16 GB. Training
keeps 2 layers, so that a step with its optimizer state fits one chip.
Weights are random, drawn from a seed by a jitted ``LM.init`` on the chip.

One chip, one process:

  E  training: a two-layer job under the ``ElasticController``, preempted
     once and resumed from its checkpoint, must repeat the unpreempted
     job's losses exactly; two planted data faults must each move them by
     more than the four-chip bound (below).
  A  contiguous ``Engine`` (16 slots, 2048-token cache) serves 24 seeded
     requests through ``Engine.run``: every one admitted, each with
     exactly ``max_new_tokens`` tokens.
  B  paged ``Engine`` (128-token pages) serves the same requests; the
     logits of the first decode steps of one admitted batch are compared
     with the contiguous engine's, and token mismatches are counted.
  C  cache consistency: for two requests, the engine's decode-step logits
     at a few positions against a float32 full-sequence forward pass over
     prompt + generated tokens, with no cache.
  D  the DSP path: the 1/2/4-width ``ServeFleet`` -> ``PartitionedEngine``
     -> ``JaxEngineAdapter`` -> paged ``Engine``, beside an
     ``EmulatedEngine`` twin at the same cache depth.

Four chips (``--chips 4``), and nothing else: two training jobs under the
``ElasticController`` on disjoint chips, with elastic grows and one
injected preemption; each job's losses must match the same job run alone
on one chip within ``LOSS_RTOL``.

The earlier lines print what is worth knowing (versions, device, seconds
per phase with compile and run apart, tokens, peak device memory), each
labelled with the device; none is a benchmark metric. The last line is
``{"ok": true, "device": {...}}``. Any failure exits non-zero before it,
and without a TPU the script exits non-zero having run nothing.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SERVE_LAYERS, TRAIN_LAYERS = 20, 2
TRAIN_SEQ, TRAIN_BATCH = 1024, 8
STEPS_PER_TICK = 2
JOB_STEPS = {"train-0": 2 * STEPS_PER_TICK, "train-1": 3 * STEPS_PER_TICK}
MAX_BATCH, MAX_LEN, PAGE_SIZE = 16, 2048, 128
PROMPT_LENS = (128, 512, 1024)
N_REQUESTS = 24
NEW_TOKENS = (64, 256)          # inclusive range of max_new_tokens
PREFILL_CHUNK = 4
PROBE_STEPS = 4                 # decode steps compared in phase B
FLEET_WORKFLOWS = 2             # per tenant, phase D
SEED = 0

#: Phase B bound on max|paged - contiguous| over the probe's logits, as a
#: share of max|contiguous|. Both engines run the same bf16 math over the
#: same keys; on the chip the two decode programs are fused differently,
#: so a reduction may round differently and the difference can grow
#: through the layers. 2e-2 is a few bf16 steps (2^-8 each) at the logits'
#: own scale; a wrong page (another token's K/V) moves them by O(1).
PAGED_TOL = 2e-2

#: Phase C bound on max|engine - reference| / max|reference| over the
#: checked positions. The engine keeps activations, K/V and logits in bf16
#: (8 mantissa bits, relative step 2^-8); the reference computes in
#: float32 with the same weights. At these widths on the CPU backend the
#: gap measured 1.1-1.4% of the logits' scale with 2 layers and 1.3-1.7%
#: with 8: it grows slowly with depth, so 5% leaves room for 20 layers and
#: for the chip's own fusion. A cache read at a wrong position or a stale
#: entry moves logits by O(1) of their scale (measured: 1.3-1.5).
REFERENCE_TOL = 5e-2

#: Four-chip bound on |loss - one-chip loss| / one-chip loss, per step.
#: A data-parallel step sums the gradient over two chips in another order,
#: and AdamW turns that rounding into parameters that differ in a few
#: entries. Phase E plants two faults on one chip every run (both chips
#: fed the same half batch; the data one step behind, as a resume that
#: replays a batch would leave it) and requires each to move the losses by
#: more than this. On a TPU v5e the sound gaps measured 1.4e-5 and 2.1e-5,
#: the faults 2.8e-2 and 0.18: the bound sits near their geometric mean.
LOSS_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    """A check that survives ``python -O``."""
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


class Phase:
    """Times one phase and prints its wall, compile and run seconds, from
    the program's compile counter (a load from the persistent cache counts
    as a program)."""

    def __init__(self, name: str, device: str):
        from repro import obs
        self.name, self.device = name, device
        self.clock = obs.compile_counter()

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clock.seconds
        self.n0 = self.clock.programs
        self.h0 = self.clock.cache_hits
        log(f"[{self.name}] start")
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        log(f"[{self.name}] {self.device}: wall {wall:.2f}s = XLA compile "
            f"{comp:.2f}s ({self.clock.programs - self.n0} programs, "
            f"{self.clock.cache_hits - self.h0} from the persistent cache) "
            f"+ the rest {wall - comp:.2f}s")
        return False


# --------------------------------------------------------------- serving
def granite(n_layers: int):
    from repro.configs.granite_3_8b import CONFIG
    return dataclasses.replace(CONFIG, n_layers=n_layers)


def make_requests(cfg, n: int, seed: int, prompt_lens=PROMPT_LENS,
                  new_tokens=NEW_TOKENS):
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(prompt_lens[i % len(prompt_lens)])
        out.append(Request(
            rid=i, tokens=rng.integers(1, cfg.vocab_size, plen,
                                       dtype=np.int32),
            max_new_tokens=int(rng.integers(new_tokens[0],
                                            new_tokens[1] + 1))))
    return out


def fresh(reqs, max_new_tokens=None):
    """Unserved copies of ``reqs`` (same rids and prompts)."""
    from repro.serve.engine import Request
    return [Request(rid=r.rid, tokens=r.tokens,
                    max_new_tokens=max_new_tokens or r.max_new_tokens)
            for r in reqs]


def keep_logits(engine):
    """Make ``engine`` keep its latest decode step's (max_batch, vocab)
    logits in ``engine.last_logits``, for phases B and C to compare."""
    decode = engine._decode

    def probed(*args):
        engine.last_logits, caches = decode(*args)
        return engine.last_logits, caches
    engine._decode = probed
    return engine


def serve_all(engine, reqs) -> dict:
    """Phases A and B: every request admitted, each with exactly its
    ``max_new_tokens`` tokens. Returns rid -> tokens."""
    done = engine.run(reqs)
    check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} came back")
    check(not any(r.rejected for r in done), "a request was rejected")
    for r in done:
        check(len(r.out_tokens) == r.max_new_tokens,
              f"request {r.rid}: {len(r.out_tokens)} tokens, "
              f"wanted {r.max_new_tokens}")
    check(not engine.active and len(engine.free) == engine.max_batch,
          "engine did not drain")
    return {r.rid: np.asarray(r.out_tokens) for r in done}


def probe_logits(engine, reqs, steps: int) -> list[np.ndarray]:
    """Admit one batch into an idle engine and return the logits of its
    first ``steps`` decode steps, rows in slot order of admission."""
    admitted = engine.admit_many(fresh(reqs, max_new_tokens=steps + 1))
    check(len(admitted) == len(reqs), "probe batch not admitted whole")
    slots = [next(s for s, q in engine.active.items() if q is r)
             for r in admitted]
    out = []
    for _ in range(steps):
        engine.step()
        out.append(np.asarray(engine.last_logits, np.float32)[slots])
    check(not engine.active, "probe batch did not finish")
    return out


def decode_trace(engine, reqs, at_steps):
    """Serve ``reqs`` (admitted together) and keep each one's decode-step
    logits at ``at_steps`` (1-based). Returns [(prompt + generated tokens,
    {step: logits})] in request order."""
    admitted = engine.admit_many(fresh(reqs))
    check(len(admitted) == len(reqs), "trace batch not admitted whole")
    slot = {id(r): next(s for s, q in engine.active.items() if q is r)
            for r in admitted}
    seen = {id(r): {} for r in admitted}
    step = 0
    while engine.active:
        engine.step()
        step += 1
        if step in at_steps:
            rows = np.asarray(engine.last_logits, np.float32)
            for r in admitted:
                seen[id(r)][step] = rows[slot[id(r)]]
    return [(np.concatenate([r.tokens, np.asarray(r.out_tokens)]),
             seen[id(r)]) for r in admitted]


def reference_logits(lm, rt, params, tokens):
    """Float32 full-sequence forward over ``tokens`` (1, S): ``LM.prefill``
    with every position's logits kept and no cache. The unscanned leaves
    are cast to float32; the scanned layer weights stay bf16 and each op
    promotes them to float32 (exact), so the float32 copy of the whole
    stack is never held."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import rmsnorm

    f32 = jnp.float32
    p32 = dict(params, embed=params["embed"].astype(f32),
               head=params["head"].astype(f32),
               final_norm=params["final_norm"].astype(f32))

    def fwd(p, toks):
        with jax.default_matmul_precision("highest"):
            x = lm.embed(p, {"tokens": toks})
            pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
            x, _, _ = lm.backbone(p, rt, x, pos, remat=False)
            x = rmsnorm(p["final_norm"], x, lm.cfg.norm_eps)
            return lm.logits(p, x)[0]
    return np.asarray(jax.jit(fwd)(p32, jnp.asarray(tokens)[None]))


def fleet_twins(engine, *, workflows: int, seed: int = SEED):
    """Phase D: the 1/2/4 fleet on the real paged engine and on an
    emulated twin at the same cache depth; returns both ``FleetStats``
    dicts and the fleet."""
    from benchmarks.serve_fleet import parse_mix, tenant_policy, tenant_streams
    from repro.core.policy import MgmtPolicy
    from repro.serve.driver import EmulatedEngine, JaxEngineAdapter
    from repro.serve.fleet import ServeFleet

    mix = parse_mix("1/2/4")
    base = MgmtPolicy(initial=1, ratio=2.0, scan_interval=3.0,
                      release_interval=60.0)

    def fleet(backend):
        streams, widths = tenant_streams(len(mix), workflows, seed, 0.04,
                                         3600.0, mix=mix)
        return ServeFleet(streams, engine=backend,
                          coordination="coordinated",
                          policies=[tenant_policy(base, w) for w in widths],
                          widths=widths, event_skip=False,
                          name="chip-fleet", page_size=engine.page_size)

    real = fleet(JaxEngineAdapter(engine, seed=seed))
    real_stats = real.run().as_dict()
    twin = fleet(EmulatedEngine(engine.max_batch, max_len=engine.max_len))
    return real_stats, twin.run().as_dict(), real


def serve_phases(lm, rt, params, device: str, *,
                 max_batch=MAX_BATCH, max_len=MAX_LEN, page_size=PAGE_SIZE,
                 n_requests=N_REQUESTS, prompt_lens=PROMPT_LENS,
                 new_tokens=NEW_TOKENS, workflows=FLEET_WORKFLOWS) -> None:
    from repro.serve.engine import Engine

    reqs = make_requests(lm.cfg, n_requests, SEED, prompt_lens, new_tokens)
    probe = reqs[:max_batch]
    with Phase("A contiguous engine", device):
        eng = keep_logits(Engine(lm, params, rt, max_batch=max_batch,
                                 max_len=max_len, prefill_chunk=PREFILL_CHUNK))
        toks_a = serve_all(eng, fresh(reqs))
        n_tok = sum(len(t) for t in toks_a.values())
        log(f"[A] {len(toks_a)} requests, {n_tok} tokens, "
            f"{eng.steps} decode steps, all admitted and complete")
        logits_a = probe_logits(eng, probe, PROBE_STEPS)

    with Phase("C cache consistency", device):
        plen = prompt_lens[len(prompt_lens) // 2]
        n_new = new_tokens[0]
        pair = fresh([r for r in reqs if len(r.tokens) == plen][:2], n_new)
        at = (1, n_new // 2, n_new - 1)
        traces = decode_trace(eng, pair, at)
        del eng
        gc.collect()
        worst = 0.0
        for seq, seen in traces:
            ref = reference_logits(lm, rt, params, seq)
            for step, got in seen.items():
                want = ref[plen + step - 1]
                err = float(np.max(np.abs(got - want))
                            / np.max(np.abs(want)))
                worst = max(worst, err)
                check(err <= REFERENCE_TOL,
                      f"decode step {step}: logits off the float32 "
                      f"reference by {err:.3g} of scale > {REFERENCE_TOL}")
        log(f"[C] 2 requests x steps {at}: max |engine - float32 ref| = "
            f"{worst:.4g} of the logits' scale (bound {REFERENCE_TOL})")

    with Phase("B paged engine", device):
        eng = keep_logits(Engine(lm, params, rt, max_batch=max_batch,
                                 max_len=max_len, prefill_chunk=PREFILL_CHUNK,
                                 page_size=page_size))
        toks_b = serve_all(eng, fresh(reqs))
        diff = sum(int(np.sum(toks_a[k] != toks_b[k])) for k in toks_a)
        first = sum(int(toks_a[k][0] != toks_b[k][0]) for k in toks_a)
        log(f"[B] {len(toks_b)} requests, {eng.steps} decode steps, "
            f"peak {eng.pager.peak_used}/{eng.pager.capacity_pages} pages; "
            f"token mismatches vs contiguous: {diff} of {n_tok} "
            f"({first} at the first token)")
        logits_b = probe_logits(eng, probe, PROBE_STEPS)
        worst = max(float(np.max(np.abs(b - a)) / np.max(np.abs(a)))
                    for a, b in zip(logits_a, logits_b))
        check(worst <= PAGED_TOL,
              f"paged logits off contiguous by {worst:.3g} of scale "
              f"> {PAGED_TOL}")
        log(f"[B] first {PROBE_STEPS} decode steps of a {len(probe)}-row "
            f"batch: max |paged - contiguous| = {worst:.4g} of the "
            f"logits' scale (bound {PAGED_TOL})")

    with Phase("D DSP fleet", device):
        real, emu, fleet = fleet_twins(eng, workflows=workflows)
        check(real["workflows_completed"] == real["workflows_expected"],
              f"fleet completed {real['workflows_completed']}/"
              f"{real['workflows_expected']} workflows")
        check(real["over_admissions"] == 0, "fleet over-admitted")
        check(real["isolation_violations"] == 0,
              "fleet violated slot isolation")
        mism = [k for k in emu if emu[k] != real.get(k)]
        check(not mism, f"real vs emulated FleetStats differ in {mism}")
        fleet.pool.pager.check_conservation()
        check(eng.pager.used_pages == fleet.pool.pager.used_pages == 0,
              "engine and pool page ledgers disagree after the run")
        log(f"[D] 1/2/4 fleet: {real['workflows_completed']} workflows, "
            f"{real['tasks_completed']} tasks, {real['ticks']} ticks; "
            f"0 over-admissions, 0 isolation violations, 0 FleetStats "
            f"mismatches vs the emulated twin, pages conserved")


# -------------------------------------------------------------- training
def train_config(cfg, seq_len: int = TRAIN_SEQ, batch: int = TRAIN_BATCH):
    from repro.configs.base import ParallelConfig, RunConfig, ShapeConfig
    # 1e-4: at these widths 1e-3 is unstable from random weights (on the
    # CPU the gradient norm jumps fivefold by the fourth step)
    return RunConfig(model=cfg,
                     shape=ShapeConfig("smoke-train", "train", seq_len, batch),
                     parallel=ParallelConfig(), total_steps=100,
                     learning_rate=1e-4, warmup_steps=2)


def run_jobs(devices, rcfg, steps: dict, *, initial: int, per_tick: int,
             grow: bool = False, fail_at: dict | None = None) -> list:
    """One one-node training job per ``steps`` entry (name -> steps) under
    an ``ElasticController`` on ``devices``. Prints the device ids each job
    holds at each tick and checks that no two jobs share one. Returns the
    finished tasks, each with one finite loss per step."""
    from repro.core.controller import ElasticController, TrainTask
    from repro.core.policy import MgmtPolicy
    from repro.core.provision import ProvisionService

    held: dict[int, set] = {}

    class Logged(ElasticController):
        def _run_segment(self, task, fail=False):
            ids = [d.id for d in self.devices_of(task)]
            taken = held.setdefault(self._tick, set())
            check(not taken & set(ids), f"tick {self._tick}: {task.name} "
                  f"on device ids {ids}, some held by another job")
            taken.update(ids)
            log(f"[E] tick {self._tick} {task.name} on device ids "
                f"{ids}{' (preempted)' if fail else ''}")
            super()._run_segment(task, fail)

    with tempfile.TemporaryDirectory() as tmp:
        ctl = Logged(policy=MgmtPolicy.htc(initial, 1.0),
                     provision=ProvisionService(capacity=len(devices)),
                     devices=devices, steps_per_tick=per_tick,
                     elastic_grow=grow)
        tasks = [TrainTask(n, rcfg, nodes=1, num_steps=k,
                           ckpt_dir=f"{tmp}/{n}") for n, k in steps.items()]
        for t in tasks:
            ctl.submit(t)
        ctl.run(fail_at=fail_at)
        ctl.destroy()
    for t in tasks:
        log(f"[E] {t.name} losses {' '.join(f'{x:.6f}' for x in t.losses)}")
        check(t.done and len(t.losses) == t.num_steps,
              f"{t.name}: {len(t.losses)} losses for {t.num_steps} steps")
        check(np.isfinite(t.losses).all(), f"{t.name}: a loss is not finite")
    return tasks


def loss_gap(got, ref) -> float:
    """Largest per-step |loss - reference loss| / reference loss."""
    ref = np.asarray(ref.losses)
    return float(np.max(np.abs(np.asarray(got.losses) - ref) / ref))


def planted(fault: str):
    """A stand-in for ``synthetic_batches`` that feeds a training job one
    known fault: ``half`` gives both halves of each batch the first half's
    rows (what two data-parallel chips see if both get the first shard);
    ``replay`` keeps the data one step behind from step 1 (what a resume
    that restores the model but replays the last batch sees)."""
    import jax.numpy as jnp
    from repro.data.synthetic import synthetic_batches

    def make(rcfg, mesh=None):
        batch_fn = synthetic_batches(rcfg, mesh)
        if fault == "replay":
            return lambda step: batch_fn(max(step - 1, 0))
        half = rcfg.shape.global_batch // 2
        return lambda step: {k: jnp.concatenate([v[:half], v[:half]])
                             for k, v in batch_fn(step).items()}
    return make


def train_phase(dev, rcfg, label: str) -> None:
    """E on one chip: train-0 preempted once must repeat its unpreempted
    losses exactly (same program, same state, same chip), and each planted
    fault must move them by more than ``LOSS_RTOL``, the bound the
    four-chip comparison holds the jobs to."""
    from unittest import mock
    name = "train-0"
    job = {name: JOB_STEPS[name]}
    with Phase("E one-chip training", label):
        alone, = run_jobs([dev], rcfg, job, initial=1, per_tick=job[name])
        preempted, = run_jobs([dev], rcfg, job, initial=1,
                              per_tick=STEPS_PER_TICK, fail_at={2: name})
        check(preempted.restarts == 1, "the preemption was not absorbed")
        check(preempted.losses == alone.losses,
              f"the preempted job's losses are off the unpreempted job's "
              f"by up to {loss_gap(preempted, alone):.3g}")
        gaps = {}
        for fault in ("half", "replay"):
            with mock.patch("repro.core.controller.synthetic_batches",
                            planted(fault)):
                bad, = run_jobs([dev], rcfg, job, initial=1,
                                per_tick=job[name])
            gaps[fault] = loss_gap(bad, alone)
        log(f"[E] {name}: preempted and resumed = unpreempted, gap 0; "
            f"planted faults move the losses by "
            f"{', '.join(f'{k} {v:.4g}' for k, v in gaps.items())} "
            f"(bound {LOSS_RTOL})")
        check(min(gaps.values()) > LOSS_RTOL,
              f"a planted fault stays within {LOSS_RTOL}: {gaps}")


def elastic_phase(devices, rcfg, label: str) -> None:
    """Two jobs on four devices: train-0 grows onto a second device before
    it runs, train-1 is preempted once and then grows onto the device
    train-0 returns. Each job's losses are compared with the same job run
    alone on one device."""
    with Phase("E elastic training", label):
        elastic = run_jobs(devices, rcfg, JOB_STEPS, initial=3, grow=True,
                           per_tick=STEPS_PER_TICK, fail_at={2: "train-1"})
        check(all(t.resizes for t in elastic),
              "a job was never resized onto more devices")
        check(elastic[1].restarts == 1, "the preemption was not absorbed")
    with Phase("E one-chip references", label):
        alone = run_jobs(devices, rcfg, JOB_STEPS, initial=2,
                         per_tick=max(JOB_STEPS.values()))
    for got, ref in zip(elastic, alone):
        gap = loss_gap(got, ref)
        check(gap <= LOSS_RTOL, f"{got.name}: losses off the one-chip run "
              f"by {gap:.3g} > {LOSS_RTOL}")
        log(f"[E] {got.name}: resizes {got.resizes}, restarts "
            f"{got.restarts}, loss {got.losses[0]:.4f} -> "
            f"{got.losses[-1]:.4f}; max relative gap to one chip "
            f"{gap:.4g} (bound {LOSS_RTOL})")


# ------------------------------------------------------------------ main
def serve(label: str) -> None:
    """Phases A-D; the 20-layer weights are freed when this returns."""
    import jax
    from repro.models.lm import LM
    cfg = granite(SERVE_LAYERS)
    lm = LM(cfg)
    rt = lm.runtime()
    with Phase("init", label):
        params = jax.jit(lambda k: lm.init(k)[0])(jax.random.key(SEED))
        jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"granite-3-8b, {cfg.n_layers} of 40 layers: {n / 1e9:.3f} B "
        f"parameters on {label}")
    serve_phases(lm, rt, params, label)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the elastic trainer across 4 chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform}; nothing was run", file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} chips")
    kind = devices[0].device_kind
    device = f"{kind} x{len(devices)}"

    from repro.launch.compile_cache import enable_compile_cache
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:               # a build without package metadata
        libtpu = "unknown"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu}; {len(devices)} x {kind}; compile cache "
        f"{enable_compile_cache()}")

    rcfg = train_config(granite(TRAIN_LAYERS))
    if args.chips == 4:
        elastic_phase(devices[:4], rcfg, device)
    else:
        train_phase(devices[0], rcfg, device)
        gc.collect()
        serve(device)
    for i, d in enumerate(devices[:args.chips]):
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            log(f"{kind} #{i}: peak memory in use "
                f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB of "
                f"{stats.get('bytes_limit', 0) / 1e9:.2f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
