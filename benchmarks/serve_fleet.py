"""Multi-tenant serve-fleet benchmark: the economies-of-scale curve for
the SERVING path — N tenant streams consolidated on one engine pool vs N
dedicated engines, for homogeneous AND heterogeneous width mixes.

For each tenant count N, width mix (``--mixes``, e.g. ``1`` = every
tenant a width-1 small model, ``1/2/4`` = small/medium/large model
classes cycled across tenants — ``sim.traces.SERVE_PROFILES``) and
coordination policy (``first-come`` vs ``coordinated``):

  - **dedicated baseline**: every tenant gets its own fixed engine sized
    at its own *eager-execution peak* — the slot count that serves every
    workflow with zero queueing delay, the serving analogue of the
    paper's DCS configuration (Montage's "accumulated parallel demand
    ~166 nodes") — at the tenant's width (a width-w tenant's dedicated
    engine bills w node units per slot), replayed through a standalone
    ``ServeDriver`` with no negotiation; billed node-hours = its
    width-sized engine held for its whole run.
  - **consolidated fleet**: the same N streams on ONE
    ``PartitionedEngine`` pool sized at the *fleet-wide* width-weighted
    peak hourly-averaged offered decode load (statistical multiplexing:
    the peak of the sum grows sublinearly while the sum of peaks is
    linear), node units partitioned by the provider's coordination
    policy, DSP management policies per tenant (elastic grow/release,
    B priced at the tenant's width), deferred grants through the
    admission queue, finished tenants destroyed mid-run so their units
    serve the stragglers.

Every consolidated cell must complete every workflow with ZERO
over-admissions and ZERO weighted-isolation violations (``strict=True``
raises on either at the offending tick — checks that survive
``python -O``), and for N >= 3 its per-tenant billed node-hours must
come in under the dedicated baseline under BOTH policies and EVERY mix —
asserted, not just reported.

Output: ``BENCH_serve_fleet.json`` (CI uploads it as an artifact and
``benchmarks/check_regression.py`` gates it against the committed
baseline and the rolling history window).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from concurrent.futures import ProcessPoolExecutor

from repro.core.policy import MgmtPolicy
from repro.core.provision import ProvisionService
from repro.serve.driver import EmulatedEngine, ServeDriver
from repro.serve.fleet import ServeFleet, ServeFleetSystem, rekey_disjoint
from repro.sim.traces import SERVE_PROFILES, workload_family


def _require(cond: bool, msg: str) -> None:
    """Acceptance-gate check that survives ``python -O`` (unlike assert)."""
    if not cond:
        raise RuntimeError(f"serve_fleet gate: {msg}")


#: tokens per KV page for the paged ledger riding under every
#: consolidated cell (and for the physical engine on the ``--real`` leg)
PAGE_SIZE = 8


def fleet_depth(streams, page_size: int = PAGE_SIZE) -> int:
    """Cache depth serving every request's full decode mark —
    ``max(prompt + decode + 1)`` over all jobs, rounded up to a page
    multiple. At this depth ``decode_budget`` never caps a mark, so a
    ``max_len``-capped (and paged) engine's service ticks — and therefore
    every ``FleetStats`` field — are identical to the uncapped engine's."""
    need = 2
    for stream in streams:
        for _, jobs in stream:
            for j in jobs:
                need = max(need, max(j.prompt_len, 1) + j.decode_len + 1)
    return -(-need // page_size) * page_size


def eager_peak_slots(stream) -> int:
    """Peak instantaneous slot demand of the stream under eager execution
    (every task decodes the moment its dependencies finish): the engine
    size a dedicated provider must own to serve with zero queueing delay
    — the DCS-configuration analogue for the serving path."""
    events: list[tuple[float, int]] = []
    for t0, jobs in stream:
        start: dict[int, float] = {}
        end: dict[int, float] = {}
        remaining = list(jobs)
        while remaining:
            rest = []
            for j in remaining:
                if all(d in end for d in j.deps):
                    s = max((end[d] for d in j.deps), default=0.0)
                    start[j.jid] = s
                    end[j.jid] = s + max(j.decode_len, 1)
                else:
                    rest.append(j)
            if len(rest) == len(remaining):
                raise ValueError("dependency cycle in stream entry")
            remaining = rest
        for j in jobs:
            events.append((t0 + start[j.jid], 1))
            events.append((t0 + end[j.jid], -1))
    events.sort()
    peak = level = 0
    for _, d in events:
        level += d
        peak = max(peak, level)
    return max(peak, 1)


def parse_mix(spec: str) -> list[int]:
    """``"1/2/4"`` -> ``[1, 2, 4]`` (widths cycled across the tenants);
    every width must name a ``SERVE_PROFILES`` model class."""
    widths = [int(tok) for tok in spec.replace(",", "/").split("/") if tok]
    if not widths:
        raise ValueError(f"empty width mix {spec!r}")
    unknown = [w for w in widths if w not in SERVE_PROFILES]
    if unknown:
        raise ValueError(f"no serve profile for widths {unknown} "
                         f"(known: {sorted(SERVE_PROFILES)})")
    return widths


def tenant_streams(n_tenants: int, workflows: int, seed: int,
                   jobs_scale: float, period: float,
                   mix: list[int] | None = None):
    """One workflow arrival stream per tenant (disjoint jid ranges): each
    tenant is its own MTC service provider with its own seeded
    ``workload_family`` of Montage-shaped mosaics, marked by its width
    class's serve profile (cycled through ``mix``). Returns
    ``(streams, widths)``."""
    mix = mix or [1]
    streams, widths = [], []
    for t in range(n_tenants):
        fam = workload_family(0, workflows, seed=seed * 1009 + t,
                              jobs_scale=jobs_scale)
        profile = SERVE_PROFILES[mix[t % len(mix)]]
        streams.append(profile.stream(fam, period=period, seed=seed + t))
        widths.append(profile.width)
    return rekey_disjoint(streams), widths


def tenant_policy(base: MgmtPolicy, width: int) -> MgmtPolicy:
    """The fleet policy priced at the tenant's width (B in node units)."""
    return MgmtPolicy(initial=base.initial * width, ratio=base.ratio,
                      scan_interval=base.scan_interval,
                      release_interval=base.release_interval)


def run_dedicated(streams, widths, *, policy: MgmtPolicy,
                  max_len: int | None = None) -> dict:
    """N dedicated engines: per-tenant fixed width-sized slots, no
    negotiation — a width-w tenant's engine bills w units per slot.
    ``max_len`` caps decode marks to a cache depth, matching a real
    engine baseline (the ``--real`` leg compares like with like)."""
    t0 = time.perf_counter()
    total = {"node_hours": 0.0, "slots": 0, "workflows": 0, "tasks": 0,
             "over_admissions": 0, "busy": 0.0, "owned": 0.0,
             "makespan_s": 0.0}
    for i, (stream, w) in enumerate(zip(streams, widths)):
        # slot floor: the consolidated tenant's B is initial * w units ==
        # `initial` slots at this width, so the floor is width-invariant
        slots = max(eager_peak_slots(stream), policy.initial)
        drv = ServeDriver(stream, provider=ProvisionService(),
                          engine=EmulatedEngine(slots, max_len=max_len),
                          fixed_nodes=slots * w, slot_width=w,
                          name=f"dedicated-t{i}")
        st = drv.run()
        _require(st.workflows_completed == st.workflows_expected,
                 f"dedicated tenant {i} completed {st.workflows_completed}"
                 f"/{st.workflows_expected} workflows")
        _require(st.over_admissions == 0,
                 f"dedicated tenant {i} over-admitted {st.over_admissions}")
        total["node_hours"] += st.node_hours
        total["slots"] += slots * w
        total["workflows"] += st.workflows_completed
        total["tasks"] += st.tasks_completed
        total["busy"] += st.busy_node_ticks
        total["owned"] += st.owned_node_ticks
        total["makespan_s"] = max(total["makespan_s"], st.makespan_s)
    total["slot_utilization"] = (total["busy"] / total["owned"]
                                 if total["owned"] else 0.0)
    total["wall_s"] = time.perf_counter() - t0
    return total


def run_consolidated(streams, widths, *, coordination: str,
                     policy: MgmtPolicy, event_skip: bool = True) -> dict:
    """The fleet: one pool sized at the fleet-wide weighted hourly decode
    peak. Event-skipping is on by default — pinned bit-identical to the
    dense loop by the parity suite, so it changes wall clock only.

    Every cell runs with the physical page ledger underneath
    (``page_size=PAGE_SIZE`` over a ``fleet_depth``-deep cache): admits
    allocate real KV pages under their tenant's quota and conservation is
    swept every tick, yet because the depth serves every mark in full the
    stats stay field-for-field identical to the unpaged PR 7 cells."""
    n = len(streams)
    policies = [tenant_policy(policy, w) for w in widths]
    # size the pool exactly as the registered scenario would: one source
    # of truth for the hourly-peak estimate and the liveness floor
    capacity = ServeFleetSystem().default_capacity(streams, policies,
                                                   widths=widths)
    depth = fleet_depth(streams)
    fleet = ServeFleet(streams,
                       engine=EmulatedEngine(capacity, max_len=depth),
                       coordination=coordination, policies=policies,
                       widths=widths, name=f"fleet-{coordination}-n{n}",
                       event_skip=event_skip, page_size=PAGE_SIZE)
    t0 = time.perf_counter()
    fs = fleet.run()
    wall = time.perf_counter() - t0
    _require(fs.workflows_completed == fs.workflows_expected,
             f"{coordination} N={n} completed {fs.workflows_completed}"
             f"/{fs.workflows_expected} workflows")
    _require(fs.over_admissions == 0,
             f"{coordination} N={n} over-admitted {fs.over_admissions}")
    _require(fs.isolation_violations == 0,
             f"{coordination} N={n} had {fs.isolation_violations} "
             f"slot-isolation violations")
    pager = fleet.pool.pager
    pager.check_conservation()
    _require(pager.used_pages == 0,
             f"{coordination} N={n} leaked {pager.used_pages} KV pages "
             f"past the last finish")
    out = fs.as_dict()
    out["wall_s"] = wall
    out["page_size"] = PAGE_SIZE
    out["pool_pages"] = pager.capacity_pages
    out["peak_pages_used"] = pager.peak_used
    return out


def run_cell(streams, widths, *, mix: str, coordination: str,
             policy: MgmtPolicy, dedicated: dict,
             event_skip: bool = True) -> dict:
    n = len(streams)
    fleet = run_consolidated(streams, widths, coordination=coordination,
                             policy=policy, event_skip=event_skip)
    row = {
        "n_tenants": n,
        "policy": coordination,
        "mix": mix,
        "widths": widths,
        "capacity": fleet["capacity"],
        "dedicated_slots": dedicated["slots"],
        "slots_vs_dedicated": fleet["capacity"] / max(dedicated["slots"], 1),
        "billed_node_hours": fleet["node_hours"],
        "dedicated_node_hours": dedicated["node_hours"],
        "billed_vs_dedicated": (fleet["node_hours"]
                                / max(dedicated["node_hours"], 1e-12)),
        "billed_per_tenant": fleet["node_hours"] / n,
        "slot_utilization": fleet["slot_utilization"],
        "pool_utilization": fleet["pool_utilization"],
        "dedicated_utilization": dedicated["slot_utilization"],
        "workflows": fleet["workflows_completed"],
        "tasks": fleet["tasks_completed"],
        "makespan_s": fleet["makespan_s"],
        "makespan_vs_dedicated": (fleet["makespan_s"]
                                  / max(dedicated["makespan_s"], 1e-12)),
        "deferred_grants": fleet["deferred_grants"],
        "deferred_nodes": fleet["deferred_nodes"],
        "over_admissions": fleet["over_admissions"],
        "isolation_violations": fleet["isolation_violations"],
        "peak_pool_active": fleet["peak_pool_active"],
        "page_size": fleet["page_size"],
        "pool_pages": fleet["pool_pages"],
        "peak_pages_used": fleet["peak_pages_used"],
        "page_utilization": (fleet["peak_pages_used"]
                             / max(fleet["pool_pages"], 1)),
        "wall_s": fleet["wall_s"],
        "workflows_per_sec": (fleet["workflows_completed"]
                              / max(fleet["wall_s"], 1e-12)),
        "dedicated_wall_s": dedicated["wall_s"],
    }
    # the acceptance gate: consolidation must pay off at fleet scale,
    # for the heterogeneous mixes exactly as for the homogeneous one
    if n >= 3:
        _require(row["billed_vs_dedicated"] < 1.0,
                 f"consolidated fleet bills "
                 f"{row['billed_vs_dedicated']:.2f}x dedicated at N={n} "
                 f"mix={mix} under {coordination}")
    return row


# hourly release windows: dynamic blocks live at least one billing
# unit, so elastic growth does not thrash fresh lease-hours (§4.4(2))
FLEET_POLICY = MgmtPolicy(initial=2, ratio=2.0, scan_interval=3.0,
                          release_interval=3600.0)

# --real leg sizing: a smoke-config musicgen engine, 8 batch slots over a
# 48-token cache = 48 / PAGE_SIZE pages per unit in the physical pool
REAL_MAX_BATCH, REAL_MAX_LEN = 8, 48


def _real_fleet_run(args, mix_spec: str, *, page_size: int | None,
                    seed: int) -> tuple[dict, dict]:
    """One heterogeneous fleet over the REAL jax engine (paged when
    ``page_size`` is set, contiguous otherwise). Streams are regenerated
    from the seed so every run replays the identical workload. Returns
    ``(FleetStats.as_dict(), extras)``."""
    import jax

    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig
    from repro.models.lm import LM
    from repro.serve.driver import JaxEngineAdapter
    from repro.serve.engine import Engine

    cfg = get_smoke_config("musicgen-large")
    lm = LM(cfg)
    rt = lm.runtime(ParallelConfig(attn_q_chunk=16, attn_kv_chunk=16))
    params = jax.jit(lambda k: lm.init(k)[0])(jax.random.key(0))
    engine = Engine(lm, params, rt, max_batch=REAL_MAX_BATCH,
                    max_len=REAL_MAX_LEN, page_size=page_size)
    adapter = JaxEngineAdapter(engine, seed=seed)

    mix = parse_mix(mix_spec)
    streams, widths = tenant_streams(len(mix), args.workflows, seed,
                                     args.jobs_scale, args.period, mix=mix)
    base = MgmtPolicy(initial=1, ratio=2.0, scan_interval=3.0,
                      release_interval=60.0)
    fleet = ServeFleet(streams, engine=adapter, coordination="coordinated",
                       policies=[tenant_policy(base, w) for w in widths],
                       widths=widths, event_skip=False,
                       # one name for the paged, contiguous and emulated
                       # runs: stats must match bit-for-bit, labels included
                       name="real-fleet", page_size=page_size)
    t0 = time.perf_counter()
    fs = fleet.run()
    wall = time.perf_counter() - t0
    _require(fs.workflows_completed == fs.workflows_expected,
             f"real mix={mix_spec} paged={bool(page_size)} completed "
             f"{fs.workflows_completed}/{fs.workflows_expected}")
    extras = {"wall_s": wall, "decode_steps": engine.steps,
              "widths": widths}
    if page_size is not None:
        fleet.pool.pager.check_conservation()
        _require(engine.pager.used_pages == fleet.pool.pager.used_pages,
                 "engine/pool page ledgers diverged post-run")
        extras["pool_pages"] = fleet.pool.pager.capacity_pages
        extras["peak_pages_used"] = fleet.pool.pager.peak_used
    return fs.as_dict(), extras


def run_real_fleet(args) -> dict:
    """The ``--real`` leg: the heterogeneous 1/2/4 fleet on the PHYSICAL
    paged engine, pinned three ways —

    - **emulator parity**: an ``EmulatedEngine(max_len=REAL_MAX_LEN)``
      twin fleet replays the identical streams; every deterministic
      ``FleetStats`` field must match the live-jax run bit-for-bit
      (``parity_mismatches == 0``).
    - **paged vs contiguous**: the same fleet on a contiguous-cache
      ``Engine`` must reproduce the paged stats field-for-field
      (``paged_vs_contiguous_mismatches == 0``) — paging is a memory
      layout, never a scheduling input.
    - **economics**: billed node-hours under a width-capped dedicated
      baseline (``billed_vs_dedicated``), the paper's consolidation
      claim surviving contact with a real engine.
    """
    rows = []
    for mix_spec in args.mixes:
        seed = args.seed
        paged, paged_x = _real_fleet_run(args, mix_spec,
                                         page_size=PAGE_SIZE, seed=seed)
        contig, contig_x = _real_fleet_run(args, mix_spec,
                                           page_size=None, seed=seed)

        mix = parse_mix(mix_spec)
        streams, widths = tenant_streams(len(mix), args.workflows, seed,
                                         args.jobs_scale, args.period,
                                         mix=mix)
        base = MgmtPolicy(initial=1, ratio=2.0, scan_interval=3.0,
                          release_interval=60.0)
        twin = ServeFleet(streams,
                          engine=EmulatedEngine(REAL_MAX_BATCH,
                                                max_len=REAL_MAX_LEN),
                          coordination="coordinated",
                          policies=[tenant_policy(base, w) for w in widths],
                          widths=widths, event_skip=False,
                          name="real-fleet", page_size=PAGE_SIZE)
        emu = twin.run().as_dict()

        streams, widths = tenant_streams(len(mix), args.workflows, seed,
                                         args.jobs_scale, args.period,
                                         mix=mix)
        dedicated = run_dedicated(streams, widths, policy=base,
                                  max_len=REAL_MAX_LEN)

        parity = [k for k in emu if emu[k] != paged.get(k)]
        pvc = [k for k in paged if paged[k] != contig.get(k)]
        row = {
            "mix": mix_spec,
            "n_tenants": len(mix),
            "widths": paged_x["widths"],
            "workflows": paged["workflows_completed"],
            "tasks": paged["tasks_completed"],
            "parity_mismatches": len(parity),
            "parity_fields": parity,
            "paged_vs_contiguous_mismatches": len(pvc),
            "paged_vs_contiguous_fields": pvc,
            "over_admissions": paged["over_admissions"],
            "isolation_violations": paged["isolation_violations"],
            "billed_node_hours": paged["node_hours"],
            "dedicated_node_hours": dedicated["node_hours"],
            "billed_vs_dedicated": (paged["node_hours"]
                                    / max(dedicated["node_hours"], 1e-12)),
            "page_size": PAGE_SIZE,
            "pool_pages": paged_x["pool_pages"],
            "peak_pages_used": paged_x["peak_pages_used"],
            "decode_steps": paged_x["decode_steps"],
            "contiguous_decode_steps": contig_x["decode_steps"],
            "wall_s": paged_x["wall_s"],
            "contiguous_wall_s": contig_x["wall_s"],
            "decode_steps_per_sec": (paged_x["decode_steps"]
                                     / max(paged_x["wall_s"], 1e-12)),
        }
        _require(row["parity_mismatches"] == 0,
                 f"emulator-vs-real stats diverged on {parity} "
                 f"(mix={mix_spec})")
        _require(row["paged_vs_contiguous_mismatches"] == 0,
                 f"paged-vs-contiguous stats diverged on {pvc} "
                 f"(mix={mix_spec})")
        rows.append(row)
    return {
        "benchmark": "serve_fleet_real",
        "config": {"workflows": args.workflows,
                   "jobs_scale": args.jobs_scale, "period_s": args.period,
                   "seed": args.seed, "mixes": args.mixes,
                   "arch": "musicgen-large", "max_batch": REAL_MAX_BATCH,
                   "max_len": REAL_MAX_LEN, "page_size": PAGE_SIZE},
        "runs": rows,
    }


def run_matrix_cell(cell: tuple) -> list[dict]:
    """One ``(mix, N)`` point of the sweep — a dedicated baseline plus
    both coordination policies. Top-level (picklable) so ``--procs``
    shards the matrix across a worker pool, exactly as
    ``benchmarks/scale_curve.py`` shards providers; cells are
    seed-deterministic, so sharding cannot change any number."""
    mix_spec, n, workflows, seed, jobs_scale, period, event_skip = cell
    mix = parse_mix(mix_spec)
    streams, widths = tenant_streams(n, workflows, seed, jobs_scale,
                                     period, mix=mix)
    dedicated = run_dedicated(streams, widths, policy=FLEET_POLICY)
    return [run_cell(streams, widths, mix=mix_spec,
                     coordination=coordination, policy=FLEET_POLICY,
                     dedicated=dedicated, event_skip=event_skip)
            for coordination in ("first-come", "coordinated")]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tenants", type=int, nargs="+", default=[1, 3, 6, 12])
    ap.add_argument("--workflows", type=int, default=24,
                    help="workflows per tenant")
    ap.add_argument("--jobs-scale", type=float, default=0.05)
    ap.add_argument("--period", type=float, default=3600.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mixes", nargs="+", default=["1", "1/2/4"],
                    help="width mixes to sweep (cycled across tenants); "
                         "'1' = the homogeneous PR 4 fleet")
    ap.add_argument("--procs", type=int, default=None,
                    help="process-pool width over (mix, N) cells "
                         "(default: min(cells, cpu count))")
    ap.add_argument("--no-event-skip", action="store_true",
                    help="dense tick loop (the reference; results are "
                         "bit-identical either way, only wall differs)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep: fewer tenants, smaller mosaics")
    ap.add_argument("--real", action="store_true",
                    help="heterogeneous fleet on the real jax engine "
                         "(paged + contiguous + emulated twin), pinning "
                         "emulator-vs-real and paged-vs-contiguous parity")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.out is None:
        args.out = ("BENCH_serve_fleet_real.json" if args.real
                    else "BENCH_serve_fleet.json")

    if args.smoke:
        args.tenants = [1, 3, 6]
        args.workflows = 10
        args.jobs_scale = 0.04
        args.period = 3600.0

    if args.real:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        args.workflows = min(args.workflows, 4)
        out = run_real_fleet(args)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=2)
        print(f"wrote {args.out} ({len(out['runs'])} real-engine cells)")
        for r in out["runs"]:
            print(f"  mix={r['mix']:>6s} parity={r['parity_mismatches']} "
                  f"paged-vs-contig={r['paged_vs_contiguous_mismatches']} "
                  f"billed/dedic={r['billed_vs_dedicated']:.3f} "
                  f"pages={r['peak_pages_used']}/{r['pool_pages']} "
                  f"steps={r['decode_steps']} wall={r['wall_s']:.1f}s")
        return out

    policy = FLEET_POLICY
    cells = [(mix_spec, n, args.workflows, args.seed, args.jobs_scale,
              args.period, not args.no_event_skip)
             for mix_spec in args.mixes for n in args.tenants]
    procs = args.procs or min(len(cells), os.cpu_count() or 1)
    if procs > 1:
        with ProcessPoolExecutor(max_workers=procs) as pool:
            per_cell = list(pool.map(run_matrix_cell, cells))
    else:
        per_cell = [run_matrix_cell(c) for c in cells]
    runs = [row for rows in per_cell for row in rows]

    out = {
        "benchmark": "serve_fleet",
        "config": {"tenants": args.tenants, "workflows": args.workflows,
                   "jobs_scale": args.jobs_scale, "period_s": args.period,
                   "seed": args.seed, "smoke": args.smoke,
                   "mixes": args.mixes, "procs": procs,
                   "policy": {"initial": policy.initial,
                              "ratio": policy.ratio,
                              "release_interval": policy.release_interval}},
        "runs": runs,
    }
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)

    n_tasks = sum(r["tasks"] for r in runs)
    print(f"wrote {args.out} ({n_tasks} tasks across {len(runs)} cells)")
    print(f"{'N':>4s} {'mix':>6s} {'policy':>12s} {'pool':>5s} "
          f"{'dedic':>6s} {'billed':>8s} {'vs-dedic':>9s} {'util':>6s} "
          f"{'defer':>6s}")
    for r in runs:
        print(f"{r['n_tenants']:>4d} {r['mix']:>6s} {r['policy']:>12s} "
              f"{r['capacity']:>5d} {r['dedicated_slots']:>6d} "
              f"{r['billed_node_hours']:>8.0f} "
              f"{r['billed_vs_dedicated']:>9.3f} "
              f"{r['slot_utilization']:>6.1%} {r['deferred_grants']:>6d}")
    return out


if __name__ == "__main__":
    main()
