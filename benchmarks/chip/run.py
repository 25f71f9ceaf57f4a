"""Run one cell of the chip benchmark once, and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
the names in ``BENCHMARK.json`` at the root of the checkout:
``configs/<config>.json`` (with ``"runner"``, the module here that drives
that kind of configuration, and ``"reference"``, its plain reference
beside it), ``traffic/<mix>.json`` and ``metrics/<metric>.py``. With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and from the benchmark's own spans and counters.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``, each number compared with its limit.
The same checks are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import json
import math
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench  # noqa: E402


def cell_metrics(spec: dict, cell: dict, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    metrics (those that list the cell, or without a list, those whose
    end-to-end metric the cell reports)."""
    name = cell["name"]
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in moved
                             else [])]


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else str(x)


def main(argv=None) -> int:
    started = bench.process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace in this directory")
    args = ap.parse_args(argv)

    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config_path = bench.ROOT / entry["file"]
    config = json.loads(config_path.read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    import jax
    import peaks
    bench.enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s). "
              f"Nothing was run.", file=sys.stderr)
        return 2
    peaks.peaks(devices[0].device_kind)     # an unknown chip is an error

    runner = importlib.import_module(config["runner"])
    record = runner.run(bench.Cell(
        name=cell["name"], chips=cell["chips"], config=config,
        config_dir=config_path.parent, traffic=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        devices=devices[:cell["chips"]], started=started,
        trace_dir=args.trace_dir))
    metrics = bench.read_metrics(cell_metrics(spec, cell, bool(args.trace)),
                                 record)
    device = record["device"]
    label = f"{device['kind']} x{device['count']}"
    for line in record.get("notes", []):
        print(f"{line} ({label})")
    for name, m in metrics.items():
        print(f"{name} = {m['value']!r} {m['unit']} ({label})")
    out = {"correct": record["correct"], "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    reduced = record.get("trace")
    if args.trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {k: {"value": _finite(v["value"]),
                         "limit": _finite(v["limit"])}
                     for k, v in record["checks"].items()}
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
