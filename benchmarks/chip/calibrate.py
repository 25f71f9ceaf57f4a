"""Readings that a serving cell's limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,3 --seconds 20 [--control]

For each seed, in one process: the cell's weights and engine, a window of
``--seconds`` at the cell's own load (the traffic mix as the cell runs
it), and then the widest gap over the seed's sample of served requests,
as a run's check computes it (the lower reading). With ``--control`` also
the control's reading on the same prompts and served tokens: the gap of
the token that the reference computed in the next lower precision puts
first at each position (the upper reading). One JSON line per seed, with
the window's times as ``sweep.py`` sums them up and the share of served
tokens that repeat the token before them.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench  # noqa: E402
import sweep  # noqa: E402

#: the precision below bfloat16 that the control computes in
CONTROL = "fp8"


def repeats(served) -> float:
    """Share of served tokens that repeat the token before them: near 1,
    the model's output would not depend on what the cache holds."""
    import numpy as np
    same = total = 0
    for s in served:
        seq = np.concatenate([s.req.tokens[-1:],
                              np.asarray(s.req.out_tokens).reshape(-1)])
        same += int(np.sum(seq[1:] == seq[:-1]))
        total += len(seq) - 1
    return same / max(total, 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg_path = bench.ROOT / entry["file"]
    cfg = json.loads(cfg_path.read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    import jax
    import serve
    bench.enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    ref = bench.load_module(cfg_path.parent / cfg["reference"])
    buckets = sorted(mix["prompt_tokens"]["buckets"])
    for seed in (int(s) for s in args.seeds.split(",")):
        key = bench.prng_key(seed)
        engine = serve.build(cfg, ref, key, dev, buckets)
        t0 = time.perf_counter()
        served = serve.requests(mix, args.seconds, seed, cfg["vocab_size"], t0)
        with jax.default_device(dev):
            steps = serve.open_loop(engine, served, t0, args.seconds,
                                    bench.Spans())
        del engine
        gc.collect()
        out = {"seed": seed, "device": dev.device_kind,
               "unserved": len(served) - len(serve.finished(served)),
               "repeats_last_token": repeats(served),
               **sweep.summary(served, t0, args.seconds, steps)}
        with jax.default_device(dev):
            out["program_gap"] = serve.widest_gap(cfg, ref, key, served, mix,
                                                  seed)
            if args.control:
                out["control_gap"] = serve.widest_gap(cfg, ref, key, served,
                                                      mix, seed, CONTROL)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
