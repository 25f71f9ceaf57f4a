"""Find a serving cell's knee: the highest offered rate whose queue does not
grow over the window. Run once, on the chip, when a cell is defined.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3,4 \\
        --seconds 30 --seed <n>

One process builds the cell's engine once, then offers its traffic mix at
each rate in turn (the mix's own rate replaced), each for ``--seconds``,
and prints one JSON line per rate: requests, output tokens per second,
time to first token (median, 95th percentile) over all requests and over
the first and last thirds of the window, the gap between tokens (median,
95th percentile), and the longest queue. A queue
that grows shows as a last third whose times to first token keep rising
above the first third's.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench  # noqa: E402


def summary(served, t0: float, seconds: float, steps) -> dict:
    import numpy as np
    ttft = np.asarray([(s.times[0] if s.times else np.inf) - s.due
                       for s in served])
    due = np.asarray([s.due - t0 for s in served])
    first, last = due < seconds / 3, due >= 2 * seconds / 3
    toks = sum(t0 <= t <= t0 + seconds for s in served for t in s.times)
    itl = [b - a for s in served for a, b in zip(s.times, s.times[1:])]
    backlog = max((sum(1 for s in served if s.due <= t and
                       (not s.times or s.times[0] > t))
                   for t, *_ in steps), default=0)
    ms = lambda a, q: (round(1000 * float(np.percentile(a, q)), 3)  # noqa
                       if len(a) else None)
    return {"requests": len(served), "out_tokens_per_s": toks / seconds,
            "ttft_p50_ms": ms(ttft, 50), "ttft_p95_ms": ms(ttft, 95),
            "ttft_first_third_p50_ms": ms(ttft[first], 50),
            "ttft_last_third_p50_ms": ms(ttft[last], 50),
            "itl_p50_ms": ms(itl, 50), "itl_p95_ms": ms(itl, 95),
            "longest_queue": backlog,
            "mean_rows_per_step": float(np.mean([r for _, r, *_ in steps]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
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
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    ref = bench.load_module(cfg_path.parent / cfg["reference"])
    t = time.perf_counter()
    engine = serve.build(cfg, ref, bench.prng_key(args.seed), dev,
                         sorted(mix["prompt_tokens"]["buckets"]))
    print(f"set-up {time.perf_counter() - t:.2f} s on {dev.device_kind}",
          flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix_r = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        t0 = time.perf_counter()
        served = serve.requests(mix_r, args.seconds, args.seed,
                                cfg["vocab_size"], t0)
        with jax.default_device(dev):
            steps = serve.open_loop(engine, served, t0, args.seconds,
                                    bench.Spans())
        print(json.dumps({"rate_per_s": rate, "device": dev.device_kind,
                          **summary(served, t0, args.seconds, steps)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
