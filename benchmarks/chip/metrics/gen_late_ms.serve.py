"""How late the load generator queued requests, 95th percentile, in ms.

The loop is one thread and the engine's calls block, so a request due
while a step or an admission runs is queued when the call returns; this
is that delay, a part of each time to first token."""
import bench


def read(rec):
    t0, t1 = rec["window"]
    late = [r["sent"] - r["due"] for r in rec["requests"]
            if r["sent"] is not None and t0 <= r["due"] < t1]
    return 1000 * bench.percentile(late, 95) if late else None
