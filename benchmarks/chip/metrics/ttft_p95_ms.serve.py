"""Time to first token, 95th percentile, in ms, over every request due in
the window (``serve.ttft_s``): with some thirty requests in a window, the
tail is set by the few that wait for a free slot behind a clump of long
prompts, so it swings from seed to seed. Recorded here, beside the
judged 90th percentile."""
import bench
import serve


def read(rec):
    values = serve.ttft_s(rec)
    return 1000 * bench.percentile(values, 95) if values else None
