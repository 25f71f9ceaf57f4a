"""Time to first token, 90th percentile, in ms, over every request due in
the window (``serve.ttft_s``)."""
import bench
import serve


def read(rec):
    values = serve.ttft_s(rec)
    return 1000 * bench.percentile(values, 90) if values else None
