"""Gap between consecutive output tokens, median, in ms, over every gap
that ends in the window (``serve.gaps_s``)."""
import bench
import serve


def read(rec):
    values = serve.gaps_s(rec)
    return 1000 * bench.percentile(values, 50) if values else None
