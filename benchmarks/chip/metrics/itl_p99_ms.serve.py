"""Gap between consecutive output tokens, 99th percentile, in ms, over
every gap that ends in the window (``serve.gaps_s``). Below the knee the
admission stalls are a few percent of the gaps, so this percentile lies
among them and reads how long an admission holds the decoding rows; which
admissions meet many decoding rows is the seed's, so it swings from seed
to seed and is recorded, not judged."""
import bench
import serve


def read(rec):
    values = serve.gaps_s(rec)
    return 1000 * bench.percentile(values, 99) if values else None
