from repro.data.synthetic import synthetic_batches  # noqa: F401
