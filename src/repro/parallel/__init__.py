from repro.parallel.sharding import (  # noqa: F401
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_POD,
    batch_axes,
    logical_rules,
    resolve_spec,
)
