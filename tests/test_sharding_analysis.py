"""Sharding rule resolution unit tests.

These run on the single CPU device (no mesh construction with >1 device
needed: Mesh objects over 1 device still exercise the rule logic via a
fake mesh namespace)."""
from __future__ import annotations

import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import resolve_spec


class FakeMesh:
    """Duck-typed mesh: .axis_names + .shape mapping (enough for rules)."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


POD_MESH = FakeMesh(data=16, model=16)
MULTI_MESH = FakeMesh(pod=2, data=16, model=16)


def test_tp_rules_shard_model_axes():
    spec = resolve_spec(("embed", "mlp"), (4096, 12800), POD_MESH, "tp")
    assert spec == P(None, "model")
    spec = resolve_spec(("vocab", "embed"), (49408, 4096), POD_MESH, "tp")
    assert spec == P("model")


def test_small_dims_replicate():
    # 8 kv heads over a 16-way axis would waste >2x: replicate
    spec = resolve_spec(("kv_heads",), (8,), POD_MESH, "tp")
    assert spec == P()
    # non-divisible dims replicate too
    spec = resolve_spec(("mlp",), (100,), POD_MESH, "tp")
    assert spec == P()


def test_fsdp_adds_data_axis_and_pod():
    spec = resolve_spec(("embed", "mlp"), (8192, 24576), POD_MESH, "fsdp_tp")
    assert spec == P("data", "model")
    spec = resolve_spec(("embed", "mlp"), (8192, 24576), MULTI_MESH,
                        "fsdp_tp")
    assert spec == P(("pod", "data"), "model")
    # a dim divisible by 16 but not 32 drops the pod axis, keeps data
    spec = resolve_spec(("embed",), (16 * 3,), MULTI_MESH, "fsdp_tp")
    assert spec == P("data")


def test_axis_used_once():
    spec = resolve_spec(("mlp", "vocab"), (12800, 49408), POD_MESH, "tp")
    assert spec == P("model")   # vocab loses: model already used


def test_unknown_strategy_raises():
    with pytest.raises(ValueError):
        resolve_spec(("embed",), (64,), POD_MESH, "zeRO9")

