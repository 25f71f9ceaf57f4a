"""Shared test fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches
must see the real single CPU device.

Also provides a ``hypothesis`` degradation shim: property-based tests import
``given``/``settings``/``st`` from here instead of from ``hypothesis``
directly, so that on machines without hypothesis installed the property
tests *skip* (instead of hard-crashing collection) while every
example-based test in the same module still runs. Install the real thing
with ``pip install -e .[test]``.
"""
from __future__ import annotations

import dataclasses

import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ParallelConfig, RunConfig, ShapeConfig

try:
    from hypothesis import given, settings, strategies as st  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:                                # degrade: skip, don't crash
    HAVE_HYPOTHESIS = False

    class _AnyStrategy:
        """Stands in for ``hypothesis.strategies``: every strategy builder
        (``st.lists(...)``, ``st.integers(...)``, ...) returns None, which is
        fine because the decorated test body never runs."""

        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

    def given(*_args, **_kwargs):
        def deco(fn):
            def _skipped():
                pytest.skip("hypothesis not installed (pip install -e .[test])")
            _skipped.__name__ = fn.__name__
            _skipped.__doc__ = fn.__doc__
            return _skipped
        return deco

    def settings(*_args, **_kwargs):
        return lambda fn: fn

SMOKE_SHAPE = ShapeConfig("smoke", "train", 32, 2)
SMOKE_PARALLEL = ParallelConfig(attn_q_chunk=16, attn_kv_chunk=16)


def smoke_runconfig(arch: str, **over) -> RunConfig:
    cfg = dataclasses.replace(get_smoke_config(arch), n_patches=8)
    return RunConfig(model=cfg, shape=SMOKE_SHAPE, parallel=SMOKE_PARALLEL,
                     total_steps=over.pop("total_steps", 20),
                     warmup_steps=2, **over)


@pytest.fixture
def tmp_ckpt(tmp_path):
    return str(tmp_path / "ckpt")
