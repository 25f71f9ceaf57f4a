"""Control-plane invariants survive ``python -O``.

Each test triggers one of the seven invariants that used to be bare
``assert`` statements (``core/tre.py`` x3, ``core/controller.py``,
``sim/engine.py``, ``sim/systems.py``, ``sim/traces.py``) and pins that
violating it raises a *guarded* error. Pre-conversion these tests fail
twice over: under normal python the violation raised ``AssertionError``
(wrong type, no message), and under ``python -O`` it raised nothing at
all and silently corrupted ledger/graph state. The suite runs in both
CI legs; the ``-O`` leg is the one these guards exist for.

Static companion: dclint rule DC101 rejects new bare asserts in
``src/repro/{core,serve,sim}`` at authoring time.
"""
from __future__ import annotations

import pytest

from repro.core.provision import ProvisionService
from repro.core.tre import HTCRuntimeEnv, TickClock
from repro.core.types import Job
from repro.sim.engine import Sim
from repro.sim.traces import _check_montage_graph, montage_like


def _env(nodes: int = 8) -> HTCRuntimeEnv:
    return HTCRuntimeEnv("t0", provision=ProvisionService(),
                         clock=TickClock(), launch=lambda task: None,
                         fixed_nodes=nodes)


# --------------------------------------------------------- core/tre.py
def test_extended_track_rejects_duplicate_jid():
    env = _env()
    env.track([Job(jid=1, arrival=0.0, runtime=1.0, nodes=1)])
    with pytest.raises(RuntimeError, match="duplicate jid 1"):
        env.track([Job(jid=1, arrival=0.0, runtime=1.0, nodes=1)],
                  extend=True)


def test_grow_beyond_free_raises():
    env = _env(nodes=4)
    task = Job(jid=1, arrival=0.0, runtime=10.0, nodes=2)
    env.track([task])
    env.submit(task)                      # fixed mode schedules immediately
    with pytest.raises(RuntimeError, match="grow exceeds free"):
        env.grow(task, env.free + 1)
    env.grow(task, env.free)              # exactly-free still allowed
    assert env.busy == 4


def test_shrink_beyond_allocation_raises():
    env = _env(nodes=4)
    task = Job(jid=1, arrival=0.0, runtime=10.0, nodes=2)
    env.track([task])
    env.submit(task)
    with pytest.raises(RuntimeError, match="shrink exceeds task allocation"):
        env.shrink(task, 3)
    env.shrink(task, 2)
    assert env.busy == 0


# --------------------------------------------------- core/controller.py
def test_mesh_wider_than_device_pool_raises():
    from repro.core.controller import ElasticController

    class _Stub:
        devices = [object(), object()]
        _free_devices = [0, 1]

    # unbound call on a stub: the guard must fire before any jax import
    with pytest.raises(RuntimeError, match="mesh wider than device pool"):
        ElasticController._take_devices(_Stub(), 3)


# ------------------------------------------------------- sim/engine.py
def test_event_scheduled_in_past_raises():
    sim = Sim()
    sim.at(5.0, lambda: None)
    sim.run()
    with pytest.raises(RuntimeError, match="event scheduled in the past"):
        sim.at(1.0, lambda: None)
    sim.at(5.0, lambda: None)             # equal-time (epsilon) still fine


# ------------------------------------------------------ sim/systems.py
def test_unknown_tre_mode_raises():
    from repro.sim.systems import REServer

    with pytest.raises(ValueError, match="unknown TRE mode 'bogus'"):
        REServer(None, None, None, mode="bogus")


# ------------------------------------------------------- sim/traces.py
def test_montage_graph_miscount_raises():
    with pytest.raises(RuntimeError, match="montage graph inconsistent"):
        _check_montage_graph(9, 1)
    _check_montage_graph(10, 1)           # 6*1+4: consistent
    # and the real generator still satisfies its own guard
    assert len(montage_like(seed=0, n_project=5).jobs) == 34
