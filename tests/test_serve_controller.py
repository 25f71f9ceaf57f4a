"""Serving engine (continuous batching) + live elastic controller tests."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.controller import ElasticController, TrainTask
from repro.core.policy import MgmtPolicy
from repro.core.provision import ProvisionService
from repro.models.lm import LM
from repro.serve.engine import Engine, Request
from tests.conftest import SMOKE_PARALLEL, smoke_runconfig


@pytest.fixture(scope="module")
def granite_engine():
    cfg = get_smoke_config("granite-3-8b")
    lm = LM(cfg)
    rt = lm.runtime(SMOKE_PARALLEL)
    params = lm.init(jax.random.key(0))[0]
    return lm, params, rt


def _req(rid, plen=8, n=4):
    return Request(rid=rid, tokens=(np.arange(plen) % 7 + 1).astype(np.int32),
                   max_new_tokens=n)


def test_engine_continuous_batching(granite_engine):
    lm, params, rt = granite_engine
    eng = Engine(lm, params, rt, max_batch=2, max_len=32)
    done = eng.run([_req(i) for i in range(5)])
    assert len(done) == 5
    assert all(len(r.out_tokens) == 4 for r in done)
    # slots freed: engine reusable
    assert len(eng.free) == 2 and not eng.active


def test_batching_does_not_change_results(granite_engine):
    """Greedy output of a request must not depend on its batch-mates."""
    lm, params, rt = granite_engine
    eng1 = Engine(lm, params, rt, max_batch=1, max_len=32)
    solo = eng1.run([_req(0, plen=6, n=5)])[0]
    eng2 = Engine(lm, params, rt, max_batch=3, max_len=32)
    reqs = [_req(0, plen=6, n=5), _req(1, plen=9, n=3), _req(2, plen=4, n=5)]
    batched = {r.rid: r for r in eng2.run(reqs)}
    np.testing.assert_array_equal(np.asarray(solo.out_tokens),
                                  np.asarray(batched[0].out_tokens))


def test_engine_rejects_oversized_request(granite_engine):
    """Oversize requests are rejected individually, never raised: the
    request comes back marked ``rejected`` with no output and the engine
    keeps its slot free for admissible work."""
    lm, params, rt = granite_engine
    eng = Engine(lm, params, rt, max_batch=1, max_len=16)
    req = _req(0, plen=14, n=8)
    assert not eng.admit(req)
    assert req.rejected and req.done and not req.out_tokens
    assert len(eng.free) == 1 and not eng.active


def test_controller_runs_queue_with_failures(tmp_path):
    rcfg = smoke_runconfig("qwen2-7b", total_steps=100)
    prov = ProvisionService(capacity=8)
    ctl = ElasticController(policy=MgmtPolicy.htc(1, 1.0), provision=prov,
                            steps_per_tick=4, elastic_grow=False)
    tasks = [TrainTask(f"job-{i}", rcfg, nodes=1, num_steps=8,
                       ckpt_dir=str(tmp_path / f"j{i}")) for i in range(2)]
    for t in tasks:
        ctl.submit(t)
    ctl.run(fail_at={2: "job-0"})
    ctl.destroy()
    assert len(ctl.finished) == 2
    assert all(t.done for t in ctl.finished)
    assert tasks[0].restarts == 1
    # DSP accounting happened: initial lease + any dynamic grants all closed
    assert prov.total_allocated == 0
    assert prov.adjust_count() >= 2


def test_controller_policy_grows_for_queue(tmp_path):
    """Two 1-node jobs + B=1: the DSP scan must lease a second node.

    CPU has one device; the controller's node bookkeeping is exercised by
    padding the device list (each 1-node task still runs on mesh=None)."""
    rcfg = smoke_runconfig("qwen2-7b", total_steps=100)
    prov = ProvisionService(capacity=4)
    ctl = ElasticController(policy=MgmtPolicy.htc(1, 1.0), provision=prov,
                            steps_per_tick=4, elastic_grow=False,
                            devices=jax.devices() * 4)
    for i in range(3):
        ctl.submit(TrainTask(f"j{i}", rcfg, nodes=1, num_steps=4,
                             ckpt_dir=str(tmp_path / f"g{i}")))
    ctl.tick()
    assert ctl.owned >= 2   # grew beyond the single initial node
    ctl.run()
    assert len(ctl.finished) == 3


class _PlacementController(ElasticController):
    """Segments record the devices they are given instead of training."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.placements: dict[int, dict[str, tuple[list, list]]] = {}

    def _run_segment(self, task, fail=False):
        self.placements.setdefault(self._tick, {})[task.name] = (
            list(task.devices), self.devices_of(task))
        task.steps_done = min(task.steps_done + self.steps_per_tick,
                              task.num_steps)


def test_concurrent_jobs_hold_disjoint_devices():
    """Jobs running at the same tick never share a device index, through
    grows and shrinks; each segment gets the devices its indices name,
    and a one-node job gets its own device, not the first (default) one.
    Indices, not device objects, are the contract: the CPU tests pad the
    list with copies of one device."""
    devices = [object() for _ in range(4)]
    ctl = _PlacementController(policy=MgmtPolicy.htc(3, 1.0),
                               provision=ProvisionService(capacity=4),
                               devices=devices, steps_per_tick=1,
                               elastic_grow=True)
    a = TrainTask("a", None, nodes=1, num_steps=3, ckpt_dir="")
    b = TrainTask("b", None, nodes=1, num_steps=6, ckpt_dir="")
    c = TrainTask("c", None, nodes=2, num_steps=2, ckpt_dir="")
    ctl.submit(a)
    ctl.submit(b)
    ctl.tick()
    ctl.submit(c)            # queued demand: grown jobs shrink back
    ctl.run()
    ctl.destroy()
    assert len(ctl.finished) == 3
    for tick, held in ctl.placements.items():
        idx = [i for ids, _ in held.values() for i in ids]
        assert len(idx) == len(set(idx)), (tick, held)
        for ids, objs in held.values():
            assert objs == [devices[i] for i in ids]
    assert ctl.placements[1] == {"a": ([0, 2], [devices[0], devices[2]]),
                                 "b": ([1], [devices[1]])}
    assert a.resizes >= 2                     # grown, then shrunk
    assert ctl.placements[6]["b"][0] == [1, 0]   # grew into a's device
    assert ctl._free_devices == [0, 1, 2, 3]
    assert all(t.devices == [] and t.alloc == 0 for t in ctl.finished)
