"""Multi-tenant serving fleet: N=1 bit-parity with ServeDriver, slot
partitioning/isolation properties, guarded-raise invariants (the checks
that must survive ``python -O``), and the registered scenario.

The fleet parity contract (tests/README.md): ``ServeFleet`` replays
``ServeDriver._tick``'s phases phase-major across tenants with one
fleet-wide decode step, so a fleet of ONE tenant must be bit-identical to
a standalone ``ServeDriver`` on the same stream and grant sequence —
same lease adjustments at the same instants, same task times, same
completion order, same ``ServeStats``. The partitioning property: at
every tick, ``sum(tenant.active) <= engine.capacity`` and
``tenant.active <= tenant.granted`` per tenant.
"""
from __future__ import annotations

from dataclasses import replace

import pytest

from tests.conftest import given, settings, st
from tests.test_serve_driver import (
    PARITY_CAPACITY, PARITY_CONTENTION, PARITY_POLICY, PARITY_W1, PARITY_W2,
    _dag_from_spec, montage_mini,
)

from repro.core.policy import MgmtPolicy
from repro.core.provider import ResourceProvider
from repro.core.provision import ProvisionService
from repro.core.registry import available_systems, get_system
from repro.core.types import Job
from repro.serve.driver import EmulatedEngine, ServeDriver, ServeInvariantError
from repro.serve.fleet import (
    PartitionedEngine, ServeFleet, aggregate_decode_peak,
)


# ---------------------------------------------------------------- helpers
class RecordingFleet(ServeFleet):
    """Record the partition state after every tick so the property is
    checked from OUTSIDE the fleet's own invariant machinery. Samples are
    width-weighted node units; for an all-width-1 fleet units == slots,
    so the weighted property IS the PR 4 partitioning property."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.samples: list[tuple[int, list[tuple[int, int]]]] = []

    def _tick(self, k):
        super()._tick(k)
        per_tenant = [(self.pool.units_of(lane.env.name), lane.env.owned)
                      for lane in self.lanes]
        self.samples.append((self.pool.active_units, per_tenant))


def _assert_partition_property(fleet: RecordingFleet) -> None:
    cap = fleet.stats.capacity
    for total, per_tenant in fleet.samples:
        assert total <= cap
        assert total == sum(units for units, _ in per_tenant)
        for units, granted in per_tenant:
            assert units <= granted


def _tenant_dags(specs: list[list[tuple[int, int]]]) -> list[list]:
    """Per-tenant single-workflow streams with disjoint jid ranges."""
    streams, base = [], 0
    for w, spec in enumerate(specs):
        jobs = _dag_from_spec(spec, wid=w, base=base)
        base += len(jobs)
        streams.append([(0.0, jobs)])
    return streams


FLEET_POLICY = MgmtPolicy(initial=1, ratio=1.0, scan_interval=3.0,
                          release_interval=60.0)


# ----------------------------------------------------------------- parity
def test_fleet_of_one_is_bit_identical_to_serve_driver():
    """ServeFleet(N=1) vs ServeDriver on the same two-workflow stream and
    the same scripted co-tenant grant sequence: identical lease
    adjustments (values AND instants), task start/finish times,
    completion order, and the full per-tenant stats record."""
    w1 = [j.fresh() for j in PARITY_W1]
    w2 = [j.fresh() for j in PARITY_W2]
    prov = ResourceProvider(PARITY_CAPACITY, coordination="first-come")
    drv = ServeDriver([(0.0, w1), (31.0, w2)], provider=prov,
                      engine=EmulatedEngine(PARITY_CAPACITY),
                      policy=PARITY_POLICY, name="parity-serve",
                      contention=PARITY_CONTENTION)
    ref = drv.run()

    f1 = [j.fresh() for j in PARITY_W1]
    f2 = [j.fresh() for j in PARITY_W2]
    fleet = ServeFleet([[(0.0, f1), (31.0, f2)]],
                       engine=EmulatedEngine(PARITY_CAPACITY),
                       coordination="first-come", policies=PARITY_POLICY,
                       names=["parity-serve"],
                       contention=PARITY_CONTENTION)
    fs = fleet.run()

    assert ([(e.t, e.delta) for e in prov.adjust_events
             if e.tre == "parity-serve"]
            == [(e.t, e.delta) for e in fleet.provider.adjust_events
                if e.tre == "parity-serve"])
    assert ([j.name for j in drv.env.completed]
            == [j.name for j in fleet.lanes[0].env.completed])
    assert ({j.name: (j.start, j.finish) for j in w1 + w2}
            == {j.name: (j.start, j.finish) for j in f1 + f2})
    assert ref.as_dict() == fleet.lanes[0].stats.as_dict()
    assert fs.over_admissions == 0 and fs.isolation_violations == 0
    assert fs.workflows_completed == 2 and fs.deferred_grants == 1


def test_fleet_shares_one_pool_and_retires_finished_tenants():
    """Three tenants on one pool, both coordination policies: everything
    completes, zero over-admissions, zero isolation violations, every
    lease closed (finished tenants are destroyed mid-run, returning
    their slots to the pool), and the partition property holds at every
    tick."""
    for coordination in ("first-come", "coordinated"):
        streams = [
            [(0.0, montage_mini(0, 0.0, 0))],
            [(7.0, montage_mini(100, 7.0, 1))],
            [(13.0, montage_mini(200, 13.0, 2))],
        ]
        fleet = RecordingFleet(streams, engine=EmulatedEngine(6),
                               coordination=coordination,
                               policies=FLEET_POLICY)
        fs = fleet.run()
        assert fs.workflows_completed == 3
        assert fs.tasks_completed == 3 * len(montage_mini())
        assert fs.over_admissions == 0 and fs.isolation_violations == 0
        assert fleet.provider.total_allocated == 0
        assert fs.node_hours > 0 and fs.slot_utilization > 0
        _assert_partition_property(fleet)
        # consolidation was real: the whole pool served at some tick, and
        # no single tenant ever owned it all
        assert fs.peak_pool_active == 6
        assert max(t["peak_owned"] for t in fs.tenants) < 6
        # tenants finished at different instants -> earlier finishers
        # were destroyed (their lanes' makespans differ from the fleet's)
        makespans = sorted(t["makespan_s"] for t in fs.tenants)
        assert makespans[0] < fs.makespan_s


def test_cutoff_stragglers_do_not_bill_zero_duration_leases():
    """Regression: at the tick-budget cutoff, finalizing straggler lanes
    one at a time let one lane's ``destroy`` (which drains the admission
    queue as it releases nodes) grant ANOTHER straggler's still-parked
    request at the cutoff instant — a zero-duration lease billed a whole
    hour per node. All parked requests must be withdrawn (``drain=False``)
    before the finalize loop, as the emulator teardown does; billed
    node-hours at cutoff must equal one lease-hour per initially-held
    slot, nothing more."""
    streams, base = [], 0
    for w in range(3):                       # 3 starved tenants, wide work
        jobs = _dag_from_spec([(100, 0)] * 6, wid=w, base=base)
        base += len(jobs)
        streams.append([(0.0, jobs)])
    pol = MgmtPolicy(initial=1, ratio=1.0, scan_interval=3.0,
                     release_interval=60.0)
    fleet = ServeFleet(streams, engine=EmulatedEngine(3),
                       policies=[pol] * 3, max_ticks=20, strict=True)
    fs = fleet.run()
    assert fs.workflows_completed == 0       # genuinely cut off mid-run
    assert fs.node_hours == 3.0              # 3 initial slots x 1 h, no
    assert fleet.provider.total_allocated == 0  # phantom cutoff grants


# --------------------------------------------------------- heterogeneous
def _wide_dag(spec, wid, base, width):
    """``_dag_from_spec`` at a tenant's slot width (nodes == width)."""
    return [replace(j, nodes=width) for j in _dag_from_spec(spec, wid, base)]


def test_all_width_one_fleet_is_bit_identical_to_unweighted():
    """The homogeneous pin: an explicit widths=[1,...] fleet must be
    bit-identical to the default (PR 4) fleet — same stats record, same
    lease adjustments at the same instants."""
    def build(widths):
        streams = [
            [(0.0, montage_mini(0, 0.0, 0))],
            [(7.0, montage_mini(100, 7.0, 1))],
            [(13.0, montage_mini(200, 13.0, 2))],
        ]
        fleet = ServeFleet(streams, engine=EmulatedEngine(6),
                           coordination="coordinated",
                           policies=FLEET_POLICY, widths=widths)
        fs = fleet.run()
        return fs, [(e.t, e.tre, e.delta)
                    for e in fleet.provider.adjust_events]
    ref, ref_events = build(None)
    pin, pin_events = build([1, 1, 1])
    assert ref.as_dict() == pin.as_dict()
    assert ref_events == pin_events
    assert pin.widths == [1, 1, 1]
    assert pin.peak_pool_units == pin.peak_pool_active


def test_hetero_fleet_mixed_widths_completes_and_isolates():
    """The tentpole end-to-end: three tenants of widths 1/2/4 share one
    weighted pool — everything completes under both coordination
    policies with zero over-admissions and zero weighted-isolation
    violations, the weighted partition property holds at every tick, and
    the big-model tenant's billing is unit-denominated (wider than its
    slot count)."""
    spec = [(3, 0)] * 5 + [(2, 1)] * 3
    widths = [1, 2, 4]
    for coordination in ("first-come", "coordinated"):
        streams = [[(0.0, _wide_dag(spec, 0, 0, 1))],
                   [(5.0, _wide_dag(spec, 1, 100, 2))],
                   [(11.0, _wide_dag(spec, 2, 200, 4))]]
        policies = [MgmtPolicy(initial=w, ratio=1.0, scan_interval=3.0,
                               release_interval=60.0) for w in widths]
        fleet = RecordingFleet(streams, engine=EmulatedEngine(14),
                               coordination=coordination,
                               policies=policies, widths=widths)
        fs = fleet.run()
        assert fs.workflows_completed == 3
        assert fs.tasks_completed == 3 * len(spec)
        assert fs.over_admissions == 0 and fs.isolation_violations == 0
        assert fs.widths == widths
        assert fleet.provider.total_allocated == 0
        _assert_partition_property(fleet)
        # weighted accounting is real: the width-4 tenant's peak owned
        # units reach beyond what a slot-count ledger would show
        t4 = fs.tenants[2]
        assert t4["slot_width"] == 4
        assert t4["peak_owned"] >= 4
        assert fs.peak_pool_units <= 14
        assert fs.peak_pool_units >= fs.peak_pool_active


def test_partitioned_engine_weighted_isolation():
    """Width-weighted slot accounting: a width-3 tenant's admit is
    checked in units (slots x width) against its granted units, and the
    pool check is ``sum(active_i * width_i) <= capacity``."""
    jobs = [Job(jid=i, arrival=0.0, runtime=2.0, nodes=1, decode_len=2)
            for i in range(8)]
    pool = PartitionedEngine(EmulatedEngine(8))
    va, vb = pool.view("a", width=3), pool.view("b", width=1)
    granted = {"a": 6, "b": 3}
    pool.bind("a", lambda: granted["a"])
    pool.bind("b", lambda: granted["b"])
    assert va.width == 3 and vb.width == 1
    va.admit_many(jobs[:2])               # 2 slots x 3 = 6 units: exact fit
    assert pool.units_of("a") == 6 and pool.active_of("a") == 2
    with pytest.raises(ServeInvariantError, match="another tenant's slots"):
        va.admit_many(jobs[2:3])          # (2+1) x 3 = 9 > 6 granted units
    # b's grant allows 3 slots, but the weighted pool only has 2 units
    with pytest.raises(ServeInvariantError, match="beyond the pool"):
        vb.admit_many(jobs[3:6])          # 6 + 3 > 8 capacity units
    vb.admit_many(jobs[3:5])              # 6 + 2 = 8: full
    assert pool.active_units == 8 and pool.active_total == 4
    # a grant ceiling dropping below the tenant's active UNITS is caught
    pool.check_isolation()
    granted["a"] = 5                      # 6 active units > 5 granted
    with pytest.raises(ServeInvariantError, match="foreign slots"):
        pool.check_isolation()
    with pytest.raises(ValueError, match="exceeds the pool"):
        pool.view("huge", width=9)


def test_nonstrict_admit_truncation_returns_subset_and_requeues():
    """Satellite regression (fails pre-fix): non-strict ``admit_many``
    used to truncate a batch to the pool's free slots and DROP the
    remainder — the lane never learned its jobs were not admitted, so
    counting-mode fleets lost workflows and spun to max_ticks. The pool
    must return the admitted subset, and the driver must requeue the
    rest in its launch buffer until slots free."""
    jobs = [Job(jid=i, arrival=0.0, runtime=2.0, nodes=1, decode_len=2,
                name=f"j{i}") for i in range(4)]
    lax = PartitionedEngine(EmulatedEngine(2), strict=False)
    va = lax.view("a")
    lax.bind("a", lambda: 4)              # overstated grant: pool is 2
    admitted = va.admit_many(jobs)
    assert admitted is not None and [j.jid for j in admitted] == [0, 1]
    assert lax.isolation_violations == 1 and lax.active_of("a") == 2

    # end to end: a driver over a too-small non-strict pool completes
    # EVERY workflow because the truncated remainder is retried, and the
    # buffered tasks still count in the engine/env consistency check
    pool = PartitionedEngine(EmulatedEngine(2), strict=False)
    view = pool.view("t")
    pool.bind("t", lambda: 4)
    drv = ServeDriver(
        [(0.0, [j.fresh() for j in jobs])], provider=ProvisionService(),
        engine=view, fixed_nodes=4, strict=False, name="t")

    # route the pool's fleet-style step through the driver's tick loop
    k = 0
    drv._tick(0)
    while not drv._done and k < drv.max_ticks:
        k += 1
        drv.clock.advance(1.0)
        pool.step_all()
        drv._tick(k)
    stats = drv.finalize(k)
    assert stats.workflows_completed == stats.workflows_expected == 1
    assert stats.tasks_completed == 4
    assert pool.isolation_violations > 0  # truncation really happened


def test_nonstrict_fleet_pool_shrink_loses_no_workflows():
    """Fleet-level companion: shrink the pool under a running non-strict
    fleet (simulated capacity loss after grants) — admits truncate and
    requeue instead of dropping, so every workflow still completes."""
    streams = [[(0.0, montage_mini(0, 0.0, 0))],
               [(5.0, montage_mini(100, 5.0, 1))]]
    fleet = ServeFleet(streams, engine=EmulatedEngine(6),
                       policies=FLEET_POLICY, strict=False)
    fleet.pool.capacity = 2
    fs = fleet.run()
    assert fs.workflows_completed == fs.workflows_expected == 2
    assert fs.tasks_completed == 2 * len(montage_mini())
    assert fleet.pool.isolation_violations > 0


def test_aggregate_decode_peak_is_width_weighted():
    """Capacity planning charges a width-w task at w units per service
    tick — the same hour of decode work at width 2 needs twice the pool."""
    def jobs(width):
        return [Job(jid=i, arrival=0.0, runtime=1.0, nodes=width,
                    decode_len=1800) for i in range(2)]
    narrow = [[(0.0, jobs(1)[:1]), (10.0, jobs(1)[1:])]]
    wide = [[(0.0, jobs(2)[:1]), (10.0, jobs(2)[1:])]]
    assert aggregate_decode_peak(narrow) == 1
    assert aggregate_decode_peak(wide) == 2


def test_serve_hetero_system_registered_and_serves():
    assert "dawningcloud-serve-hetero" in available_systems()
    impl = get_system("dawningcloud-serve-hetero")
    assert impl.tenant_widths(5) == [1, 2, 4, 1, 2]
    spec = [(3, 0)] * 4
    streams = [[(0.0, _wide_dag(spec, 0, 0, 1))],
               [(3.0, _wide_dag(spec, 1, 100, 2))],
               [(7.0, _wide_dag(spec, 2, 200, 4))]]
    fs = impl.serve(streams, names=["s", "m", "l"])
    assert fs.widths == [1, 2, 4]
    assert fs.coordination == "coordinated"
    assert fs.workflows_completed == 3
    assert fs.over_admissions == 0 and fs.isolation_violations == 0
    # B is priced at each tenant's width, and the liveness floor covers
    # every B plus one widest slot
    assert [t["slot_width"] for t in fs.tenants] == [1, 2, 4]
    assert fs.capacity >= (4 * 1 + 4 * 2 + 4 * 4) + 4


# ------------------------------------------------------------- isolation
def test_partitioned_engine_blocks_cross_tenant_admission():
    """Tenant A can never admit into tenant B's granted slots: the pool
    has room, but A's grant is exhausted — the admit raises (strict) or
    counts (non-strict) instead of silently stealing B's slots."""
    jobs = [Job(jid=i, arrival=0.0, runtime=2.0, nodes=1, decode_len=2)
            for i in range(4)]
    pool = PartitionedEngine(EmulatedEngine(4))
    va, vb = pool.view("a"), pool.view("b")
    granted = {"a": 1, "b": 3}
    pool.bind("a", lambda: granted["a"])
    pool.bind("b", lambda: granted["b"])
    va.admit_many(jobs[:1])
    with pytest.raises(ServeInvariantError, match="another tenant's slots"):
        va.admit_many(jobs[1:3])          # a: 1 active + 2 > 1 granted
    vb.admit_many(jobs[1:3])              # b's own slots are fine
    assert pool.active_of("a") == 1 and pool.active_of("b") == 2

    lax = PartitionedEngine(EmulatedEngine(4), strict=False)
    va = lax.view("a")
    lax.bind("a", lambda: 1)
    va.admit_many(jobs[2:])               # over-grant: counted, not raised
    assert lax.isolation_violations == 1 and lax.active_of("a") == 2


def test_check_isolation_catches_post_admit_grant_shrink():
    """A grant ceiling that drops below a tenant's active slots (e.g. a
    release-check bug) is caught by the per-tick isolation sweep."""
    jobs = [Job(jid=i, arrival=0.0, runtime=2.0, nodes=1, decode_len=2)
            for i in range(2)]
    pool = PartitionedEngine(EmulatedEngine(4))
    va = pool.view("a")
    granted = {"a": 2}
    pool.bind("a", lambda: granted["a"])
    va.admit_many(jobs)
    pool.check_isolation()                # fine: 2 active <= 2 granted
    granted["a"] = 1
    with pytest.raises(ServeInvariantError, match="foreign slots"):
        pool.check_isolation()


def test_emulated_engine_admit_beyond_free_raises():
    """The engine-level guard is a raise, not an assert: it survives
    ``python -O`` (the CI leg that runs this suite optimized)."""
    eng = EmulatedEngine(2)
    jobs = [Job(jid=i, arrival=0.0, runtime=1.0, nodes=1, decode_len=1)
            for i in range(3)]
    with pytest.raises(ServeInvariantError, match="beyond free slots"):
        eng.admit_many(jobs)
    assert eng.active_count == 0 and len(eng.free) == 2


def test_fleet_rejects_duplicate_jids_and_capacity_mismatch():
    dup = [[(0.0, montage_mini(0, 0.0, 0))], [(0.0, montage_mini(0, 0.0, 1))]]
    with pytest.raises(ValueError, match="globally unique jids"):
        ServeFleet(dup, engine=EmulatedEngine(4), policies=FLEET_POLICY)
    with pytest.raises(ValueError, match="1 batching slot = 1 node"):
        ServeFleet([[(0.0, montage_mini())]], engine=EmulatedEngine(4),
                   provider=ResourceProvider(8), policies=FLEET_POLICY)


# ------------------------------------------------------------ properties
@given(st.lists(st.lists(st.tuples(st.integers(1, 9), st.integers(0, 3)),
                         min_size=1, max_size=10),
                min_size=2, max_size=4),
       st.integers(3, 8),
       st.sampled_from(["first-come", "coordinated"]))
@settings(max_examples=25, deadline=None)
def test_property_fleet_partitioning(specs, capacity, coordination):
    """For all tick sequences: ``sum(tenant.active) <= engine.capacity``
    and ``tenant.active <= tenant.granted`` per tenant — and every task
    of every tenant completes with zero over-admissions. A fleet whose
    tenants' initial nodes exceed the pool is refused at creation."""
    if len(specs) * FLEET_POLICY.initial > capacity:
        with pytest.raises(RuntimeError, match="initial resources rejected"):
            RecordingFleet(_tenant_dags(specs),
                           engine=EmulatedEngine(capacity),
                           coordination=coordination, policies=FLEET_POLICY)
        return
    fleet = RecordingFleet(_tenant_dags(specs),
                           engine=EmulatedEngine(capacity),
                           coordination=coordination, policies=FLEET_POLICY)
    fs = fleet.run()
    assert fs.tasks_completed == sum(len(s) for s in specs)
    assert fs.over_admissions == 0 and fs.isolation_violations == 0
    assert fleet.provider.total_allocated == 0
    _assert_partition_property(fleet)


@pytest.mark.parametrize("coordination", ["first-come", "coordinated"])
def test_fleet_refuses_a_tenant_beyond_the_pool(coordination):
    """Four tenants each need one initial node from a three-node pool: the
    §3.1.3 creation path refuses the fourth TRE, so the fleet is not
    built."""
    with pytest.raises(RuntimeError,
                       match="TRE 'serve-fleet-t3': initial resources "
                             "rejected"):
        ServeFleet(_tenant_dags([[(1, 0)]] * 4),
                   engine=EmulatedEngine(3), coordination=coordination,
                   policies=FLEET_POLICY)


def test_fleet_partitioning_deterministic():
    """Shim-proof companion of the partitioning property: fixed tenant
    mixes on tight and ample pools, both policies."""
    cases = [
        ([[(3, 0)] * 6, [(2, 1)] * 8], 3),          # wide + chain, starved
        ([[(4, 0), (2, 1), (2, 2)], [(1, 0)] * 10, [(5, 1)] * 4], 4),
        ([[(2, 0)] * 5] * 4, 8),                     # four equal tenants
    ]
    for specs, capacity in cases:
        for coordination in ("first-come", "coordinated"):
            fleet = RecordingFleet(_tenant_dags(specs),
                                   engine=EmulatedEngine(capacity),
                                   coordination=coordination,
                                   policies=FLEET_POLICY)
            fs = fleet.run()
            assert fs.tasks_completed == sum(len(s) for s in specs)
            assert fs.over_admissions == 0
            assert fs.isolation_violations == 0
            _assert_partition_property(fleet)


# ------------------------------------------------------------- scenario
def test_serve_fleet_system_registered_and_serves():
    assert "dawningcloud-serve-fleet" in available_systems()
    impl = get_system("dawningcloud-serve-fleet")
    with pytest.raises(NotImplementedError, match="tick-driven"):
        impl.build(None, None)
    streams = [[(0.0, montage_mini(0, 0.0, 0))],
               [(5.0, montage_mini(100, 5.0, 1))]]
    fs = impl.serve(streams, names=["t0", "t1"])
    assert fs.coordination == "coordinated"
    assert fs.workflows_completed == 2
    assert fs.over_admissions == 0 and fs.isolation_violations == 0
    # the default pool covers the liveness floor (sum of Bs + 1)
    assert fs.capacity >= 2 * impl.default_policy().initial + 1


def test_aggregate_decode_peak_hourly_buckets():
    jobs = [Job(jid=i, arrival=0.0, runtime=1.0, nodes=1, decode_len=1800)
            for i in range(4)]
    # two workflows in hour 0 (3600 ticks of work -> 1 slot-hour each),
    # two in hour 1 — the peak hour offers 2 slots of sustained decode
    streams = [[(0.0, jobs[:1]), (10.0, jobs[1:2])],
               [(3700.0, jobs[2:3]), (3800.0, jobs[3:4])]]
    assert aggregate_decode_peak(streams) == 1
    both = [[(0.0, jobs[:1]), (10.0, jobs[1:2]),
             (20.0, jobs[2:3]), (30.0, jobs[3:4])]]
    assert aggregate_decode_peak(both) == 2
