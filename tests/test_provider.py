"""Multi-tenant ResourceProvider: admission queueing, coordination
policies, quotas/reservations, and the provision-ledger invariants
(property tests via the conftest hypothesis shim)."""
from __future__ import annotations

import pytest

from tests.conftest import given, settings, st

from repro.core.policy import MgmtPolicy, PolicyEngine
from repro.core.provider import (
    CoordinatedPolicy, FirstComePolicy, ResourceProvider,
    resolve_coordination,
)
from repro.core.provision import BILL_UNIT_S, ProvisionService
from repro.core.tre import HTCRuntimeEnv, TickClock
from repro.core.types import Job, Workload
from repro.sim.engine import Sim
from repro.sim.systems import REServer, run_system


class Tenant:
    """Minimal requester: accepts up to its remaining need, logs grants."""

    def __init__(self, need: int):
        self.need = need
        self.grants: list[tuple[float, int]] = []

    def on_grant(self, offer: int, t: float) -> int:
        take = min(offer, self.need)
        self.need -= take
        if take:
            self.grants.append((t, take))
        return take


def submit(prov, name, tenant, n, t, **kw):
    return prov.submit_request(name, n, t, on_grant=tenant.on_grant, **kw)


# ----------------------------------------------------------- coordination
def test_resolve_coordination():
    assert isinstance(resolve_coordination(None), FirstComePolicy)
    assert isinstance(resolve_coordination("coordinated"), CoordinatedPolicy)
    pol = CoordinatedPolicy(starvation_s=60.0)
    assert resolve_coordination(pol) is pol
    with pytest.raises(ValueError, match="unknown coordination"):
        resolve_coordination("round-robin")


def test_immediate_grant_when_uncontended():
    prov = ResourceProvider(100)
    a = Tenant(30)
    req = submit(prov, "a", a, 30, 0.0)
    assert req.status == "granted" and req.granted == 30
    assert prov.allocated["a"] == 30 and a.grants == [(0.0, 30)]
    assert not prov.admission_queue


def test_rejected_request_parks_and_grants_on_release():
    """An indivisible (DR2-style) request that does not fit parks whole
    and lands through its callback when enough capacity frees."""
    prov = ResourceProvider(100)
    assert prov.request("a", 80, 0.0)
    b = Tenant(50)
    req = submit(prov, "b", b, 50, 1.0, min_useful=50)
    assert req.status == "queued" and b.grants == []
    prov.release("a", 20, 2.0)           # frees 20: still short of 50
    assert req.status == "queued" and b.grants == []
    prov.release("a", 40, 3.0)           # now 60 free -> deferred grant lands
    assert req.status == "granted" and b.grants == [(3.0, 50)]
    assert prov.allocated["b"] == 50
    assert not prov.admission_queue


def test_divisible_request_drains_available_capacity_eagerly():
    """A divisible (DR1-style) parked request takes whatever the pool has
    at each drain instead of idling it (work-conserving FIFO)."""
    prov = ResourceProvider(100)
    assert prov.request("a", 80, 0.0)
    b = Tenant(50)
    req = submit(prov, "b", b, 50, 1.0)
    assert req.status == "queued" and b.grants == [(1.0, 20)]
    prov.release("a", 30, 2.0)
    assert req.status == "granted" and b.grants == [(1.0, 20), (2.0, 30)]
    assert prov.allocated["b"] == 50


def test_first_come_queue_is_fifo_fair():
    """A head blocked on global capacity blocks later requests even if
    they would fit — the head soaks up every release (work-conserving
    FIFO) and completes before anything younger is served."""
    prov = ResourceProvider(100)
    prov.request("x", 90, 0.0)
    a, b = Tenant(40), Tenant(5)
    ra = submit(prov, "a", a, 10, 1.0)   # 10 free: immediate partial? no —
    assert ra.status == "granted"        # fits whole, uncontended
    ra = submit(prov, "a", a, 30, 1.5)
    rb = submit(prov, "b", b, 5, 2.0)
    prov.release("x", 20, 3.0)           # 20 free: b fits, but a is the head
    assert ra.status == "queued" and rb.status == "queued"
    assert b.grants == []
    assert a.grants[-1] == (3.0, 20)     # divisible head drains the pool
    prov.release("x", 20, 4.0)           # head completes, then b
    assert ra.status == "granted" and rb.status == "granted"
    assert a.grants[-1] == (4.0, 10) and b.grants == [(4.0, 5)]


def test_first_come_skips_tenant_capped_head():
    """A head blocked only by its own quota must not starve the fleet."""
    prov = ResourceProvider(100, quotas={"a": 10})
    a, b = Tenant(40), Tenant(30)
    ra = submit(prov, "a", a, 40, 0.0)    # over quota: can never be served
    rb = submit(prov, "b", b, 30, 1.0)
    assert rb.status == "granted" and b.grants == [(1.0, 30)]
    assert ra.status == "queued"


def test_first_come_head_blocked_by_others_reservation_is_fifo_fair():
    """A head waiting on capacity set aside by another tenant's undrawn
    reservation is shared-pool-blocked: younger requests must not keep
    overtaking it (only an own-quota block may be skipped)."""
    prov = ResourceProvider(100, reservations={"r": 30})
    a, c = Tenant(80), Tenant(40)
    ra = submit(prov, "a", a, 80, 0.0, min_useful=80)  # headroom 70: parks
    rc = submit(prov, "c", c, 40, 1.0)    # would fit, but the head blocks
    assert ra.status == "queued" and rc.status == "queued"
    assert a.grants == [] and c.grants == []
    prov.cancel(ra, 2.0)                  # head withdraws: queue re-drains
    assert ra.status == "cancelled"
    assert rc.status == "granted" and c.grants == [(2.0, 40)]


def test_release_check_regrant_cannot_oversubscribe_env():
    """An env's own release may be re-granted to its own parked request by
    the provider's drain inside provision.release(); the deficit (and the
    schedule() that follows) must see the post-release pool, or busy can
    exceed owned."""
    sim = Sim()
    prov = ResourceProvider(20, coordination="first-come")
    jobs = [Job(jid=0, arrival=0.0, runtime=200.0, nodes=10),
            Job(jid=1, arrival=70.0, runtime=5000.0, nodes=11),
            Job(jid=2, arrival=70.0, runtime=5000.0, nodes=11)]
    wl = Workload("a", "htc", jobs, trace_nodes=11, period=40000.0)
    srv = REServer(sim, wl, prov, mode="dsp",
                   policy=MgmtPolicy(2, 1.0, 60.0, 3600.0))
    sim.at(61.0, prov.request, "hog", 10, 61.0)     # platform fills
    sim.at(8000.0, prov.release, "hog", 10, 8000.0)
    checks = []
    def check():
        checks.append((srv.env.busy, srv.env.owned))
        assert srv.env.busy <= srv.env.owned, (sim.t, srv.env.busy,
                                               srv.env.owned)
    for t in (3601.0, 3660.0, 8001.0):
        sim.at(t, check)
    sim.run()
    assert len(srv.completed) == 3                  # and the TRE drains fully
    assert prov.total_allocated == 0                # everything released
    assert checks                                   # invariant was exercised


def test_stale_request_declined_not_granted():
    """A decline (take=0) never pushes nodes onto the tenant — and the
    request keeps its queue position (the live floor may merely have
    risen past this offer); only the requester's amend retires it."""
    prov = ResourceProvider(50)
    prov.request("x", 50, 0.0)
    a = Tenant(20)
    req = submit(prov, "a", a, 20, 1.0)
    assert req.status == "queued"
    a.need = 0                            # tenant's backlog drained meanwhile
    prov.release("x", 50, 2.0)
    assert req.status == "queued" and a.grants == []
    assert prov.allocated.get("a", 0) == 0
    prov.amend(req, 0, 3.0)               # the tenant's next scan retires it
    assert req.status == "cancelled" and req not in prov.admission_queue


def test_amend_cancel_drains_followers_at_amend_time():
    """Retiring a drained head via amend serves the follower at the amend
    time — not at the head's stale submission time (a lease backdated
    hours would overbill and break the alloc curve's time order)."""
    prov = ResourceProvider(50)
    prov.request("x", 50, 0.0)
    a, b = Tenant(30), Tenant(10)
    ra = submit(prov, "a", a, 30, 100.0, min_useful=30)
    rb = submit(prov, "b", b, 10, 200.0, min_useful=10)
    prov.release("x", 20, 3000.0)         # head (30 > 20) still blocks b
    assert b.grants == []
    prov.amend(ra, 0, 5000.0)             # head's need vanished
    assert ra.status == "cancelled"
    assert rb.status == "granted" and b.grants == [(5000.0, 10)]
    ts = [t for t, _ in prov._alloc_curve]
    assert ts == sorted(ts)               # curve stays time-ordered


def test_cancel_without_drain_serves_nobody():
    """Teardown detach: cancelling with drain=False must not hand the
    freed queue position to anyone (the envs are about to be destroyed)."""
    prov = ResourceProvider(50)
    prov.request("x", 50, 0.0)
    a, b = Tenant(30), Tenant(10)
    ra = submit(prov, "a", a, 30, 100.0, min_useful=30)
    rb = submit(prov, "b", b, 10, 200.0, min_useful=10)
    prov.release("x", 20, 3000.0)
    prov.cancel(ra, 5000.0, drain=False)
    assert ra.status == "cancelled"
    assert rb.status == "queued" and b.grants == []


def test_below_floor_decline_keeps_fifo_position():
    """An offer below the requester's *live* floor is declined without
    losing the parked request's FIFO position or starvation age."""
    prov = ResourceProvider(100, coordination="coordinated")
    prov.request("x", 100, 0.0)
    grants = []

    def picky(offer, t):                  # live floor rose to 30 meanwhile
        if offer < 30:
            return 0
        grants.append((t, offer))
        return offer

    req = prov.submit_request("p", 40, 1.0, on_grant=picky)
    assert req.status == "queued"
    prov.release("x", 10, 2.0)            # water-fill offers 10 -> declined
    assert req.status == "queued" and req in prov.admission_queue
    assert grants == []
    prov.release("x", 30, 3.0)            # 40 free -> whole grant accepted
    assert req.status == "granted" and grants == [(3.0, 40)]


def test_amend_updates_cancels_and_completes():
    prov = ResourceProvider(50)
    prov.request("x", 40, 0.0)
    a = Tenant(30)
    req = submit(prov, "a", a, 30, 1.0, min_useful=30)
    assert req.status == "queued"
    a.need = 10
    prov.amend(req, 10, 2.0, 10)          # smaller need now fits (10 free)
    assert req.status == "granted" and a.grants == [(2.0, 10)]
    b = Tenant(30)
    rb = submit(prov, "b", b, 30, 3.0, min_useful=30)
    prov.amend(rb, 0, 4.0)                # need vanished -> cancelled
    assert rb.status == "cancelled" and rb not in prov.admission_queue


def test_amend_priority_only_change_redrains():
    """Regression: a priority-only amend must re-drain. Before the fix,
    ``changed`` ignored priority, so an urgency bump under coordinated
    arbitration could not unblock a parked request (e.g. one declined in
    an earlier drain whose tenant's backlog refilled at the same width)
    until an unrelated release happened to trigger a drain."""
    prov = ResourceProvider(50, coordination="coordinated")
    prov.request("x", 50, 0.0)
    grants = []
    backlog = {"empty": True}

    def on_grant(offer, t):               # declines while backlog is empty
        if backlog["empty"]:
            return 0
        grants.append((t, offer))
        return offer

    req = prov.submit_request("p", 20, 1.0, on_grant=on_grant)
    assert req.status == "queued"
    prov.release("x", 30, 2.0)            # drain offers 20 -> declined
    assert req.status == "queued" and grants == []
    backlog["empty"] = False              # the tenant's queue refilled,
    # same width, higher urgency — the scan amends priority only
    prov.amend(req, 20, 3.0, min_useful=1, priority=4.0)
    assert req.priority == 4.0
    assert req.status == "granted" and grants == [(3.0, 20)]


def test_amend_same_request_without_priority_does_not_drain():
    """The no-change fast path survives the priority fix: re-amending an
    identical (n, min_useful) with priority=None must not drain (the env
    re-scans every 3 s; a drain per no-op amend would re-offer declined
    requests every scan)."""
    prov = ResourceProvider(50)
    prov.request("x", 50, 0.0)
    calls = []
    req = prov.submit_request("p", 20, 1.0,
                              on_grant=lambda o, t: calls.append(t) or 0)
    prov.release("x", 30, 2.0)            # declined once (take=0)
    n_calls = len(calls)
    prov.amend(req, 20, 3.0, min_useful=1)            # no-op amend
    assert len(calls) == n_calls          # no fresh drain, no re-offer
    prov.amend(req, 20, 4.0, min_useful=1, priority=req.priority)
    assert len(calls) == n_calls          # same priority: still a no-op


def test_cancel_with_empty_alloc_curve_falls_back_to_submit_time():
    """Regression: ``cancel(req, t=None)`` backdate-guarded against
    ``_alloc_curve[-1]`` and raised IndexError when no allocation event
    had been recorded; it must fall back to the request's own submission
    time instead."""
    prov = ResourceProvider(10)
    prov.request("x", 10, 0.0)
    a, b = Tenant(10), Tenant(4)
    ra = submit(prov, "a", a, 10, 5.0, min_useful=10)
    rb = submit(prov, "b", b, 4, 6.0, min_useful=4)
    prov.release("x", 4, 7.0)             # head (10 > 4) still blocks b
    prov._alloc_curve.clear()             # no allocation event on record
    prov.cancel(ra)                       # t=None: must not IndexError
    assert ra.status == "cancelled"
    # the follower's grant lands at the *cancelled head's* submission
    # time — the only defensible floor with an empty event log
    assert rb.status == "granted" and b.grants == [(5.0, 4)]


def test_quota_and_reservation_headroom():
    prov = ResourceProvider(100, quotas={"a": 60},
                            reservations={"r": 30})
    # a's headroom: 100 free minus r's undrawn 30, capped by quota 60
    assert prov.headroom("a") == min(100 - 30, 60)
    assert prov.headroom("r") == 100      # may draw everything incl. its own
    assert prov.request("a", 60, 0.0)
    assert not prov.request("a", 1, 1.0)  # quota exhausted
    assert prov.headroom("a") == 0
    # r's reservation survives: 40 left, none reserved away from r
    assert prov.headroom("r") == 40
    b = Tenant(20)
    # 40 free - 30 reserved for r = 10 headroom: an indivisible 20 parks
    req = submit(prov, "b", b, 20, 2.0, min_useful=20)
    assert req.status == "queued"
    assert prov.request("r", 30, 3.0)     # r draws its guarantee
    assert prov.headroom("b") == 10


def test_reservations_must_fit_capacity():
    with pytest.raises(ValueError, match="reservations exceed capacity"):
        ResourceProvider(10, reservations={"a": 8, "b": 8})


def test_coordinated_serves_most_urgent_first():
    prov = ResourceProvider(100, coordination="coordinated")
    prov.request("x", 100, 0.0)           # platform full: both park
    calm, urgent = Tenant(30), Tenant(30)
    r1 = submit(prov, "calm", calm, 30, 1.0, priority=1.5)
    r2 = submit(prov, "urgent", urgent, 30, 2.0, priority=9.0)
    prov.release("x", 30, 3.0)            # room for exactly one whole grant
    assert r2.status == "granted" and urgent.grants == [(3.0, 30)]
    assert r1.status == "queued" and calm.grants == []


def test_coordinated_water_fills_contended_backlog():
    """When no whole request fits, the remaining capacity is split in
    fair shares instead of parking behind a wide head."""
    prov = ResourceProvider(100, coordination="coordinated")
    prov.request("x", 100, 0.0)           # platform full: both park
    a, b = Tenant(40), Tenant(40)
    ra = submit(prov, "a", a, 40, 1.0, priority=2.0)
    rb = submit(prov, "b", b, 40, 1.0, priority=2.0)
    prov.release("x", 30, 2.0)            # 30 free, two 40-wide requests
    assert a.grants == [(2.0, 15)] and b.grants == [(2.0, 15)]
    assert ra.status == "queued" and rb.status == "queued"
    assert ra.nodes == 25 and rb.nodes == 25   # remainders stay parked


def test_coordinated_respects_min_useful():
    """An indivisible (DR2-style) request is never served below its
    useful floor — a partial grant would idle until reclaimed."""
    prov = ResourceProvider(100, coordination="coordinated")
    prov.request("x", 90, 0.0)
    wide = Tenant(40)
    req = submit(prov, "wide", wide, 40, 1.0, min_useful=40)
    prov.release("x", 20, 2.0)            # 30 free < 40: nothing offered
    assert wide.grants == [] and req.status == "queued"
    prov.release("x", 20, 3.0)            # 50 free >= 40
    assert wide.grants == [(3.0, 40)] and req.status == "granted"


def test_starving_elder_reserves_capacity():
    """Past the starvation age, released capacity accumulates for the
    elder instead of being water-filled to younger requests."""
    prov = ResourceProvider(
        100, coordination=CoordinatedPolicy(starvation_s=10.0))
    prov.request("x", 100, 0.0)
    wide = Tenant(60)
    young = Tenant(30)
    rw = submit(prov, "wide", wide, 60, 0.0, min_useful=60)
    prov.release("x", 40, 50.0)           # elder (age 50) reserves its 60
    ry = submit(prov, "young", young, 30, 50.0)
    assert young.grants == [] and ry.status == "queued"
    prov.release("x", 30, 60.0)           # 70 free: elder finally fits
    assert wide.grants == [(60.0, 60)] and rw.status == "granted"
    # leftovers flow to the younger request once the elder is served
    assert young.grants == [(60.0, 10)]


def test_direct_request_cannot_overtake_parked_fifo_head():
    """Satellite regression (fails pre-fix): the direct grant-or-reject
    path used to check only live headroom, so a lifecycle creation or
    DRP burst could take the very capacity a FIFO head was parked
    waiting for — overtaking a request it should queue behind. The
    direct path must be arbitration-aware: denied while a parked elder
    of another tenant has a prior claim on the shared pool."""
    prov = ResourceProvider(100, coordination="first-come")
    prov.request("x", 90, 0.0)
    a = Tenant(50)
    ra = submit(prov, "a", a, 50, 1.0, min_useful=50)   # 10 free: parks
    prov.release("x", 20, 2.0)            # 30 free < 50: head still blocked
    assert ra.status == "queued"
    # pre-fix this succeeded (20 <= 30 live headroom) and starved the head
    assert not prov.request("drp", 20, 3.0)
    assert prov.allocated.get("drp", 0) == 0
    # the head's own tenant overtakes nothing by drawing directly
    assert prov.request("a", 10, 4.0)
    prov.release("x", 60, 5.0)            # head finally fits and completes
    assert ra.status == "granted" and a.grants == [(5.0, 50)]
    # queue empty again: the direct path reopens
    assert prov.request("drp", 20, 6.0)


def test_direct_request_respects_starving_coordinated_elder():
    """Coordinated arbitration re-plans every drain, so only a *starving*
    elder (whose useful floor the arbiter is already reserving out of
    free capacity) hardens a claim against the direct path — a young
    parked request does not."""
    prov = ResourceProvider(
        100, coordination=CoordinatedPolicy(starvation_s=10.0))
    prov.request("x", 100, 0.0)
    wide = Tenant(60)
    rw = submit(prov, "wide", wide, 60, 0.0, min_useful=60)
    prov.release("x", 40, 50.0)           # elder (age 50) reserves its 60
    assert rw.status == "queued"
    # pre-fix this drained the capacity accumulating for the elder
    assert not prov.request("drp", 30, 51.0)
    prov.release("x", 20, 60.0)           # 60 free: elder served
    assert rw.status == "granted" and wide.grants == [(60.0, 60)]
    prov.release("x", 10, 61.0)           # queue empty: direct path reopens
    assert prov.request("drp", 10, 62.0)

    young = ResourceProvider(
        100, coordination=CoordinatedPolicy(starvation_s=1e9))
    young.request("x", 100, 0.0)
    w2 = Tenant(60)
    submit(young, "wide", w2, 60, 0.0, min_useful=60)
    young.release("x", 40, 50.0)          # parked, but nowhere near starving
    assert young.request("drp", 30, 51.0)


def test_direct_request_own_reservation_senior_to_parked_claim():
    """A tenant's guaranteed minimum is exactly the capacity no parked
    elder can speak for: drawing it directly stays possible while a
    foreign head is parked on the shared pool."""
    prov = ResourceProvider(100, coordination="first-come",
                            reservations={"r": 30})
    prov.request("x", 70, 0.0)
    a = Tenant(50)
    ra = submit(prov, "a", a, 50, 1.0, min_useful=50)
    assert ra.status == "queued"          # headroom 30 - debt 30 = 0
    assert prov.request("r", 30, 2.0)     # the reservation is senior
    assert not prov.request("drp", 1, 3.0)   # everyone else still queues


def test_plain_service_rejects_without_queueing():
    prov = ProvisionService(50)
    a = Tenant(40)
    prov.request("x", 20, 0.0)
    req = submit(prov, "a", a, 40, 1.0)
    assert req.status == "rejected" and a.grants == []
    ok = submit(prov, "a", a, 30, 2.0)
    assert ok.status == "granted" and a.grants == [(2.0, 30)]


# ------------------------------------------------- env integration (sim)
def test_deferred_grant_wakes_queued_env_on_release():
    """The tentpole end-to-end: TRE b's DR1 is parked by a full platform
    and lands through the admission queue the moment TRE a releases —
    not at b's next scan."""
    sim = Sim()
    prov = ResourceProvider(20, coordination="first-come")
    jobs_a = [Job(jid=0, arrival=0.0, runtime=4000.0, nodes=12)]
    wl_a = Workload("a", "htc", jobs_a, trace_nodes=12, period=20000.0)
    jobs_b = [Job(jid=0, arrival=0.0, runtime=600.0, nodes=14)]
    wl_b = Workload("b", "htc", jobs_b, trace_nodes=14, period=20000.0)
    # a: B=12, runs immediately; b: B=4, needs DR2=10 > free 4 -> parks
    REServer(sim, wl_a, prov, mode="dsp", policy=MgmtPolicy.htc(12, 100.0))
    srv_b = REServer(sim, wl_b, prov, mode="dsp",
                     policy=MgmtPolicy.htc(4, 1.0))
    sim.run()
    assert len(srv_b.completed) == 1
    job_b = srv_b.completed[0]
    # a's lifetime: [0, 4000] + destroy; b's wide job cannot start before
    # a's destroy released the platform (deferred grant, not a scan poll)
    assert job_b.start >= 4000.0
    assert prov.total_allocated == 0     # both TREs destroyed, all released


def test_env_amend_keeps_parked_request_fresh():
    clock = TickClock()
    prov = ResourceProvider(20, coordination="first-come")
    prov.request("x", 16, 0.0)
    started = []
    env = HTCRuntimeEnv("t", provision=prov, clock=clock,
                        launch=started.append, policy=MgmtPolicy.htc(2, 1.0))
    env.submit(Job(jid=0, arrival=0.0, runtime=50.0, nodes=6))
    clock.advance()
    env.scan()                            # DR1 needs 4, only 2 free: parks
    assert env._pending_req is not None
    assert env._pending_req.status == "queued"
    env.queue.clear()                     # demand vanishes
    clock.advance()
    env.scan()                            # amend with need 0 -> cancelled
    assert env._pending_req is None and not prov.admission_queue


def test_run_system_quota_scenario_caps_each_tenant():
    jobs = [Job(jid=i, arrival=0.0, runtime=7200.0, nodes=4)
            for i in range(4)]
    wl = Workload("q", "htc", jobs, trace_nodes=8, period=14400.0)
    res = run_system("dawningcloud-quota", [wl],
                     policies={"q": MgmtPolicy.htc(4, 1.0)})
    assert res.per_workload["q"].completed_total == 4
    # demand is 16 wide, but the quota pins the TRE at its cluster size —
    # and the tenant still grows all the way TO the quota (a quota-capped
    # divisible request is served partially, not starved at B)
    assert res.peak_nodes_per_hour == 8


# ------------------------------------------------------- property tests
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 40),
                          st.booleans()), min_size=1, max_size=40),
       st.integers(30, 120))
@settings(max_examples=60)
def test_capacity_never_exceeded_with_admission_queue(ops, capacity):
    """Under arbitrary submit/release interleavings (both coordination
    policies), total allocation never exceeds capacity and all ledger
    state stays consistent."""
    for coordination in ("first-come", "coordinated"):
        prov = ResourceProvider(capacity, coordination=coordination)
        tenants: dict[str, Tenant] = {}
        t = 0.0
        for who, n, is_release in ops:
            t += 60.0
            name = f"t{who}"
            if is_release and prov.allocated.get(name, 0) >= n:
                prov.release(name, n, t)
            elif not is_release:
                tenant = Tenant(n)
                tenants.setdefault(name, tenant)
                prov.submit_request(name, n, t, on_grant=tenant.on_grant)
            assert prov.total_allocated <= capacity
            assert all(v >= 0 for v in prov.allocated.values())
        # the admission queue holds only still-queued requests
        assert all(r.status == "queued" for r in prov.admission_queue)


@given(st.lists(st.tuples(st.integers(1, 50), st.booleans()), min_size=1,
                max_size=30))
@settings(max_examples=60)
def test_billed_at_least_worked(ops):
    """Per-started-hour billing can never undercut the actual node-time
    integral of the leases."""
    prov = ProvisionService()
    t = 0.0
    worked = 0.0                          # node-seconds actually held
    held_since: list[tuple[float, int]] = []
    for n, is_release in ops:
        t += 137.0
        if is_release and prov.allocated.get("a", 0) >= n:
            prov.release("a", n, t)
        elif not is_release:
            assert prov.request("a", n, t)
    worked = sum((l.t1 - l.t0) * l.nodes for l in prov.closed_leases)
    worked += sum((t - l.t0) * l.nodes
                  for blocks in prov.open_leases.values() for l in blocks)
    assert prov.node_hours("a", now=t) * BILL_UNIT_S >= worked - 1e-6


@given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 30),
                          st.booleans()), min_size=1, max_size=40))
@settings(max_examples=60)
def test_lifo_release_splitting_conserves_nodes(ops):
    """Closing newest blocks first (with partial-release splits) loses no
    nodes: open blocks match live allocation, open+closed match grants."""
    prov = ProvisionService(capacity=10_000)
    granted: dict[str, int] = {}
    released: dict[str, int] = {}
    t = 0.0
    for who, n, is_release in ops:
        t += 60.0
        name = f"t{who}"
        if is_release and prov.allocated.get(name, 0) >= n:
            prov.release(name, n, t)
            released[name] = released.get(name, 0) + n
        elif not is_release:
            assert prov.request(name, n, t)
            granted[name] = granted.get(name, 0) + n
    for name in granted:
        open_nodes = sum(l.nodes for l in prov.open_leases.get(name, []))
        closed_nodes = sum(l.nodes for l in prov.closed_leases
                           if l.tre == name)
        assert open_nodes == prov.allocated.get(name, 0)
        assert open_nodes == granted[name] - released.get(name, 0)
        assert closed_nodes == released.get(name, 0)


@given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 30),
                          st.booleans()), min_size=1, max_size=40),
       st.floats(0, 5e4))
@settings(max_examples=40)
def test_vectorized_accounting_matches_loop_reference(ops, extra):
    prov = ProvisionService(capacity=10_000)
    t = 0.0
    for who, n, is_release in ops:
        t += 311.0
        name = f"t{who}"
        if is_release and prov.allocated.get(name, 0) >= n:
            prov.release(name, n, t)
        elif not is_release:
            prov.request(name, n, t)
    now = t + extra
    assert prov.node_hours(None, now=now) == \
        prov.node_hours_loop(None, now=now)
    assert prov.node_hours("t0", now=now) == \
        prov.node_hours_loop("t0", now=now)
    assert prov.peak_nodes_per_hour(now) == \
        prov.peak_nodes_per_hour_loop(now)


@given(st.lists(st.integers(1, 30), min_size=2, max_size=8),
       st.integers(40, 80))
@settings(max_examples=60)
def test_admission_queue_drains_fifo_fair(needs, capacity):
    """First-come: deferred requests complete in submission order — a
    later request never completes before an earlier one (quotas unset).
    Needs that fit the pool are all granted; needs beyond it are granted
    in full as a prefix in submission order, the head after that prefix
    takes what the pool has left, and the rest stay queued."""
    prov = ResourceProvider(capacity, coordination="first-come")
    prov.request("hog", capacity, 0.0)
    order: list[int] = []
    reqs = []
    for i, n in enumerate(needs):
        def make(i=i, n=n):
            def on_grant(offer, t, *, _i=i, _n=n):
                take = min(offer, _n)
                order.append(_i)
                return take
            return on_grant
        reqs.append(prov.submit_request(f"t{i}", n, float(i + 1),
                                        on_grant=make()))
    assert all(r.status == "queued" for r in reqs)
    # release everything in dribs: grants must land oldest-first
    for step in range(capacity):
        if prov.allocated.get("hog", 0) > 0:
            prov.release("hog", 1, 100.0 + step)
    assert order == sorted(order)
    if sum(needs) <= capacity:
        assert all(r.status == "granted" for r in reqs)
        return
    full = [r.status == "granted" for r in reqs]
    k = full.index(False)
    assert not any(full[k:])
    assert [r.granted for r in reqs[:k]] == needs[:k]
    assert all(r.status == "queued" and r.granted < n
               for r, n in zip(reqs[k:], needs[k:]))
    # work-conserving: the pool the hog gave back is drawn in full
    assert sum(r.granted for r in reqs) == capacity


# -------------------------------------------------- drain re-entrancy
def _reentrancy_invariants(prov, reqs, accepted):
    """The ledger/queue consistency a mid-drain side effect must never
    break: no double-grant (the provider's ledger matches what each
    requester actually accepted), no orphaned ``queued`` status (a
    request is in the admission queue IFF its status says so), and the
    pool is never oversubscribed."""
    assert prov.total_allocated <= (prov.capacity or 1 << 31)
    assert all(v >= 0 for v in prov.allocated.values())
    in_queue = set(map(id, prov.admission_queue))
    for req in reqs:
        assert (req.status == "queued") == (id(req) in in_queue), \
            (req.tre, req.status)
        assert req.granted == accepted.get(req.seq, 0), \
            (req.tre, req.granted, accepted.get(req.seq, 0))
    per_tre: dict[str, int] = {}
    for seq, take in accepted.items():
        req = next(r for r in reqs if r.seq == seq)
        per_tre[req.tre] = per_tre.get(req.tre, 0) + take
    for tre, total in per_tre.items():
        assert prov.allocated.get(tre, 0) == total, (tre,)


def _run_reentrant_drain(ops, coordination, capacity=60):
    """Submit parked requests whose ``on_grant`` callbacks amend / cancel
    / priority-bump ANOTHER parked request mid-drain, then free capacity
    in dribs so every drain interleaves with the side effects."""
    prov = ResourceProvider(capacity, coordination=coordination)
    prov.request("hog", capacity, 0.0)
    reqs: list = []
    accepted: dict[int, int] = {}
    need_left: dict[int, int] = {}

    def make(slot: int, victim: int, action: str):
        def on_grant(offer: float, t: float) -> int:
            req = reqs[slot]
            take = min(offer, need_left[slot])
            need_left[slot] -= take
            if take:
                accepted[req.seq] = accepted.get(req.seq, 0) + take
            target = reqs[victim] if victim < len(reqs) else None
            if target is not None and target is not req \
                    and target.status == "queued":
                if action == "amend":
                    prov.amend(target, max(target.nodes - 1, 1), t,
                               min_useful=1)
                elif action == "cancel":
                    prov.cancel(target, t)
                elif action == "bump":
                    prov.amend(target, target.nodes, t,
                               min_useful=target.min_useful, priority=9.0)
            return take
        return on_grant

    t = 1.0
    for i, (need, victim, action) in enumerate(ops):
        need_left[i] = need
        req = prov.submit_request(f"t{i}", need, t,
                                  on_grant=make(i, victim, action))
        reqs.append(req)
        _reentrancy_invariants(prov, reqs, accepted)
        t += 1.0
    for step in range(capacity):
        if prov.allocated.get("hog", 0) == 0:
            break
        prov.release("hog", 1, 100.0 + step)
        _reentrancy_invariants(prov, reqs, accepted)
    return prov, reqs, accepted


@given(st.lists(st.tuples(st.integers(1, 25), st.integers(0, 11),
                          st.sampled_from(["none", "amend", "cancel",
                                           "bump"])),
                min_size=2, max_size=12),
       st.sampled_from(["first-come", "coordinated"]))
@settings(max_examples=50, deadline=None)
def test_property_drain_reentrant_side_effects_keep_ledger_consistent(
        ops, coordination):
    """For all interleavings of grants whose callbacks amend, cancel or
    priority-bump OTHER parked requests mid-drain: no double-grant, no
    orphaned ``queued`` status, pool never oversubscribed."""
    prov, reqs, accepted = _run_reentrant_drain(ops, coordination)
    _reentrancy_invariants(prov, reqs, accepted)


def test_drain_reentrant_cancel_and_amend_deterministic():
    """Shim-proof companion: a grant callback that cancels one victim and
    bumps/amends others mid-drain leaves the queue consistent. Under
    first-come the FIFO head is served first, so its cancel fires before
    the victim ever receives a grant; under coordinated the water-fill
    may legitimately serve the victim first, so only the consistency
    invariants are pinned there."""
    ops = [(10, 1, "cancel"),     # t0's grant cancels t1
           (20, 2, "amend"),      # t1's grant (never lands) amends t2
           (30, 0, "bump"),       # t2's grant bumps t0 (already done)
           (5, 0, "none")]
    prov, reqs, accepted = _run_reentrant_drain(ops, "first-come")
    _reentrancy_invariants(prov, reqs, accepted)
    assert reqs[1].status == "cancelled" and reqs[1].granted == 0
    assert reqs[1] not in prov.admission_queue
    assert reqs[0].status == "granted" and reqs[0].granted == 10
    assert reqs[3].status == "granted"
    assert prov.allocated.get("t2", 0) == accepted.get(reqs[2].seq, 0)

    prov, reqs, accepted = _run_reentrant_drain(ops, "coordinated")
    _reentrancy_invariants(prov, reqs, accepted)
    assert reqs[1].status in ("cancelled", "granted", "queued")
    assert reqs[1].granted == accepted.get(reqs[1].seq, 0)


# ----------------------------------------------------- PolicyEngine DR split
def test_scan_request_dr1_floor_dr2_indivisible():
    eng = PolicyEngine(MgmtPolicy.htc(10, 1.2))
    # DR1 backlog: useful floor = what the narrowest queued job would
    # need even with everything owned free
    assert eng.scan_request([30, 30], 10) == (50, 20)
    assert eng.scan_request([], 10) == (0, 0)
    eng14 = PolicyEngine(MgmtPolicy.htc(4, 1.0))
    # a single wide job via DR1 is as indivisible as via DR2
    assert eng14.scan_request([14], 4) == (10, 10)
    # a narrow job in the mix lowers the floor to its own deficit
    assert eng14.scan_request([6, 14], 4) == (16, 2)
    # jobs already narrower than owned: any grant relieves contention
    assert eng14.scan_request([2, 3, 4], 4) == (5, 1)
    # DR2 (ratio below R, one oversized job) -> all-or-nothing
    eng2 = PolicyEngine(MgmtPolicy.htc(40, 2.0))
    assert eng2.scan_request([64], 40) == (24, 24)


def test_urgency_is_obtaining_ratio():
    eng = PolicyEngine(MgmtPolicy.htc(10, 1.2))
    assert eng.urgency([30, 30], 20) == 3.0
    assert eng.urgency([], 20) == 0.0
    assert eng.urgency([5], 0) == 5.0     # owned floor of 1
