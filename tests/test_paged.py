"""Paged-KV engine contracts: allocator conservation, kernel parity,
physical-engine bit-parity, and the paged fleet's field-for-field
equivalence with the slot-arithmetic fleet.

The layering mirrors the serve stack: ``PagedKVAllocator`` (pure-python
ledger) -> ``paged_decode_attention`` (pallas, interpret mode on CPU) ->
``Engine(page_size=...)`` (real jax serving) -> ``ServeFleet(page_size=)``
(emulated fleet with the physical ledger underneath). Each layer's
contract is pinned against the layer below's un-paged twin: paging is a
memory layout, never a scheduling or numerics input.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.policy import MgmtPolicy
from repro.core.provision import ProvisionService
from repro.serve.driver import (
    EmulatedEngine, JaxEngineAdapter, ServeDriver, ServeInvariantError,
    decode_budget,
)
from repro.serve.paged import PagedKVAllocator, pages_for
from repro.core.types import Job
from repro.sim.traces import SERVE_PROFILES, workload_family
from tests.conftest import given, settings, st

jax = pytest.importorskip("jax")
jnp = jax.numpy


# ===================================================================
# allocator: deterministic companion (runs under python -O and without
# hypothesis — the guarded raises are ServeInvariantError, not assert)
# ===================================================================
def test_pages_for_rounds_up_and_floors_at_one():
    assert pages_for(0, 8) == 1          # a slot always owns >= 1 page
    assert pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1
    assert pages_for(9, 8) == 2
    assert pages_for(48, 8) == 6
    with pytest.raises(ValueError):
        pages_for(4, 0)


def test_allocator_lifecycle_and_guarded_raises():
    g = PagedKVAllocator(9, page_size=8, reserve_null=True)
    assert g.capacity_pages == 8 and g.free_pages == 8 and g.used_pages == 0

    a = g.alloc("a", 3)
    b = g.alloc("b", 2)
    assert len(a) == 3 and len(b) == 2
    assert g.used_pages == 5 and sorted(g.owners()) == ["a", "b"]
    assert 0 not in a + b                      # null page never handed out
    g.check_conservation()

    with pytest.raises(ServeInvariantError):   # double-own
        g.alloc("a", 1)
    with pytest.raises(ServeInvariantError):   # exhaustion (3 free)
        g.alloc("c", 4)
    with pytest.raises(ServeInvariantError):   # nonsense size
        g.alloc("c", 0)
    with pytest.raises(ServeInvariantError):   # unknown owner
        g.free("zzz")

    freed = g.free("a")
    assert sorted(freed) == sorted(a)
    assert g.used_pages == 2
    # LIFO: the freshly freed pages are first out again (cache-warm)
    c = g.alloc("c", 3)
    assert sorted(c) == sorted(freed)
    g.preempt("c")                             # preempt is free, physically
    g.free("b")
    assert g.used_pages == 0 and g.peak_used == 5
    g.check_conservation()


def test_allocator_tenant_quota_tracks_live_supplier():
    g = PagedKVAllocator(13, page_size=8, pages_per_unit=2)
    granted = {"m": 2}                               # units, live
    g.set_quota("m", lambda: granted["m"] * g.pages_per_unit)
    g.alloc("j1", 3, tenant="m")
    with pytest.raises(ServeInvariantError):         # 3 + 2 > 2*2
        g.alloc("j2", 2, tenant="m")
    granted["m"] = 4                                 # a grant arrived
    g.alloc("j2", 2, tenant="m")
    g.check_conservation()
    granted["m"] = 1                                 # shrink below usage:
    with pytest.raises(ServeInvariantError):         # the sweep catches it
        g.check_conservation()
    g.free("j1")
    g.free("j2")
    g.check_conservation()


@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 5),
                          st.integers(1, 4)),
                min_size=1, max_size=60))
@settings(max_examples=60, deadline=None)
def test_allocator_conservation_property(ops):
    """Random admit/finish/preempt interleavings over two quota'd tenants:
    no page is ever double-mapped, freed pages return to the pool, and no
    tenant's usage exceeds its quota — swept after every op."""
    g = PagedKVAllocator(17, page_size=4, pages_per_unit=2,
                         reserve_null=True)
    quotas = {"t0": 3, "t1": 2}                       # units
    for t, q in quotas.items():
        g.set_quota(t, lambda t=t: quotas[t] * g.pages_per_unit)
    live: dict[int, str] = {}
    for i, (kind, key, n) in enumerate(ops):
        tenant = f"t{key % 2}"
        if kind == 0 and key not in live:             # admit
            try:
                g.alloc(key, n, tenant=tenant)
                live[key] = tenant
            except ServeInvariantError:
                pass                                  # quota/pool refusal
        elif kind == 1 and live:                      # finish
            victim = sorted(live)[key % len(live)]
            g.free(victim)
            del live[victim]
        elif kind == 2 and live:                      # preempt
            victim = sorted(live)[key % len(live)]
            g.preempt(victim)
            del live[victim]
        g.check_conservation()
        for t in quotas:
            assert g.tenant_pages(t) <= quotas[t] * g.pages_per_unit
    for owner in list(live):
        g.free(owner)
    assert g.used_pages == 0
    g.check_conservation()


# ===================================================================
# kernel: paged gather-through-page-table vs the contiguous kernels
# ===================================================================
def _paged_views(cache, page_size, *, shuffle_seed=None):
    """Cut a contiguous (B,S,KVH,hd) cache into a (NP,ps,KVH,hd) pool +
    page table (page 0 reserved as a poisoned null page)."""
    B, S, KVH, hd = cache.shape
    n_pt = S // page_size
    perm = np.arange(B * n_pt)
    if shuffle_seed is not None:        # physical placement is arbitrary
        np.random.default_rng(shuffle_seed).shuffle(perm)
    pool = np.full((1 + B * n_pt, page_size, KVH, hd), np.nan,
                   dtype=cache.dtype)
    table = np.zeros((B, n_pt), np.int32)
    for b in range(B):
        for j in range(n_pt):
            p = 1 + int(perm[b * n_pt + j])
            pool[p] = cache[b, j * page_size:(j + 1) * page_size]
            table[b, j] = p
    return jnp.asarray(pool), jnp.asarray(table)


@pytest.mark.parametrize("B,H,KVH,hd,S,ps",
                         [(4, 4, 2, 16, 64, 16),
                          (3, 8, 8, 32, 96, 32),
                          (2, 4, 1, 64, 64, 16)])
def test_paged_decode_bitwise_matches_contiguous_kernel(B, H, KVH, hd, S,
                                                        ps):
    """With ``page_size == block_s`` the paged kernel walks the same
    blocks in the same order as the contiguous kernel — outputs must be
    bit-identical, regardless of where pages physically live."""
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.kernels.ref import decode_attention_ref

    r = np.random.default_rng(7)
    q = jnp.asarray(r.standard_normal((B, H, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((B, S, KVH, hd)), jnp.float32)
    v = jnp.asarray(r.standard_normal((B, S, KVH, hd)), jnp.float32)
    lengths = jnp.asarray(r.integers(1, S + 1, (B,)), jnp.int32)

    contiguous = decode_attention(q, k, v, lengths, block_s=ps,
                                  interpret=True)
    k_pool, table = _paged_views(np.asarray(k), ps, shuffle_seed=3)
    v_pool, _ = _paged_views(np.asarray(v), ps, shuffle_seed=3)
    paged = paged_decode_attention(q, k_pool, v_pool, table, lengths,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(paged),
                                  np.asarray(contiguous))
    ref = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_zero_length_rows_are_exact_zero_in_all_decode_kernels():
    """Satellite contract: a ``length == 0`` row (empty slot sharing the
    decode batch) yields EXACTLY zero from the ref oracle, the contiguous
    kernel and the paged kernel — never a softmax over garbage — while
    live rows in the same batch stay unperturbed."""
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.kernels.ref import decode_attention_ref

    B, H, KVH, hd, S, ps = 4, 4, 2, 16, 64, 16
    r = np.random.default_rng(11)
    q = jnp.asarray(r.standard_normal((B, H, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((B, S, KVH, hd)), jnp.float32)
    v = jnp.asarray(r.standard_normal((B, S, KVH, hd)), jnp.float32)
    lengths = jnp.asarray([0, 17, 0, S], jnp.int32)

    ref = np.asarray(decode_attention_ref(q, k, v, lengths))
    contiguous = np.asarray(decode_attention(q, k, v, lengths, block_s=ps,
                                             interpret=True))
    k_pool, table = _paged_views(np.asarray(k), ps)
    v_pool, _ = _paged_views(np.asarray(v), ps)
    paged = np.asarray(paged_decode_attention(q, k_pool, v_pool, table,
                                              lengths, interpret=True))
    for name, out in [("ref", ref), ("contiguous", contiguous),
                      ("paged", paged)]:
        assert np.all(out[0] == 0.0), name
        assert np.all(out[2] == 0.0), name
        assert np.all(np.isfinite(out)), name
    np.testing.assert_array_equal(paged[1], contiguous[1])
    np.testing.assert_array_equal(paged[3], contiguous[3])
    np.testing.assert_allclose(paged[[1, 3]], ref[[1, 3]],
                               rtol=2e-5, atol=2e-5)


# ===================================================================
# physical engine: paged vs contiguous serving, page hygiene on reject
# ===================================================================
@pytest.fixture(scope="module")
def musicgen_lm():
    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig
    from repro.models.lm import LM

    cfg = get_smoke_config("musicgen-large")
    lm = LM(cfg)
    rt = lm.runtime(ParallelConfig(attn_q_chunk=16, attn_kv_chunk=16))
    params = lm.init(jax.random.key(0))[0]
    return lm, params, rt


def _requests(lm, n, seed, *, plen=5, budget=6):
    from repro.serve.engine import Request

    r = np.random.default_rng(seed)
    ncb = lm.cfg.n_codebooks
    return [Request(rid=i,
                    tokens=r.integers(1, lm.cfg.vocab_size,
                                      (plen, ncb)).astype(np.int32),
                    max_new_tokens=budget) for i in range(n)]


def test_paged_engine_bitwise_matches_contiguous_engine(musicgen_lm):
    """The tentpole pin: a paged ``Engine`` (page-table splice + paged
    decode reads) must reproduce the contiguous engine's greedy tokens
    BIT-FOR-BIT and its finish order exactly, across multiple admission
    waves that force page reuse."""
    from repro.serve.engine import Engine

    lm, params, rt = musicgen_lm
    contiguous = Engine(lm, params, rt, max_batch=4, max_len=48)
    paged = Engine(lm, params, rt, max_batch=4, max_len=48, page_size=8)
    assert paged.pager.capacity_pages == 4 * 6

    def serve(eng, seed):
        reqs = _requests(lm, 9, seed)      # > 2 full batches: slot reuse
        order, pending = [], list(reqs)
        while pending or eng.active:
            admitted = eng.admit_many(pending[:len(eng.free)])
            pending = pending[len(admitted):]
            order.extend(r.rid for r in eng.step())
        return reqs, order

    ref_reqs, ref_order = serve(contiguous, 23)
    pg_reqs, pg_order = serve(paged, 23)
    assert pg_order == ref_order
    for a, b in zip(pg_reqs, ref_reqs):
        np.testing.assert_array_equal(np.asarray(a.out_tokens),
                                      np.asarray(b.out_tokens))
    # every page returned once the batch drained; ledger still consistent
    assert paged.pager.used_pages == 0
    paged.pager.check_conservation()


def _scan_output_lm(cfg):
    """An ``LM`` whose decode step is an ``xs``/``ys`` layer scan: each
    layer's caches go in as a scan input, sliced out of the stack, and
    come back as a scan output, stacked anew. The same block math as
    ``LM.decode`` (``block_apply`` over a one-layer stack); only where
    the caches live differs, so its logits and caches are the reference
    for the carried, in-place step."""
    from repro.models.blocks import block_apply
    from repro.models.lm import LM

    class ScanOutputLM(LM):
        def decode_backbone(self, params, rt, x, lengths, caches,
                            page_table=None):
            cfg, positions = self.cfg, lengths[:, None]

            def body(x, xs):
                layer_params, layer_caches = xs
                new = {}
                for i in range(cfg.pattern_period):
                    one = jax.tree.map(lambda c: c[None],
                                       layer_caches[f"pos{i}"])
                    x, c, _ = block_apply(
                        layer_params[f"pos{i}"], cfg, rt, x, positions, i,
                        cache=one, lengths=lengths, decode=True,
                        page_table=page_table, layer=0)
                    new[f"pos{i}"] = jax.tree.map(lambda c: c[0], c)
                return x, new

            return jax.lax.scan(body, x, (params["blocks"], caches))
    return ScanOutputLM(cfg)


def _recorded(engine):
    """Keep every decode step's tokens, logits and caches, copied to the
    host before the next step donates them; each step's tokens are the
    argmax of its logits."""
    decode, engine.record = engine._decode, []

    def probed(*args):
        out = decode(*args)
        toks, logits, _ = rec = jax.tree.map(np.asarray, out)
        np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))
        engine.record.append(rec)
        return out
    engine._decode = probed
    return engine


@pytest.mark.parametrize("arch,page_size,kv_shard", [
    ("granite-3-8b", 4, "auto"), ("granite-3-8b", None, "auto"),
    ("granite-3-8b", None, "seq"), ("jamba-1.5-large-398b", 4, "auto"),
    ("mamba2-1.3b", None, "auto"), ("musicgen-large", 4, "auto")])
def test_in_place_decode_bitwise_matches_scan_output_reference(
        arch, page_size, kv_shard):
    """The decode step that writes each layer's cache update in place in
    the carried stack returns the same logits and the same cache leaves,
    bit for bit, as the step that stacks every layer's caches as a scan
    output; over decode steps that cross page boundaries and a second
    admission into a freed slot. ``kv_shard="seq"`` takes the
    sequence-sharded attention, here on one device. Attention-only
    models run one step ahead, so the admission drops one step and
    dispatches it again; models with SSM state step synchronously."""
    from repro.configs import get_smoke_config
    from repro.configs.base import ParallelConfig
    from repro.models.lm import LM
    from repro.serve.engine import Engine, Request

    cfg = get_smoke_config(arch)
    rt = LM(cfg).runtime(ParallelConfig(attn_q_chunk=16, attn_kv_chunk=16,
                                        decode_kv_shard=kv_shard))
    params = LM(cfg).init(jax.random.key(3))[0]
    ncb = cfg.n_codebooks

    def serve(lm):
        eng = _recorded(Engine(lm, params, rt, max_batch=4, max_len=24,
                               page_size=page_size))
        r = np.random.default_rng(9)

        def req(rid, plen, budget):
            shape = (plen,) if ncb <= 1 else (plen, ncb)
            return Request(rid=rid, tokens=r.integers(
                1, cfg.vocab_size, shape).astype(np.int32),
                max_new_tokens=budget)
        eng.admit_many([req(0, 5, 3), req(1, 7, 8), req(2, 5, 6)])
        for _ in range(3):
            eng.step()
        eng.admit_many([req(3, 7, 5), req(4, 5, 4)])
        while eng.active:
            eng.step()
        assert eng.counters["decode_redone"] == (0 if cfg.ssm else 1)
        return eng.record

    got, want = serve(LM(cfg)), serve(_scan_output_lm(cfg))
    assert len(got) == len(want) == (7 if cfg.ssm else 8)
    for step, (g, w) in enumerate(zip(got, want)):
        assert jax.tree.structure(g) == jax.tree.structure(w)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype), step
            np.testing.assert_array_equal(a, b, err_msg=f"step {step}")


@pytest.mark.parametrize("arch,page_size", [
    ("granite-3-8b", None), ("granite-3-8b", 4),
    ("jamba-1.5-large-398b", 4)])
def test_admission_keeps_one_version_of_each_cache_leaf(arch, page_size,
                                                       monkeypatch):
    """The splices that write a prefill into the cache are not donated,
    so each makes a new version of the leaf it updates. The engine drops
    the version it replaced before the next update: no more cache-shaped
    arrays are alive at any update than before the admission, on the
    contiguous cache, the page pool, and beside SSM state."""
    from repro.configs import get_smoke_config
    from repro.models.lm import LM
    from repro.serve.engine import Engine, Request

    cfg = get_smoke_config(arch)
    lm = LM(cfg)
    params = lm.init(jax.random.key(5))[0]
    # 3 slots of 40: no prefill output or other test's cache has a cache
    # leaf's shape
    eng = Engine(lm, params, lm.runtime(), max_batch=3, max_len=40,
                 page_size=page_size)
    r = np.random.default_rng(2)

    def admit():
        eng.admit_many([Request(rid=i, max_new_tokens=2, tokens=r.integers(
            1, cfg.vocab_size, (9,)).astype(np.int32)) for i in range(2)])
    admit()                                 # compile prefill and decode
    while eng.active:
        eng.step()
    leaf = {(x.shape, x.dtype) for x in jax.tree.leaves(eng.caches)}

    def alive():
        return sum((x.shape, x.dtype) in leaf for x in jax.live_arrays())
    before, seen, update = alive(), [], jax.lax.dynamic_update_slice

    def counted(*args):
        seen.append(alive())
        return update(*args)
    monkeypatch.setattr(jax.lax, "dynamic_update_slice", counted)
    admit()
    assert len(seen) > 1 and max(seen) == before, (before, seen)


def test_oversize_rejects_leak_neither_slots_nor_pages(musicgen_lm):
    """Satellite regression at engine scale (fails pre-fix): a mid-batch
    oversize request must be rejected individually — later requests still
    admit, no slot is consumed, and on the paged engine no page is ever
    allocated for it."""
    from repro.serve.engine import Engine, Request

    lm, params, rt = musicgen_lm
    eng = Engine(lm, params, rt, max_batch=4, max_len=48, page_size=8)
    r = np.random.default_rng(5)
    ncb = lm.cfg.n_codebooks

    def req(rid, plen, budget):
        toks = r.integers(1, lm.cfg.vocab_size,
                          (plen, ncb)).astype(np.int32)
        return Request(rid=rid, tokens=toks, max_new_tokens=budget)

    batch = [req(0, 5, 4), req(1, 40, 40), req(2, 6, 3), req(3, 47, 2)]
    admitted = eng.admit_many(batch)
    assert [q.rid for q in admitted] == [0, 2]
    assert batch[1].rejected and batch[1].done
    assert batch[3].rejected and batch[3].done
    assert len(eng.free) == 2                      # only 2 slots consumed
    assert eng.pager.used_pages == pages_for(5 + 4, 8) + pages_for(6 + 3, 8)
    while eng.active:
        eng.step()
    assert eng.pager.used_pages == 0
    eng.pager.check_conservation()


def test_decode_budget_clamp_parity_near_full_cache(musicgen_lm):
    """Satellite regression (fails pre-fix): jobs whose prompts land AT
    or BEYOND the cache depth used to drive ``decode_budget`` to <= 0 —
    the jax adapter then built an inadmissible request and raised, while
    the emulator happily served them. Post-fix both backends clamp to the
    same >= 1 budget and finish on identical ticks."""
    from repro.serve.engine import Engine

    lm, params, rt = musicgen_lm
    cap = 48

    def jobs():
        return [Job(jid=0, arrival=0.0, runtime=1.0, nodes=1, wid=0,
                    prompt_len=cap - 1, decode_len=9, name="at-edge"),
                Job(jid=1, arrival=0.0, runtime=1.0, nodes=1, wid=0,
                    prompt_len=cap + 20, decode_len=5, name="beyond"),
                Job(jid=2, arrival=0.0, runtime=1.0, nodes=1, wid=0,
                    prompt_len=cap, decode_len=0, name="zero-decode")]

    assert decode_budget(9, cap - 1, cap) == 1     # clamp floor binds
    assert decode_budget(5, cap + 20, cap) == 1
    assert decode_budget(0, 7, cap) == 2           # room=41: floor min(2,..)

    def run(engine):
        js = jobs()
        drv = ServeDriver([(0.0, js)], provider=ProvisionService(),
                          engine=engine, fixed_nodes=4)
        stats = drv.run()
        assert stats.tasks_completed == 3 and stats.over_admissions == 0
        return {j.name: (j.start, j.finish) for j in js}

    eng = Engine(lm, params, rt, max_batch=4, max_len=cap, page_size=8)
    jax_times = run(JaxEngineAdapter(eng, seed=0))
    emu_times = run(EmulatedEngine(4, max_len=cap))
    assert jax_times == emu_times
    # a clamped budget of 1 is one decode step = one slot-tick, both sides
    assert emu_times["at-edge"][1] - emu_times["at-edge"][0] == 1.0
    assert eng.pager.used_pages == 0
    eng.pager.check_conservation()


# ===================================================================
# fleet: the paged ledger under the weighted pool
# ===================================================================
def _fleet_streams(mix, *, n_tenants, workflows=4, seed=0):
    from repro.serve.fleet import rekey_disjoint

    streams, widths = [], []
    for t in range(n_tenants):
        fam = workload_family(0, workflows, seed=seed * 1009 + t,
                              jobs_scale=0.04)
        profile = SERVE_PROFILES[mix[t % len(mix)]]
        streams.append(profile.stream(fam, period=1800.0, seed=seed + t))
        widths.append(profile.width)
    return rekey_disjoint(streams), widths


def _depth(streams, ps=8):
    need = max(max(j.prompt_len, 1) + j.decode_len + 1
               for s in streams for _, jobs in s for j in jobs)
    return -(-need // ps) * ps


def _run_fleet(streams, widths, *, page_size=None):
    from repro.serve.fleet import ServeFleet

    policies = [MgmtPolicy(initial=2 * w, ratio=2.0, scan_interval=3.0,
                           release_interval=3600.0) for w in widths]
    cap = sum(2 * w for w in widths) + 4
    eng = (EmulatedEngine(cap, max_len=_depth(streams))
           if page_size else EmulatedEngine(cap))
    fleet = ServeFleet(streams, engine=eng, coordination="coordinated",
                       policies=policies, widths=widths, event_skip=True,
                       name="paged-fleet-test", page_size=page_size)
    fs = fleet.run()
    return fs, fleet


def test_width1_paged_fleet_matches_unpaged_field_for_field():
    """Acceptance pin: the all-width-1 paged fleet reproduces the PR 7
    fleet's ``FleetStats`` field for field — the physical ledger rides
    underneath without perturbing a single admit or finish."""
    ref_fs, _ = _run_fleet(*_fleet_streams([1], n_tenants=3))
    pg_fs, fleet = _run_fleet(*_fleet_streams([1], n_tenants=3),
                              page_size=8)
    assert pg_fs.as_dict() == ref_fs.as_dict()
    assert fleet.pool.pager.used_pages == 0
    assert fleet.pool.pager.peak_used > 0
    fleet.pool.pager.check_conservation()


def test_hetero_paged_fleet_isolates_in_pages():
    """Width mix 1/2/4 under the physical ledger: every admit maps real
    pages under its tenant's quota, the sweep stays clean for the whole
    run, and the stats still match the unpaged heterogeneous fleet."""
    ref_fs, _ = _run_fleet(*_fleet_streams([1, 2, 4], n_tenants=3))
    pg_fs, fleet = _run_fleet(*_fleet_streams([1, 2, 4], n_tenants=3),
                              page_size=8)
    assert pg_fs.as_dict() == ref_fs.as_dict()
    assert pg_fs.over_admissions == 0
    assert pg_fs.isolation_violations == 0
    pager = fleet.pool.pager
    assert pager.used_pages == 0 and pager.peak_used > 0
    pager.check_conservation()
