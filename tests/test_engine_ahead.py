"""The serve engine's decode step run one ahead of the host, on smoke
configurations on the CPU: it serves, token for token, what the
synchronous step serves, across admissions between steps, mixed budgets
and pages reused at once; its counters count the steps it ran ahead and
those it dropped; and engines with SSM state never run ahead."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.models.lm import LM
from repro.serve.engine import Engine, Request

MAX_BATCH, MAX_LEN = 4, 32
#: an oversize request: rejected, so its admission admits nothing
OVERSIZE = (30, 8)

#: each a list of ("admit", [(prompt length, budget), ...]) and
#: ("step", n) calls, then the engine is drained
SCHEDULES = {
    # budgets 1 and 2 finish at the first step; the next admission takes
    # their slots, and on the paged engine the pages they freed there
    "admit_into_pages_freed_at_the_last_step": [
        ("admit", [(5, 1), (7, 2), (6, 5)]), ("step", 1),
        ("admit", [(9, 3), (5, 2)])],
    # the first row finishes at the first step of the row admitted after
    # one step; an admission that admits nothing keeps the step run ahead
    "a_row_finishes_at_the_step_another_joins": [
        ("admit", [(5, 3), (6, 8)]), ("step", 1), ("admit", [(7, 2)]),
        ("step", 1), ("admit", [OVERSIZE]), ("step", 1)],
    # one or two rows at a time, each admission into a slot freed by the
    # step before it
    "admissions_between_single_steps": [
        ("admit", [(5, 2)]), ("step", 1), ("admit", [(6, 2)]), ("step", 1),
        ("admit", [(5, 1), (7, 4)]), ("step", 2), ("admit", [(8, 2)]),
        ("step", 1), ("admit", [(6, 6), (5, 3)])],
}
ENGINES = [("granite-3-8b", None), ("granite-3-8b", 4),
           ("mamba2-1.3b", None), ("jamba-1.5-large-398b", 4)]


@pytest.fixture(scope="module", params=ENGINES,
                ids=[f"{a}-{'paged' if p else 'contiguous'}"
                     for a, p in ENGINES])
def engines(request):
    """Two engines of one model: one as built, and one whose every step is
    synchronous. Each test serves the same calls on both, so the two see
    the same history: what an inactive row feeds an expert layer is what
    its slot served last, and it can take a place in the experts'
    capacity from an active row."""
    arch, page_size = request.param
    lm = LM(get_smoke_config(arch))
    params = lm.init(jax.random.key(11))[0]
    pair = []
    for _ in range(2):
        eng = Engine(lm, params, lm.runtime(), max_batch=MAX_BATCH,
                     max_len=MAX_LEN, page_size=page_size)
        decode, eng.dispatches = eng._decode, 0

        def counted(*args, eng=eng, decode=decode):
            eng.dispatches += 1
            return decode(*args)
        eng._decode = counted
        pair.append(eng)
    pair[1]._run_ahead = False
    return pair


def serve(eng, schedule, seed: int):
    """Run ``schedule`` on ``eng`` and drain it. Returns each request's
    tokens by rid, and the counts of steps run ahead and dropped that the
    calls' effects imply: a step call with rows left active runs the next
    step ahead, and an admission that admits a row drops it."""
    rng = np.random.default_rng(seed)
    vocab = eng.lm.cfg.vocab_size
    served, rid, in_flight = {}, 0, False
    ahead = redone = 0

    def step():
        nonlocal in_flight, ahead
        for req in eng.step():
            served[req.rid] = [int(t) for t in req.out_tokens]
        in_flight = eng._run_ahead and bool(eng.active)
        ahead += in_flight

    for call, arg in schedule:
        if call == "step":
            for _ in range(arg):
                step()
            continue
        reqs = []
        for plen, budget in arg:
            reqs.append(Request(rid=rid, max_new_tokens=budget,
                                tokens=rng.integers(1, vocab, plen,
                                                    dtype=np.int32)))
            rid += 1
        if eng.admit_many(reqs) and in_flight:
            in_flight, redone = False, redone + 1
    while eng.active:
        step()
    return served, ahead, redone


@pytest.mark.parametrize("name", list(SCHEDULES))
def test_run_ahead_serves_what_the_synchronous_step_serves(engines, name):
    """Each schedule on the engine as built and on its synchronous twin.
    The tokens are the same; the counters count each step run ahead and
    each one an admission dropped, and the engine dispatched one decode
    program per step and one per dropped step. Engines with SSM state run
    none ahead."""
    engine, sync = engines
    schedule = SCHEDULES[name]
    attends = not engine.lm.cfg.ssm
    assert engine._run_ahead == attends
    before = dict(engine.counters)
    steps0, dispatches0 = engine.steps, engine.dispatches
    got, ahead, redone = serve(engine, schedule, seed=len(name))
    counted = {k: engine.counters[k] - before[k]
               for k in ("decode_ahead", "decode_redone")}
    assert counted == {"decode_ahead": ahead, "decode_redone": redone}
    if attends:
        assert ahead > redone > 0
    else:
        assert ahead == redone == 0
    assert (engine.dispatches - dispatches0
            == engine.steps - steps0 + redone)

    assert serve(sync, schedule, seed=len(name)) == (got, 0, 0)
    assert sync.counters["decode_ahead"] == sync.counters["decode_redone"] == 0
    assert len(got) == sum(shape != OVERSIZE for call, arg in schedule
                           if call == "admit" for shape in arg)
    if engine.pager is not None:
        assert engine.pager.used_pages == 0
        engine.pager.check_conservation()


def test_the_first_admission_reuses_pages_freed_at_the_step_before():
    """The paged case the first schedule is for: the rows that finish at
    the first step free their pages there, and the admission right after
    it hands those pages to the new rows while the step run ahead is in
    flight."""
    cfg = get_smoke_config("granite-3-8b")
    lm = LM(cfg)
    eng = Engine(lm, lm.init(jax.random.key(11))[0], lm.runtime(),
                 max_batch=MAX_BATCH, max_len=MAX_LEN, page_size=4)
    rng = np.random.default_rng(0)
    (_, first), _, (_, second) = SCHEDULES[
        "admit_into_pages_freed_at_the_last_step"]

    def reqs(shapes):
        return [Request(rid=i, max_new_tokens=b, tokens=rng.integers(
            1, cfg.vocab_size, p, dtype=np.int32))
            for i, (p, b) in enumerate(shapes)]
    eng.admit_many(reqs(first))
    held = {id(r): eng.pager.pages_of(s) for s, r in eng.active.items()}
    finished = eng.step()
    assert [r.max_new_tokens for r in finished] == [1, 2]
    assert eng._ahead is not None
    freed = {p for r in finished for p in held[id(r)]}
    admitted = {id(r) for r in eng.admit_many(reqs(second))}
    new = {p for s, r in eng.active.items() if id(r) in admitted
           for p in eng.pager.pages_of(s)}
    assert eng._ahead is None and eng.counters["decode_redone"] == 1
    assert freed & new


@pytest.mark.parametrize("page_size", [None, 4])
def test_steps_from_the_host_and_run_ahead_share_one_program(page_size):
    """A step dispatched on the host's tokens and one dispatched on the
    previous step's, still on the device, run one compiled program."""
    lm = LM(get_smoke_config("granite-3-8b"))
    eng = Engine(lm, lm.init(jax.random.key(2))[0], lm.runtime(),
                 max_batch=MAX_BATCH, max_len=MAX_LEN, page_size=page_size)
    counter = obs.compile_counter()
    n0 = len(counter.events)
    served, ahead, redone = serve(
        eng, SCHEDULES["admissions_between_single_steps"], seed=3)
    assert ahead > redone > 0
    assert [e.fun_name for e in counter.events[n0:]].count(
        "jit(engine_decode)") == 1
