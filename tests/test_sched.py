"""slt-check (PR 8): the cooperative model-checking scheduler itself.

Covers: schedule determinism (same trace id => bit-identical
interleaving), counterexample replay, one seeded-violation toy per
invariant (proving each invariant actually fires and hands back a
replayable schedule id), explore() determinism in both modes, and a
real-tree-clean gate over a fast subset of the registered scenarios.

The toys deliberately reintroduce the concurrency bugs the runtime is
checked against: check-then-act claim races, if-guarded (instead of
while-guarded) condition waits, AB/BA lock ordering, dropped waiters.
Racy plain reads/writes are marked with ``ctx.step(tag)`` on BOTH
sides so the sleep-set pruner keeps both orders (plain dict access is
invisible to the dependence relation).
"""

import pytest

from split_learning_tpu.analysis import engine
from split_learning_tpu.analysis.invariants import (
    GENERIC, INVARIANTS, check_run)
from split_learning_tpu.analysis.sched import (
    decode_choices, encode_choices, explore, run_schedule)


# ---------------------------------------------------------------------- #
# toy scenarios
# ---------------------------------------------------------------------- #

def _counter_race(ctx):
    """Two incrementers over a lock-protected counter — correct code,
    used for determinism tests (the lock gives real interleavings)."""
    lock = ctx.lock("counter")
    box = {"n": 0}

    def bump(label):
        for _ in range(2):
            with lock:
                box["n"] += 1
        ctx.note("done", who=label)

    a = ctx.spawn(bump, "a")
    b = ctx.spawn(bump, "b")
    a.join()
    b.join()
    return {"n": box["n"]}


def _double_claim(ctx):
    """Check-then-act claim table with no lock: two threads can both
    observe the key absent and both claim ownership."""
    claims = {}

    def worker(name):
        ctx.step("claims")
        owner = "k" not in claims
        ctx.step("claims")
        claims["k"] = name
        if owner:
            ctx.note("begin", key="k", owner=True)
            ctx.note("apply", key="k")
            ctx.note("resolve", key="k", value=name)

    a = ctx.spawn(worker, "a")
    b = ctx.spawn(worker, "b")
    a.join()
    b.join()


def _lost_wakeup(ctx):
    """Flag checked under the lock but waited on in a second critical
    section: the notify can land in between and is lost forever."""
    cond = ctx.condition("cv")
    box = {"ready": False}

    def waiter():
        with cond:
            ctx.step("box")
            ready = box["ready"]
        if not ready:
            with cond:
                cond.wait()     # bug: no re-check, no while loop

    def setter():
        ctx.step("box")
        box["ready"] = True
        with cond:
            cond.notify()

    w = ctx.spawn(waiter)
    s = ctx.spawn(setter)
    s.join()
    w.join()


def _ab_ba(ctx):
    """Classic AB/BA lock-ordering deadlock."""
    la = ctx.lock("a")
    lb = ctx.lock("b")

    def one():
        with la:
            with lb:
                pass

    def two():
        with lb:
            with la:
                pass

    t1 = ctx.spawn(one)
    t2 = ctx.spawn(two)
    t1.join()
    t2.join()


def _edf_inversion(ctx):
    ctx.note("pickup", group=[(5.0, 1), (2.0, 0)], left=[])


def _edf_overtaken(ctx):
    ctx.note("pickup", group=[(5.0, 0)], left=[(2.0, 1)])


def _forgotten_release(ctx):
    # a 429'd step whose claim was never released: no retry ever applies
    ctx.note("begin", key=7, owner=True)
    ctx.note("backpressure", key=7)


def _leaked_admit(ctx):
    ctx.note("admitted", tenant=0)
    ctx.note("admitted", tenant=0)
    ctx.note("completed", tenant=0)
    ctx.note("final_depth", tenant=0, depth=1)


def _dropped_waiter(ctx):
    ctx.note("enqueue", key="r1")
    ctx.note("enqueue", key="r2")
    ctx.note("resolved", key="r1")


def _violations(name, fn, named=(), *, budget=200, bound=3):
    out = []
    explore(name, fn, budget=budget, bound=bound,
            on_run=lambda run: out.extend(check_run(run, named)))
    return out


# ---------------------------------------------------------------------- #
# determinism and replay
# ---------------------------------------------------------------------- #

def test_same_forced_schedule_is_bit_identical():
    res = explore("counter", _counter_race, budget=50)
    assert res.schedules >= 2
    for sid in res.schedule_ids[:5]:
        forced = decode_choices(sid.split(":", 1)[1])
        a = run_schedule("counter", _counter_race, forced=forced)
        b = run_schedule("counter", _counter_race, forced=forced)
        assert a.trace_fingerprint() == b.trace_fingerprint()
        assert a.trace == b.trace
        assert a.notes == b.notes
        assert a.decisions == b.decisions
        assert a.state == b.state == {"n": 4}


def test_schedule_id_roundtrip():
    for choices in ((), (0,), (1, 0, 2), tuple(range(7))):
        assert decode_choices(encode_choices(choices)) == choices


def test_explore_is_deterministic_in_both_modes():
    for mode in ("dfs", "random"):
        a = explore("counter", _counter_race, budget=40, mode=mode, seed=3)
        b = explore("counter", _counter_race, budget=40, mode=mode, seed=3)
        assert a.schedule_ids == b.schedule_ids
        assert a.sample == b.sample
        assert a.summary() == b.summary()


def test_counterexample_replays_bit_for_bit():
    # find a deadlocking schedule of the AB/BA toy, then replay it from
    # nothing but the violation's schedule id
    found = _violations("abba", _ab_ba)
    dead = [v for v in found if v.invariant == "deadlock_free"]
    assert dead, "AB/BA toy must deadlock under exploration"
    v = dead[0]
    forced = decode_choices(v.schedule_id.split(":", 1)[1])
    replay = run_schedule("abba", _ab_ba, forced=forced)
    assert replay.deadlock is not None
    assert replay.schedule_id == v.schedule_id
    again = run_schedule("abba", _ab_ba, forced=forced)
    assert again.trace_fingerprint() == replay.trace_fingerprint()


# ---------------------------------------------------------------------- #
# each invariant fires on its seeded-violation toy
# ---------------------------------------------------------------------- #

def test_exactly_once_claims_catches_double_owner():
    found = _violations("dbl", _double_claim, ("exactly_once_claims",))
    assert any(v.invariant == "exactly_once_claims" for v in found)
    v = next(v for v in found if v.invariant == "exactly_once_claims")
    assert "--schedule" in str(v)          # replay instructions carried
    assert v.schedule_id.startswith("dbl:")


def test_no_lost_wakeup_catches_if_guarded_wait():
    found = _violations("lw", _lost_wakeup)
    stuck = [v for v in found if v.invariant == "no_lost_wakeup"]
    assert stuck
    # and the counterexample replays to the same stall
    forced = decode_choices(stuck[0].schedule_id.split(":", 1)[1])
    replay = run_schedule("lw", _lost_wakeup, forced=forced)
    assert replay.stalled and not replay.deadlock


def test_deadlock_free_reports_the_cycle():
    found = _violations("abba", _ab_ba)
    dead = [v for v in found if v.invariant == "deadlock_free"]
    assert dead
    assert "cycle" in str(dead[0])


def test_edf_pickup_order_catches_inversion_and_overtaking():
    assert any(v.invariant == "edf_pickup_order" for v in _violations(
        "edf1", _edf_inversion, ("edf_pickup_order",)))
    assert any(v.invariant == "edf_pickup_order" for v in _violations(
        "edf2", _edf_overtaken, ("edf_pickup_order",)))


def test_reclaimable_429_catches_forgotten_release():
    found = _violations("bp", _forgotten_release, ("reclaimable_429",))
    assert any(v.invariant == "reclaimable_429" for v in found)


def test_admission_conservation_catches_leaked_slot():
    found = _violations("adm", _leaked_admit, ("admission_conservation",))
    assert any(v.invariant == "admission_conservation" for v in found)


def test_all_resolved_catches_dropped_waiter():
    found = _violations("drop", _dropped_waiter, ("all_resolved",))
    assert any(v.invariant == "all_resolved" for v in found)
    assert "r2" in str(found[0])


def test_correct_toy_is_clean():
    assert _violations("counter", _counter_race,
                       tuple(INVARIANTS) ) == []


def test_generic_invariants_are_registered():
    for fn in GENERIC:
        assert INVARIANTS[fn.__name__] is fn


# ---------------------------------------------------------------------- #
# real-tree-clean gate (mirrors test_real_tree_has_zero_unwaived_findings)
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("scenario", ["replay_dup_storm",
                                      "admission_bucket_race",
                                      "coalesce_window_handoff",
                                      "coalesce_shed_close_race"])
def test_real_scenarios_are_clean(scenario):
    assert engine.main(["--check", "--scenario", scenario,
                        "--budget", "60"]) == 0


def test_check_rejects_unknown_scenario():
    with pytest.raises(SystemExit):
        engine.main(["--check", "--scenario", "no_such_scenario"])
