"""Graph capture & replay (DESIGN.md §12).

The replay contract under test:

* an unchanged graph re-run through the facade dispatches from its
  captured :class:`ReplayPlan` from the second pass on — same results,
  bit-identical dataflow values, same observer event stream;
* every divergence source (structural mutation, a condition branching
  off the recorded path is *allowed*, runtime-sized subflows resizing is
  *allowed*, cancellation, task failure) either replays correctly or
  falls back to live dispatch transparently — never a wrong answer;
* the serial backend never compiles a plan (there is nothing to save),
  the process backend replays with full §11 placement parity.
"""
import threading

import pytest

from repro.core import (
    CancelledError,
    Executor,
    Runtime,
    StatsObserver,
    TaskGraph,
)

BACKENDS = ("serial", "thread", "process")


@pytest.fixture(params=BACKENDS)
def ex(request):
    """One Executor per backend — replay must be invisible on all three."""
    n = 2 if request.param == "process" else 4
    with Executor(n, backend=request.param) as e:
        yield e


@pytest.fixture()
def tex():
    """Thread-backend executor for replay-internal assertions."""
    with Executor(4, backend="thread") as e:
        yield e


def _plan_expected(ex):
    return ex.backend in ("thread", "process")


# ---------------------------------------------------------------------------
# parity: unchanged graphs replay with identical results (all backends)
# ---------------------------------------------------------------------------


def test_replay_parity_across_backends(ex):
    """Three passes of a diamond-with-tails graph: pass 1 runs live and
    records, later passes replay (thread/process) or stay live (serial) —
    results identical either way."""
    g = TaskGraph("diamond")
    a = g.add(lambda: 2, name="a")
    b = g.then(a, lambda x: x + 1, name="b")
    c = g.then(a, lambda x: x * 10, name="c")
    d = g.add(lambda: "done", name="d")
    d.after(b, c)
    for i in range(3):
        assert ex.run(g).result(30) is None
        assert (b.result, c.result, d.result) == (3, 20, "done")
        has_plan = g.replay_plan is not None
        assert has_plan == (_plan_expected(ex) and i >= 1)


def test_replay_chain_dataflow_bit_identical(ex):
    """A pure dataflow chain produces the same value every pass — the
    fused segment forwards argument slots exactly like live fan-out."""
    g = TaskGraph("chain")
    t = g.add(lambda: 1.0, name="head")
    for i in range(12):
        t = g.then(t, lambda x, k=i: x * 3.0 + k, name=f"n{i}")
    results = []
    for _ in range(4):
        ex.run(g).result(30)
        results.append(t.result)
    assert all(r == results[0] for r in results[1:])


def test_replay_runtime_sized_subflow_changes_size(ex):
    """A spawner sized by runtime state replays through the same plan —
    subflows are spawned fresh each pass, never captured."""
    g = TaskGraph("sub")
    width = {"n": 2}
    acc = []

    def spawn(rt: Runtime):
        # affinity="local": side effects on ``acc`` must stay in-parent
        # so the assertion sees them on the process backend too
        for i in range(width["n"]):
            rt.sub.add(lambda i=i: acc.append(i), affinity="local")

    sp = g.add(spawn, takes_runtime=True, name="spawn")
    g.add(
        lambda _: acc.append(-1), name="tail", takes_inputs=True, affinity="local"
    ).succeed(sp)
    for n in (2, 5, 1, 4):
        width["n"] = n
        acc.clear()
        ex.run(g).result(30)
        assert sorted(acc) == [-1, *range(n)]


def test_replay_condition_loop_trip_count_varies(ex):
    """A condition loop whose trip count differs between passes keeps its
    plan: branch tables are part of the capture, outcomes are not."""
    g = TaskGraph("loop")
    state = {"i": 0, "limit": 3, "runs": 0}
    # loop state lives in the condition body (always runs in-parent), so
    # the counters are authoritative on every backend; entry pins local
    entry = g.add(lambda: state.update(i=0), name="entry", affinity="local")
    body = g.add(lambda: None, name="body")
    body.after(entry)

    def more():
        state["i"] += 1
        state["runs"] += 1
        return 0 if state["i"] < state["limit"] else 1

    cond = g.add(more, kind="condition", name="more")
    cond.after(body)
    cond.precede(body)
    total = 0
    for limit in (3, 7, 1, 5):
        state["limit"] = limit
        ex.run(g).result(30)
        total += limit
        assert state["runs"] == total
        assert (g.replay_plan is not None) == (_plan_expected(ex) and total > 3)


# ---------------------------------------------------------------------------
# invalidation matrix (thread backend: asserts on the plan itself)
# ---------------------------------------------------------------------------


def test_mutation_via_add_drops_plan(tex):
    g = TaskGraph("mut-add")
    seen = []
    g.add(lambda: seen.append("a"), name="a")
    tex.run(g).result(10)
    tex.run(g).result(10)
    plan = g.replay_plan
    assert plan is not None
    g.add(lambda: seen.append("b"), name="b")
    tex.run(g).result(10)  # structural epoch moved: falls back live
    assert seen.count("a") == 3 and seen.count("b") == 1  # a, b run in parallel
    assert g.replay_plan is not plan  # old plan dropped (recompile or None)
    tex.run(g).result(10)  # settled again: recompiles
    assert g.replay_plan is not None and g.replay_plan is not plan


def test_mutation_via_then_drops_plan(tex):
    g = TaskGraph("mut-then")
    a = g.add(lambda: 5, name="a")
    tex.run(g).result(10)
    tex.run(g).result(10)
    assert g.replay_plan is not None
    b = g.then(a, lambda x: x * x, name="b")
    tex.run(g).result(10)
    assert b.result == 25
    tex.run(g).result(10)
    assert b.result == 25 and g.replay_plan is not None


def test_cancellation_mid_replay_falls_back_live(tex):
    """Cancelling a replayed run marks the plan diverged; the next pass
    runs live (full reset) and produces the correct result."""
    g = TaskGraph("cancel")
    gate = threading.Event()
    release = threading.Event()
    hits = []

    def slow():
        gate.set()
        release.wait(10)
        hits.append(1)

    head = g.add(slow, name="head")
    g.then(head, lambda _: hits.append(2), name="tail")
    release.set()  # pass 1 runs straight through; only the replay is held
    tex.run(g).result(10)
    gate.clear()
    release.clear()
    fut = tex.run(g)  # replayed pass
    plan = g.replay_plan
    assert plan is not None
    assert gate.wait(10)  # head is running inside the replay
    fut.cancel()
    release.set()
    with pytest.raises(CancelledError):
        fut.result(10)
    assert plan.diverged
    tex.wait_idle(10)
    hits.clear()
    tex.run(g).result(10)  # live fallback
    assert hits == [1, 2]


def test_failure_mid_replay_then_live_clears_stale_exceptions(tex):
    """Regression (§12 satellite): after a replayed pass fails, the live
    fallback pass must clear every stale member exception — success must
    not be poisoned by the previous pass's corpse."""
    g = TaskGraph("fail")
    mode = {"boom": False}

    def maybe():
        if mode["boom"]:
            raise ValueError("boom")
        return 7

    x = g.add(maybe, name="x")
    y = g.then(x, lambda v: v + 1, name="y")
    tex.run(g).result(10)
    tex.run(g).result(10)
    assert g.replay_plan is not None
    mode["boom"] = True
    with pytest.raises(ValueError, match="boom"):
        tex.run(g).result(10)
    assert g.replay_plan is None or g.replay_plan.diverged
    with pytest.raises(ValueError, match="boom"):
        tex.wait_idle(10)  # drains + clears the pool poison (§10 contract)
    mode["boom"] = False
    tex.run(g).result(10)  # live fallback: stale x/y exceptions must clear
    assert x.exception is None and y.exception is None and y.result == 8


def test_invalidate_plan_escape_hatch(tex):
    g = TaskGraph("hatch")
    a = g.add(lambda: 1, name="a")
    tex.run(g).result(10)
    tex.run(g).result(10)
    assert g.replay_plan is not None
    g.invalidate_plan()
    assert g.replay_plan is None
    tex.run(g).result(10)  # live again, then recompiles
    tex.run(g).result(10)
    assert g.replay_plan is not None and a.result == 1


def test_replay_false_forces_live(tex):
    g = TaskGraph("optout")
    g.add(lambda: 1, name="a")
    for _ in range(3):
        tex.run(g, replay=False).result(10)
    assert g.replay_plan is None


# ---------------------------------------------------------------------------
# submit-path replay + observer parity (thread backend)
# ---------------------------------------------------------------------------


def test_pool_submit_reuses_plan(tex):
    """ThreadPool.submit/run of a graph whose plan was captured by the
    facade dispatches from the plan too (the §12 submit fast path)."""
    g = TaskGraph("submit")
    a = g.add(lambda: 3, name="a")
    b = g.then(a, lambda x: x + 4, name="b")
    tex.run(g).result(10)
    tex.run(g).result(10)
    plan = g.replay_plan
    assert plan is not None
    before = plan.replays
    tex.pool.run(g)  # plain pool path, no future
    assert b.result == 7
    assert g.replay_plan is plan and plan.replays == before + 1


def test_observer_counts_identical_live_vs_replayed(tex):
    """StatsObserver must not be able to tell a replayed pass from a live
    one: per-pass submitted/started/finished deltas are identical, and
    started/finished cover every member of every fused segment."""
    obs = StatsObserver()
    tex.add_observer(obs)
    try:
        g = TaskGraph("obs")
        a = g.add(lambda: 1, name="a")
        b = g.then(a, lambda x: x + 1, name="b")
        c = g.then(b, lambda x: x + 1, name="c")
        d = g.add(lambda: 0, name="d")
        d.after(a)
        def counts():
            return {
                "submitted": obs.submitted,
                "started": obs.started,
                "finished": obs.finished,
            }

        deltas = []
        prev = counts()
        for i in range(3):
            tex.run(g).result(10)
            tex.wait_idle(10)
            cur = counts()
            deltas.append({k: cur[k] - prev[k] for k in prev})
            prev = cur
        assert deltas[1] == deltas[0] == deltas[2]
        # every member ran visibly each pass: a, b, c, d + the hidden fin
        assert deltas[1]["started"] == deltas[1]["finished"] == 5
        assert c.result == 3
    finally:
        tex.remove_observer(obs)


def test_replay_plan_introspection(tex):
    """The plan reports its shape: a pure chain contracts to one segment."""
    g = TaskGraph("intro")
    t = g.add(lambda: 0, name="n0")
    for i in range(1, 6):
        t = g.then(t, lambda x: x + 1, name=f"n{i}")
    tex.run(g).result(10)
    tex.run(g).result(10)
    plan = g.replay_plan
    assert plan is not None
    # the 6 user tasks contract to one segment; the hidden fin keeps its
    # own (its propagate_errors differs — it must run even on failure)
    assert plan.segments == 2 and plan.fused == 5
    assert plan.replays == 1 and not plan.diverged
    assert t.result == 5
