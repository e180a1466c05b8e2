"""Observer-layer tests (DESIGN.md §8): lifecycle hooks, aggregate stats,
and Chrome-trace export validity."""
import json
import threading

from repro.core import (
    ChromeTraceObserver,
    PoolObserver,
    StatsObserver,
    TaskGraph,
    ThreadPool,
)


class Recorder(PoolObserver):
    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def on_submit(self, task):
        with self._lock:
            self.events.append(("submit", task.name))

    def on_start(self, task, worker):
        with self._lock:
            self.events.append(("start", task.name))

    def on_finish(self, task, worker):
        with self._lock:
            self.events.append(("finish", task.name))

    def on_steal(self, task, thief, victim):
        with self._lock:
            self.events.append(("steal", task.name))


def test_observer_sees_lifecycle_events():
    rec = Recorder()
    with ThreadPool(2, observers=[rec]) as pool:
        g = TaskGraph()
        a = g.add(lambda: None, name="a")
        g.add(lambda: None, name="b").succeed(a)
        pool.run(g)
    kinds = [k for k, _ in rec.events]
    assert kinds.count("start") == 2 and kinds.count("finish") == 2
    # the root is submitted; the continuation (b) runs inline, no re-queue
    assert ("submit", "a") in rec.events
    starts = [n for k, n in rec.events if k == "start"]
    assert starts == ["a", "b"]


def test_add_remove_observer():
    rec = Recorder()
    with ThreadPool(1) as pool:
        pool.run(lambda: None)
        pool.add_observer(rec)
        pool.run(lambda: None)
        pool.remove_observer(rec)
        pool.remove_observer(rec)  # absent: no-op
        pool.run(lambda: None)
    assert [k for k, _ in rec.events].count("finish") == 1


def test_observer_exceptions_are_swallowed():
    class Broken(PoolObserver):
        def on_start(self, task, worker):
            raise RuntimeError("observer bug")

    with ThreadPool(1, observers=[Broken()]) as pool:
        hits = []
        pool.run(lambda: hits.append(1))
        assert hits == [1]


def test_stats_observer_counts_and_timing():
    obs = StatsObserver()
    with ThreadPool(2, observers=[obs]) as pool:
        g = TaskGraph()
        for i in range(8):
            g.add(lambda: sum(range(200)), name=f"work:{i}")
        pool.run(g)
    s = obs.summary()
    assert s["started"] == s["finished"] == 8
    assert s["errors"] == 0
    assert s["by_name"]["work"]["count"] == 8
    assert s["by_name"]["work"]["total_s"] >= 0.0


def test_stats_observer_sees_steals():
    """One worker parks holding a gate after pushing tasks to its own deque;
    the other worker can only get them by stealing."""
    obs = StatsObserver()
    with ThreadPool(2, observers=[obs]) as pool:
        gate = threading.Event()
        done = threading.Event()
        remaining = [6]
        lock = threading.Lock()

        def child():
            with lock:
                remaining[0] -= 1
                if remaining[0] == 0:
                    done.set()

        def parent():
            for _ in range(6):
                pool.submit(child)  # lands in this worker's own deque
            done.wait(10)  # hold this worker until the children finish
            gate.set()

        pool.submit(parent)
        assert gate.wait(10)
        assert pool.wait_idle(10)
    assert obs.stolen >= 1
    assert pool.stats()["steals"] >= 1


def test_chrome_trace_is_valid_trace_event_json():
    """Acceptance: the exporter output loads as trace-event JSON — a dict
    with a traceEvents list of complete events carrying name/ph/ts/dur and
    integer pid/tid, exactly what chrome://tracing ingests."""
    tracer = ChromeTraceObserver()
    with ThreadPool(2, observers=[tracer]) as pool:
        g = TaskGraph("traced")
        a = g.add(lambda: sum(range(100)), name="root")
        g.then(a, lambda x: x + 1, name="child")
        pool.run(g)
        payload = tracer.to_json(num_workers=pool.num_threads)
    trace = json.loads(payload)  # round-trips as strict JSON
    assert isinstance(trace, dict)
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} >= {"root", "child"}
    for e in complete:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # worker-name metadata present for every lane
    meta = [e for e in events if e.get("ph") == "M"]
    assert {m["tid"] for m in meta} == {0, 1}


def test_chrome_trace_save_roundtrip(tmp_path):
    tracer = ChromeTraceObserver()
    with ThreadPool(1, observers=[tracer]) as pool:
        pool.run(lambda: None)
    path = tmp_path / "trace.json"
    tracer.save(path)
    trace = json.loads(path.read_text())
    assert any(e["ph"] == "X" for e in trace["traceEvents"])


def test_chrome_trace_marks_errors_and_cancellations():
    tracer = ChromeTraceObserver()
    with ThreadPool(1, observers=[tracer]) as pool:
        f = pool.submit_future(lambda: 1 / 0)
        try:
            f.result(10)
        except ZeroDivisionError:
            pass
        assert pool.wait_idle(10)
    events = json.loads(tracer.to_json())["traceEvents"]
    assert any("error" in e.get("args", {}) for e in events)


def test_stats_observer_counts_retries_and_timeouts():
    """§14 observability: StatsObserver's summary carries the retried /
    timed_out counters alongside the lifecycle counts."""
    from repro.core import RetryPolicy, checkpoint

    stats = StatsObserver()
    attempts = [0]

    def flaky():
        attempts[0] += 1
        if attempts[0] < 3:
            raise ValueError("transient")
        return attempts[0]

    def wedged():
        import time

        while True:
            time.sleep(0.005)
            checkpoint()

    with ThreadPool(2, observers=[stats]) as pool:
        g = TaskGraph("faulty")
        g.add(flaky, name="flaky", retry=RetryPolicy(max_attempts=3, backoff=0.0))
        w = g.add(wedged, name="wedged", timeout=0.05)
        w.propagate_errors = False
        pool.run(g)
    s = stats.summary()
    assert s["retried"] == 2
    assert s["timed_out"] == 1
    assert s["finished"] >= 2  # both tasks still complete their lifecycle


def test_chrome_trace_marks_retries_and_timeouts():
    """§14 observability: retries show up as "retry:<name>" complete events
    (cat "fault", args.attempt) and timeouts as "timeout:<name>" instants."""
    from repro.core import RetryPolicy, checkpoint

    tracer = ChromeTraceObserver()
    attempts = [0]

    def flaky():
        attempts[0] += 1
        if attempts[0] < 2:
            raise ValueError("transient")

    def wedged():
        import time

        while True:
            time.sleep(0.005)
            checkpoint()

    with ThreadPool(2, observers=[tracer]) as pool:
        g = TaskGraph("faulty")
        g.add(flaky, name="flaky", retry=RetryPolicy(max_attempts=2, backoff=0.0))
        w = g.add(wedged, name="wedged", timeout=0.05)
        w.propagate_errors = False
        pool.run(g)
    events = json.loads(tracer.to_json())["traceEvents"]
    retries = [e for e in events if e["name"] == "retry:flaky"]
    assert len(retries) == 1
    assert retries[0]["ph"] == "X" and retries[0]["cat"] == "fault"
    assert retries[0]["args"]["attempt"] == 1
    timeouts = [e for e in events if e["name"] == "timeout:wedged"]
    assert len(timeouts) == 1
    assert timeouts[0]["ph"] == "i" and timeouts[0]["cat"] == "fault"


def test_chrome_trace_span_nests_on_the_worker_lane():
    """``span()`` inside a task body lands on that worker's lane, inside the
    task's slice, with the args the block added; on a thread outside the
    pool it gets a lane of its own, named after the thread."""
    tracer = ChromeTraceObserver()

    def body():
        with tracer.span("phase", step=1) as args:
            args["rows"] = 8

    with ThreadPool(2, observers=[tracer]) as pool:
        g = TaskGraph("one")
        g.add(body, name="work")
        pool.run(g)
        assert pool.wait_idle(10)
    with tracer.span("outside"):
        pass
    events = json.loads(tracer.to_json(num_workers=2))["traceEvents"]
    (task,) = [e for e in events if e["name"] == "work"]
    (phase,) = [e for e in events if e["name"] == "phase"]
    (outside,) = [e for e in events if e["name"] == "outside"]
    assert phase["tid"] == task["tid"] and phase["cat"] == "span"
    assert task["ts"] <= phase["ts"] and phase["ts"] + phase["dur"] <= task["ts"] + task["dur"]
    assert phase["args"] == {"step": 1, "rows": 8}
    assert outside["tid"] == threading.get_ident() and "args" not in outside
    names = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    assert names == {0: "worker-0", 1: "worker-1", outside["tid"]: threading.current_thread().name}
