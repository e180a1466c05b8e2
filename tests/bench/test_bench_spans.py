"""The per-layer metrics that read the serve engine's own spans and marks:
``prefill_wall_ms``, ``join_wait_ms`` and ``tick_host_ms``. Exact values on
views made by hand, nothing on a view without the marks, and finite numbers
from a tiny chat cell run on the CPU through the driver's window with the
engine's tracer attached."""
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import traffic  # noqa: E402
from test_bench_correct import tiny_cell  # noqa: E402

NEW = ("prefill_wall_ms", "join_wait_ms", "tick_host_ms")


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py", f"spans_{name}")


def _rec(start=None, done=None, first=None):
    handle = SimpleNamespace(rid=0, prefill_start_t=start, prefill_done_t=done)
    return SimpleNamespace(handle=handle, first=first)


def _view(records=(), spans=(), t0=100.0, t_end=200.0):
    return SimpleNamespace(records=list(records), spans=list(spans), t0=t0, t_end=t_end)


def test_prefill_wall_and_join_wait_from_the_marks():
    records = [
        _rec(110.0, 110.030, 110.080),
        _rec(120.0, 120.010, 120.050),
        _rec(130.0, 130.050, 130.070),
        _rec(140.0, 140.020, None),  # no first token yet: no join wait
        _rec(150.0, None, None),  # still prefilling: neither
        _rec(),  # waiting
        _rec(190.0, 190.040, 200.5),  # first token after the close
    ]
    view = _view(records)
    # walls 30, 10, 50, 20, 40 ms; join waits 50, 40, 20 ms
    assert _reader("prefill_wall_ms").read(view) == pytest.approx(30.0, abs=1e-9)
    assert _reader("join_wait_ms").read(view) == pytest.approx(40.0, abs=1e-9)


def test_tick_host_pairs_each_sync_with_the_next_dispatch():
    spans = [
        ("serve.tick.dispatch", 100.000, 100.001),
        ("serve.tick.sync", 100.001, 100.030),
        ("serve.tick.dispatch", 100.032, 100.033),  # 3 ms after the sync
        ("serve.tick.sync", 100.033, 100.060),
        ("serve.tick.dispatch", 100.064, 100.065),  # 5 ms
        ("serve.tick.sync", 100.065, 100.090),
        ("tick-entry", 100.5, 100.5),  # the loop drained and restarted
        ("serve.tick.dispatch", 100.6, 100.601),  # left out: straddles it
        ("serve.tick.sync", 100.601, 100.630),
        ("serve.tick.dispatch", 100.633, 100.634),  # 4 ms
        ("serve.tick.sync", 100.635, 100.660),  # no next dispatch
        ("serve.tick.sync", 99.0, 99.030),  # ends before the window
        ("serve.tick.dispatch", 99.031, 99.032),
        ("decode-tick", 100.0, 100.090),
    ]
    assert _reader("tick_host_ms").read(_view(spans=spans)) == pytest.approx(4.0, abs=1e-9)


def test_nothing_to_read_reads_none():
    # a program without the marks and spans, as the benchmark's parent had
    bare = SimpleNamespace(
        records=[SimpleNamespace(handle=SimpleNamespace(rid=0), first=101.0),
                 SimpleNamespace(handle=None, first=None)],
        spans=[("prefill:0", 100.0, 100.1), ("decode-tick", 100.1, 100.2)],
        t0=100.0, t_end=200.0,
    )
    for name in NEW:
        assert _reader(name).read(bare) is None
        assert _reader(name).read(_view()) is None
    drained = [
        ("serve.tick.sync", 100.0, 100.01), ("tick-entry", 100.5, 100.5),
        ("serve.tick.dispatch", 100.6, 100.61),
    ]
    assert _reader("tick_host_ms").read(_view(spans=drained)) is None


def test_a_tiny_cell_reads_all_three_on_one_clock(tmp_path):
    """The chat cell cut to the CPU, with the engine's tracer attached as a
    traced run attaches it; the marks and the tracer's spans share a clock."""
    serve = harness.load_module(BENCH / "drivers" / "serve.py", "spans_serve_driver")
    cell = tiny_cell()
    cell.trace, cell.scratch = True, str(tmp_path)
    engine = serve.build(cell)
    try:
        requests = traffic.make_requests(
            cell.traffic, cell.seed, cell.seconds, cell.published["vocab_size"]
        )
        records, marks = serve.window(engine, requests, cell.seconds)
        _outputs, failed = serve.collect(records, requests, marks["t_end"])
        spans = serve.pool_spans(engine, marks["mono_minus_perf"])
    finally:
        serve.free(engine)
    assert failed == 0
    view = SimpleNamespace(t0=marks["t0"], t_end=marks["t_end"], records=records, spans=spans)
    for name in NEW:
        value = _reader(name).read(view)
        assert value is not None and math.isfinite(value) and value > 0, (name, value)
    first = {}
    for name, s, e in spans:
        if name.startswith("prefill:"):
            first.setdefault(int(name.split(":")[1]), (s, e))
    handles = [r.handle for r in records if r.handle is not None]
    assert handles and all(h.rid in first for h in handles)
    # each mark lies in its task's slice, and typically within a millisecond
    # of its start; a thread switch between the slice's start and the body
    # (the interpreter's 5 ms switch interval) can part the two on a busy host
    late = []
    for h in handles:
        s, e = first[h.rid]
        assert s - 1e-6 <= h.prefill_start_t <= e + 1e-6, h.rid
        late.append(h.prefill_start_t - s)
    assert sorted(late)[len(late) // 2] < 1e-3
