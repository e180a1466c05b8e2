"""The engine's own spans and per-request marks: the ``serve.tick.*`` phases
nest in their pool ``decode-tick`` slice, the TTFT marks are ordered and
come from the first prefill, the same spans reach a ``jax.profiler`` trace
with their args, and without ``trace_path`` nothing is recorded or hooked."""
import gc
import glob
import time

import jax
import numpy as np

from repro.configs import get_reduced
from repro.core import ChromeTraceObserver, ThreadPool
from repro.models import build_model
from repro.serve import ServeEngine

PHASES = [
    "serve.tick.join", "serve.tick.prepare", "serve.tick.dispatch", "serve.tick.sync",
    "serve.tick.apply",
]


def _build():
    cfg = get_reduced("tinyllama-1.1b")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _spans(tracer):
    return [e for e in tracer.to_trace()["traceEvents"] if e["ph"] == "X"]


def _inside(inner, outer):
    return (
        inner["tid"] == outer["tid"]
        and outer["ts"] <= inner["ts"]
        and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    )


def _serve_traced(model, params, prompts, budgets, **kw):
    """Run ``prompts`` through a traced engine; returns (handles, stats,
    tracer, offset), ``offset`` taking tracer µs to the monotonic clock."""
    engine = ServeEngine(model, params, trace_path=kw.pop("trace_path"), **kw)
    tracer = engine.tracer
    offset = tracer._t0 + (time.monotonic() - time.perf_counter())
    try:
        handles = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
        for h in handles:
            h.result(300)
        engine.drain(60)
        engine.pool.wait_idle(30)  # the last tick's slice has closed
        stats = engine.stats()
    finally:
        engine.close()
    return handles, stats, tracer, lambda us: offset + us / 1e6


def test_tick_phases_nest_in_their_decode_tick_in_order(tmp_path):
    cfg, model, params = _build()
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32) for n in (4, 9, 6, 12, 5)
    ]
    budgets = [5, 1, 7, 3, 6]
    handles, stats, tracer, _ = _serve_traced(
        model, params, prompts, budgets, max_slots=2, max_len=32, prefill_buckets=(8, 16),
        trace_path=str(tmp_path / "t.json"),
    )
    events = _spans(tracer)
    ticks = [e for e in events if e["name"] == "decode-tick"]
    phases = [e for e in events if e["name"].startswith("serve.tick.")]
    owned = 0
    decoded = []
    for tick in ticks:
        inner = sorted((e for e in phases if _inside(e, tick)), key=lambda e: e["ts"])
        owned += len(inner)
        names = [e["name"] for e in inner]
        # a tick with nothing to decode stops after prepare
        assert names in (PHASES, PHASES[:2]), names
        for a, b in zip(inner, inner[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        if names == PHASES:
            decoded.append({e["name"]: e.get("args", {}) for e in inner})
    assert owned == len(phases)  # every phase span sits in exactly one tick
    assert len(decoded) == stats["ticks"]
    joined = [rid for e in phases if e["name"] == "serve.tick.join" for rid in e["args"]["joined"]]
    # a budget of one completes at its prefill and never joins
    assert sorted(joined) == sorted(h.rid for h, b in zip(handles, budgets) if b > 1)
    # the prefill pushes each first token, the ticks' apply every other
    assert stats["first_at_prefill"] == len(handles)
    assert sum(d["serve.tick.apply"]["tokens"] for d in decoded) + len(handles) == sum(budgets)
    assert sum(e["args"]["retired"] for e in phases if e["name"] == "serve.tick.apply") <= 5
    assert all(1 <= d["serve.tick.prepare"]["live"] <= 2 for d in decoded)


def test_marks_are_ordered_and_come_from_the_first_prefill(tmp_path):
    """With the page pool oversubscribed, a resident is preempted and
    resumes by re-prefill; its marks stay those of its first prefill."""
    cfg, model, params = _build()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(3)]
    handles, stats, tracer, to_mono = _serve_traced(
        model, params, prompts, [12, 11, 10], max_slots=2, max_len=24, page_size=4,
        num_pages=6, trace_path=str(tmp_path / "t.json"),
    )
    assert stats["preemptions"] >= 1
    for h in handles:
        assert h.submit_t <= h.prefill_start_t <= h.prefill_done_t <= h.first_token_t
    resumes = {}
    for e in _spans(tracer):
        if e["name"].startswith("resume:"):
            resumes.setdefault(int(e["name"].split(":")[1]), to_mono(e["ts"]))
    assert resumes
    for h in handles:
        if h.rid in resumes:
            assert h.prefill_done_t <= h.first_token_t < resumes[h.rid]


def test_a_request_cancelled_while_waiting_has_no_prefill_marks():
    cfg, model, params = _build()
    engine = ServeEngine(model, params, max_slots=1, max_len=16, prefill_lookahead=0)
    try:
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        handles = [engine.submit(prompt, 6) for _ in range(4)]
        # one slot, no lookahead: the first request holds the only place
        # until it finishes, so the other three are still waiting
        assert all(h.cancel() for h in reversed(handles[1:]))
        assert len(handles[0].result(300)) == 6
        assert handles[0].prefill_start_t is not None
        for h in handles[1:]:
            assert h.prefill_start_t is None and h.prefill_done_t is None
            assert h.first_token_t is None
    finally:
        engine.close(drain=False)


def test_the_profiler_sees_the_same_spans_with_their_args(tmp_path):
    cfg, model, params = _build()
    prompts = [np.arange(n, dtype=np.int32) % cfg.vocab_size for n in (3, 7, 11)]
    with jax.profiler.trace(str(tmp_path / "prof")):
        handles, stats, tracer, _ = _serve_traced(
            model, params, prompts, [4, 2, 5], max_slots=2, max_len=32,
            prefill_buckets=(8, 16), trace_path=str(tmp_path / "t.json"),
        )
    path = sorted(glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"), recursive=True))[-1]
    host = [p for p in jax.profiler.ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    assert host
    seen = [
        (e.name, dict(e.stats)) for line in host[0].lines for e in line.events
        if e.name.startswith("serve.")
    ]
    traced = [e for e in _spans(tracer) if e["name"].startswith("serve.tick.")]
    profiled = [(n, a) for n, a in seen if n.startswith("serve.tick.")]
    assert len(profiled) == len(traced)
    assert sorted(n for n, _ in profiled) == sorted(e["name"] for e in traced)
    want = {
        "serve.tick.join": {"joined"},
        "serve.tick.prepare": {"live", "preempted"},
        "serve.tick.apply": {"tokens", "retired"},
        "serve.prefill": {"rid", "bucket", "resume"},
    }
    for name, args in seen:
        assert want.get(name, set()) <= set(args), (name, args)
    prefills = {a["rid"]: a["bucket"] for n, a in seen if n == "serve.prefill"}
    assert prefills == {h.rid: b for h, b in zip(handles, (8, 8, 16))}
    ticks = [e for e in _spans(tracer) if e["name"] == "decode-tick"]
    assert sum(n == "serve.tick" for n, _ in seen) == len(ticks)


def test_without_trace_path_nothing_is_recorded_or_hooked():
    """A tracer the user attached to a shared pool sees the pool's tasks but
    none of the engine's spans, and no gc hook outlives the engine."""
    cfg, model, params = _build()
    hooks = list(gc.callbacks)
    outside = ChromeTraceObserver()
    with ThreadPool(2, observers=[outside]) as pool:
        engine = ServeEngine(model, params, max_slots=2, max_len=16, pool=pool)
        assert engine.tracer is None and gc.callbacks == hooks
        engine.generate([np.arange(3, dtype=np.int32)] * 2, 3, timeout=300)
        gc.collect()
        engine.close()
        pool.wait_idle(30)
    names = {e["name"] for e in _spans(outside)}
    assert "decode-tick" in names
    assert not any(n.startswith(("serve.", "host.gc")) for n in names)
    assert gc.callbacks == hooks


def test_trace_path_hooks_gc_until_close(tmp_path):
    cfg, model, params = _build()
    hooks = list(gc.callbacks)
    engine = ServeEngine(
        model, params, max_slots=2, max_len=16, trace_path=str(tmp_path / "t.json")
    )
    tracer = engine.tracer
    assert len(gc.callbacks) == len(hooks) + 1
    gc.collect()
    engine.close()
    assert gc.callbacks == hooks
    collections = [e for e in _spans(tracer) if e["name"] == "host.gc"]
    assert any(e["args"] == {"generation": 2} for e in collections)
    gc.collect()  # after close: no more spans
    assert len([e for e in _spans(tracer) if e["name"] == "host.gc"]) == len(collections)
