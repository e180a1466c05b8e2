"""Serve driver: a mix of requests, open loop, through ``ServeEngine.submit``.

Set-up makes the weights on the chip from the seed in one jitted call,
builds the engine as the mix's ``engine`` block says, and warms up each
prefill bucket the mix's prompts fall in, the cache write at that bucket and
the decode tick. Then a generator thread submits every request at its due
time, whatever the engine is doing, for ``--seconds``. Latencies are timed
from due times, so a stall that delays later requests counts against them.

After the window: requests still running are waited for (up to a minute),
the peak memory is read, the engine and its weights are freed, and a sample
of finished requests drawn from the seed, the longest among them, is run
through the plain float32 reference: for every served token, the gap by
which its reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import math
import sys
import tempfile
import threading
import time
from types import SimpleNamespace

import numpy as np

import harness
import trace_reduce
import traffic as traffic_gen
from peaks import peaks_for

TRACE_SPAN_S = 10.0  # the traced part of the window: its middle ten seconds
DRAIN_S = 60.0  # how long past the close a request may take to finish


class CompileCounter:
    """Counts lowerings and backend compiles while ``armed``."""

    def __init__(self) -> None:
        import jax

        self.armed = False
        self.count = 0

        def listen(event: str, _secs: float, **_kw) -> None:
            if self.armed and event in (
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration",
            ):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def build(cell):
    """The engine with the cell's weights, warmed up."""
    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = cell.model_config()
    model = build_model(cfg)
    params = cell.reference().program_params(
        cell.seed, cell.dims(), cfg.kv_pad_to, expect=model.abstract_params()
    )
    eng = dict(cell.traffic["engine"])
    engine = ServeEngine(
        model, params, trace_path=f"{cell.scratch}/pool.json" if cell.trace else None, **eng
    )
    # warm up: one request per bucket the mix's prompt lengths reach
    lo, hi = cell.traffic["prompt_len"]["min"], cell.traffic["prompt_len"]["max"]
    buckets = sorted(eng.get("prefill_buckets") or [])
    used = [b for i, b in enumerate(buckets) if b >= lo and (i == 0 or buckets[i - 1] < hi)]
    handles = [engine.submit(np.zeros(b, np.int32), 2) for b in used]
    for h in handles:
        h.result(600)
    return engine


def _generate(engine, requests, t0, records, stop) -> None:
    import jax

    for r in requests:
        if stop.is_set():
            return
        rec = records[r.idx]
        delay = t0 + r.due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        with jax.profiler.TraceAnnotation(f"bench.submit:{r.idx}"):
            rec.mark_ns = time.perf_counter_ns()
            rec.sent = time.monotonic()
            try:
                rec.handle = engine.submit(r.prompt, r.max_new)
            except Exception as e:  # noqa: BLE001 - a refused request counts as failed
                rec.error = repr(e)


def _trace_window(tdir, t0, seconds, out, stop) -> None:
    import jax

    a, b = 0.0, seconds
    if seconds > TRACE_SPAN_S:
        a = (seconds - TRACE_SPAN_S) / 2
        b = a + TRACE_SPAN_S
    time.sleep(max(0.0, t0 + a - time.monotonic()))
    jax.profiler.start_trace(tdir)
    out["start"] = time.monotonic()
    stop.wait(max(0.0, t0 + b - time.monotonic()))
    out["stop"] = time.monotonic()
    jax.profiler.stop_trace()


def run(cell):
    with tempfile.TemporaryDirectory(prefix="bench-") as cell.scratch:
        return _run(cell)


def window(engine, requests, seconds, tdir=None):
    """Offer ``requests`` open loop for ``seconds``; trace the middle of the
    window into ``tdir`` when given. Returns the client's view of each
    request when the window closed, and the window's clock marks."""
    records = [
        SimpleNamespace(
            idx=r.idx, prompt_len=len(r.prompt), max_new=r.max_new, handle=None, error=None,
            due=None, sent=None, mark_ns=None, first=None, times=[],
        )
        for r in requests
    ]
    counter = CompileCounter()
    stop = threading.Event()
    marks: dict = {"mono_minus_perf": time.monotonic() - time.perf_counter()}
    t0 = marks["t0"] = time.monotonic()
    counter.armed = True
    for rec, r in zip(records, requests):
        rec.due = t0 + r.due
    gen = threading.Thread(target=_generate, args=(engine, requests, t0, records, stop))
    tracer_thread = None
    if tdir is not None:
        tracer_thread = threading.Thread(
            target=_trace_window, args=(tdir, t0, seconds, marks, stop)
        )
        tracer_thread.start()
    gen.start()
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    t_end = marks["t_end"] = time.monotonic()
    stop.set()
    counter.armed = False
    gen.join()
    if tracer_thread is not None:
        tracer_thread.join()
    for rec in records:  # what the client had seen when the window closed
        h = rec.handle
        if h is not None:
            rec.first = h.first_token_t
            rec.times = [t for t in list(h.token_times) if t <= t_end]
    marks["compiles"] = counter.count
    return records, marks


def collect(records, requests, t_end):
    """Wait for every answer (up to a minute past the close); returns the
    outputs by request index and the number of requests that failed."""
    outputs = {}
    for rec in records:
        if rec.handle is None:
            continue
        try:
            out = rec.handle.result(max(1.0, t_end + DRAIN_S - time.monotonic()))
            outputs[rec.idx] = np.asarray(out)
        except Exception as e:  # noqa: BLE001 - late past the limit, or failed
            rec.error = repr(e)
    failed = sum(1 for rec in records if rec.idx not in outputs)
    failed += sum(1 for i, o in outputs.items() if len(o) != requests[i].max_new)
    return outputs, failed


def free(engine) -> None:
    """Close the engine and drop its weights and page pools from the chip:
    request handles keep the engine object alive past its close."""
    engine.close()
    engine.params = None
    engine.kv.pools = None
    gc.collect()


def pool_spans(engine, mono_minus_perf) -> list:
    """The engine tracer's task spans as (name, start, end) on the monotonic clock."""
    tracer = engine.tracer
    if tracer is None:
        return []
    base = tracer._t0 + mono_minus_perf  # tracer timestamps are µs from its creation
    return [
        (e["name"], base + e["ts"] / 1e6, base + (e["ts"] + e["dur"]) / 1e6)
        for e in tracer.to_trace()["traceEvents"]
        if e.get("ph") == "X"
    ]


def _run(cell):
    engine = build(cell)
    mix = cell.traffic
    requests = traffic_gen.make_requests(mix, cell.seed, cell.seconds, cell.published["vocab_size"])
    setup_s = time.perf_counter() - cell.t_start  # the window opens next
    tdir = f"{cell.scratch}/xplane" if cell.trace else None
    records, marks = window(engine, requests, cell.seconds, tdir)
    outputs, failed = collect(records, requests, marks["t_end"])
    stats = engine.stats()
    mem = harness.memory_peak_bytes()
    spans = pool_spans(engine, marks["mono_minus_perf"])
    free(engine)

    view = SimpleNamespace(
        setup_s=setup_s, seconds=cell.seconds, t0=marks["t0"], t_end=marks["t_end"],
        records=records, spans=spans, dims=cell.dims(), trace=None, traced=None,
    )
    breakdown = None
    if cell.trace:
        view.trace, view.traced, breakdown = _reduce(tdir, marks, records, spans)
        view.peaks = peaks_for(cell.device["kind"])

    t_ref = time.perf_counter()
    gap, sampled = check_outputs(cell, requests, outputs)
    ref_s = time.perf_counter() - t_ref
    checks = [
        ("max_logit_gap", gap, float(cell.config["check"]["max_logit_gap"])),
        ("requests_failed", failed, 0),
    ]
    correct = all(v <= lim for _, v, lim in checks)
    metrics = harness.read_metrics(
        cell.per_layer if cell.trace else cell.end_to_end, view, required=not cell.trace
    )
    device = dict(cell.device, memory_peak_bytes=mem)
    if cell.trace:
        device.update(busy_s=view.trace["busy_s"], window_s=view.trace["window_s"])
    result = {
        "correct": bool(correct), "attempted": len(requests), "failed": int(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    lateness = [r.sent - r.due for r in records if r.sent is not None]
    ttft = [(r.first if r.first is not None else marks["t_end"]) - r.due for r in records]
    print(
        f"info: compiles_in_window={marks['compiles']} sampled_tokens={sampled} "
        f"reference_s={ref_s:.3f} generator_late_p95_ms={1e3 * _pct(lateness, 95):.3f} "
        f"ttft_ms_p50_p90_p95_p99={[round(1e3 * _pct(ttft, q), 3) for q in (50, 90, 95, 99)]} "
        f"engine={_brief(stats)}",
        file=sys.stderr, flush=True,
    )
    return result, checks


def _brief(stats: dict) -> str:
    keys = ("requests", "completed", "preemptions", "ticks", "mean_occupancy", "tokens_out")
    return ",".join(f"{k}={stats[k]}" for k in keys)


def _pct(xs, q):
    return float(np.percentile(xs, q)) if len(xs) else math.nan


def _reduce(tdir, marks, records, spans):
    """The device-0 trace on the monotonic clock, and the run's breakdown."""
    tr = trace_reduce.load(trace_reduce.find_xplane(tdir), host_prefixes=("bench.submit:",))
    submit_ns = {r.idx: r.mark_ns for r in records if r.mark_ns is not None}
    offs = [
        submit_ns[int(name.split(":")[1])] - (start + dur / 2)
        for name, start, dur in tr.host
        if int(name.split(":")[1]) in submit_ns
    ]
    if not offs:
        raise RuntimeError("no bench.submit span in the trace: cannot place it on the host clock")
    # xplane ns + off = perf_counter ns; + mono_minus_perf = monotonic
    off = float(np.median(offs))
    mono_minus_perf = marks["mono_minus_perf"]
    to_ns = lambda t_mono: (t_mono - mono_minus_perf) * 1e9 - off  # noqa: E731
    to_mono = lambda ns: (ns + off) / 1e9 + mono_minus_perf  # noqa: E731
    lo, hi = int(to_ns(marks["start"])), int(to_ns(marks["stop"]))
    if not tr.devices:
        raise RuntimeError("the trace holds no TPU device plane")
    dev = tr.devices[min(tr.devices)]
    merged = trace_reduce.merge(((o.start, o.end) for o in dev.ops), lo, hi)
    busy = trace_reduce.total(merged) / 1e9
    programs = [
        (p.name, to_mono(p.start), to_mono(p.end)) for p in dev.programs if lo <= p.start <= hi
    ]
    idle = sorted(trace_reduce.gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:10]

    def host_task(ns_mid):
        t = to_mono(ns_mid)
        running = [n for n, s, e in spans if s <= t <= e]
        return ",".join(sorted({n.split(":")[0] for n in running})) or "no pool task"

    breakdown = {
        "device_ops": trace_reduce.top_ops(dev, lo, hi),
        "idle_gaps": [[host_task((s + e) / 2), (e - s) / 1e9] for s, e in idle],
    }
    view_trace = {
        "busy_s": busy, "window_s": (hi - lo) / 1e9, "programs": programs,
    }
    return view_trace, (marks["start"], marks["stop"]), breakdown


def sample_rows(cell, requests, outputs):
    """A seeded sample of finished requests, the longest among them, as
    reference inputs: ``(tokens, positions, served, mask)``. Row ``i`` holds
    prompt + served tokens but the last; position ``j`` is where served
    token ``j`` was predicted."""
    done = sorted(outputs)
    k = int(cell.traffic["check"]["sample_requests"])
    longest = max(done, key=lambda i: len(requests[i].prompt) + len(outputs[i]))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng((cell.seed, 0x636B))
    pick = [longest] + list(rng.choice(rest, size=min(k - 1, len(rest)), replace=False))
    S = cell.traffic["prompt_len"]["max"] + cell.traffic["output_len"]["max"]
    P = cell.traffic["output_len"]["max"]
    toks = np.zeros((k, S), np.int32)
    pos = np.zeros((k, P), np.int32)
    served = np.zeros((k, P), np.int32)
    mask = np.zeros((k, P), bool)
    for row, i in enumerate(pick):
        prompt, out = requests[i].prompt, outputs[i]
        seq = np.concatenate([prompt, out[:-1]])
        toks[row, : len(seq)] = seq
        n = len(out)
        pos[row, :n] = len(prompt) - 1 + np.arange(n)
        served[row, :n] = out
        mask[row, :n] = True
    return toks, pos, served, mask


def check_outputs(cell, requests, outputs):
    """Widest gap of a served token's reference logit below the reference's
    best, over :func:`sample_rows`. Returns (gap, tokens compared); with no
    finished request it reads +inf."""
    if not outputs:
        return math.inf, 0
    toks, pos, served, mask = sample_rows(cell, requests, outputs)
    logits = cell.reference().logits_at(cell.seed, cell.dims(), cell.published, toks, pos)
    return masked_max(token_gaps(logits, served), mask), int(mask.sum())


def masked_max(x, mask) -> float:
    return float(np.max(np.where(mask, x, -np.inf)))


def token_gaps(logits, tokens) -> np.ndarray:
    """``max(logits) - logits[token]`` at each position, in float32."""
    import jax.numpy as jnp

    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[..., None], axis=-1)[..., 0]
    return np.asarray(best - got)
