"""End-to-end engine tests: continuous-batched greedy decode must equal
sequential single-request decode token-for-token; eviction, cancellation and
input validation."""
import threading
import time

import numpy as np
import jax
import pytest

from repro.configs import get_reduced
from repro.core import CancelledError, ThreadPool
from repro.models import build_model
from repro.serve import RequestHandle, ServeEngine

from decode_reference import sequential_decode


def _build(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_continuous_batching_matches_single_request_decode():
    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 28
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
        for n in rng.integers(3, 13, size=6)
    ]
    budgets = [int(b) for b in rng.integers(2, 9, size=6)]
    refs = [
        sequential_decode(model, params, p, b, MAX_LEN) for p, b in zip(prompts, budgets)
    ]
    with ServeEngine(
        model, params, max_slots=3, max_len=MAX_LEN, prefill_buckets=(8, 16)
    ) as engine:
        outs = engine.generate(prompts, budgets, timeout=300)
        stats = engine.stats()
    for ref, out in zip(refs, outs):
        assert list(map(int, out)) == ref  # token-for-token
    assert stats["completed"] == 6
    assert stats["kv"]["peak_live"] <= 3  # never exceeded the slot pool


def test_ssm_family_matches_single_request_decode():
    """Recurrent-state caches (no bucketing) through the same engine."""
    cfg, model, params = _build("mamba2-1.3b")
    MAX_LEN = 16
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(2)]
    refs = [sequential_decode(model, params, p, 4, MAX_LEN) for p in prompts]
    with ServeEngine(model, params, max_slots=2, max_len=MAX_LEN) as engine:
        outs = engine.generate(prompts, 4, timeout=300)
    for ref, out in zip(refs, outs):
        assert list(map(int, out)) == ref


def test_submit_async_matches_sync_submit():
    """§10 asyncio bridge: awaited generations equal the sync path and the
    event loop is never blocked by the pool."""
    import asyncio

    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 16
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(3)]
    with ServeEngine(model, params, max_slots=2, max_len=MAX_LEN) as engine:
        sync_outs = engine.generate(prompts, 4, timeout=300)

        async def main():
            return await asyncio.gather(
                *(engine.submit_async(p, 4) for p in prompts)
            )

        async_outs = asyncio.run(main())
    for s, a in zip(sync_outs, async_outs):
        assert list(map(int, a)) == list(map(int, s))


def test_capacity_eviction_truncates():
    cfg, model, params = _build("tinyllama-1.1b")
    with ServeEngine(model, params, max_slots=1, max_len=10) as engine:
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        h = engine.submit(prompt, max_new_tokens=50)  # cannot fit in 10
        out = h.result(300)
    assert h.truncated
    # feeds at positions 4..9 -> prefill token + 6 decode outputs
    assert len(out) == 7
    assert engine.stats()["truncations"] == 1
    assert engine.stats()["kv"]["evictions"] == 1


def test_cancel_waiting_request():
    cfg, model, params = _build("tinyllama-1.1b")
    engine = ServeEngine(model, params, max_slots=1, max_len=16, prefill_lookahead=0)
    try:
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        handles = [engine.submit(prompt, 6) for _ in range(4)]
        # the tail of the queue has not been admitted yet; cancel it
        cancelled = [h for h in reversed(handles) if h.cancel()]
        assert cancelled, "expected at least one still-waiting request"
        with pytest.raises(CancelledError):
            cancelled[0].result(5)
        # everyone else still completes
        done = [h for h in handles if h not in cancelled]
        for h in done:
            assert len(h.result(300)) == 6
        assert engine.stats()["completed"] == len(done)
    finally:
        engine.close(drain=False)


def test_rejects_unsupported_configs():
    cfg, model, params = _build("mamba2-1.3b")
    with pytest.raises(ValueError):  # SSM state would absorb pad tokens
        ServeEngine(model, params, prefill_buckets=(16,))
    cfg_e, model_e, _ = _build("whisper-medium")
    with pytest.raises(NotImplementedError):
        ServeEngine(model_e, None)
    for layout in ("flat", "contiguous"):  # paged is the one KV layout
        with pytest.raises(ValueError, match="'paged'"):
            ServeEngine(model, None, kv_layout=layout)


def test_validates_requests_and_shares_pool():
    cfg, model, params = _build("tinyllama-1.1b")
    with ThreadPool(2) as pool:
        engine = ServeEngine(model, params, max_slots=1, max_len=8, pool=pool)
        with pytest.raises(ValueError):
            engine.submit(np.zeros(0, np.int32), 2)  # empty prompt
        with pytest.raises(ValueError):
            engine.submit(np.zeros(4, np.int32), 0)  # no budget
        with pytest.raises(ValueError):
            engine.submit(np.zeros(8, np.int32), 2)  # prompt fills max_len
        out = engine.generate([np.arange(3, dtype=np.int32)], 2, timeout=300)
        assert len(out[0]) == 2
        engine.close()  # must not close the shared pool
        pool.run(lambda: None)  # still alive


def test_trace_path_emits_valid_chrome_trace(tmp_path):
    """A serve run with trace_path set writes trace-event JSON on close,
    with the prefill tasks and decode ticks visible as complete events."""
    import json

    cfg, model, params = _build("tinyllama-1.1b")
    trace_file = tmp_path / "serve_trace.json"
    with ServeEngine(
        model, params, max_slots=2, max_len=16, trace_path=str(trace_file)
    ) as engine:
        prompts = [np.arange(3, dtype=np.int32) % cfg.vocab_size for _ in range(2)]
        outs = engine.generate(prompts, 3, timeout=300)
        assert all(len(o) == 3 for o in outs)
    trace = json.loads(trace_file.read_text())
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    assert any(n.startswith("prefill:") for n in names)
    assert "decode-tick" in names


def test_replayed_tick_keeps_trace_and_stats_truthful(tmp_path):
    """The tick graph replays from its captured plan on every restart
    after the first (DESIGN.md §12); the Chrome trace must still show the
    decode ticks of the replayed passes as per-task complete events, and
    the engine/observer tick counts must agree."""
    import json

    cfg, model, params = _build("tinyllama-1.1b")
    trace_file = tmp_path / "serve_trace_replay.json"
    with ServeEngine(
        model, params, max_slots=2, max_len=16, trace_path=str(trace_file)
    ) as engine:
        prompt = np.arange(3, dtype=np.int32) % cfg.vocab_size
        # sequential generates with an explicit drain in between: results
        # resolve *inside* the tick body, so without the drain a fast next
        # submit can join the still-live run and no restart would happen —
        # the drain guarantees each later batch restarts the tick graph,
        # a §12 replay
        for _ in range(3):
            outs = engine.generate([prompt], 2, timeout=300)
            assert len(outs[0]) == 2
            engine.drain(60)
            engine.pool.wait_idle(30)
        # the pool quiesce also ensures the final tick's on_finish has
        # fired before the trace is written
        s = engine.stats()
    assert s["tick_replays"] >= 1  # at least one restart took the replay path
    trace = json.loads(trace_file.read_text())
    ticks = [e for e in trace["traceEvents"] if e["ph"] == "X" and e["name"] == "decode-tick"]
    # every tick is visible in the trace — live and replayed passes alike
    assert len(ticks) == s["ticks"]


def test_prefill_failure_readmits_waiting_requests():
    """Regression: a failed prefill frees admission capacity — requests
    still waiting behind it must be pumped, not stalled forever."""
    cfg, model, params = _build("tinyllama-1.1b")
    engine = ServeEngine(model, params, max_slots=1, max_len=16, prefill_lookahead=0)
    try:
        real_prefill = engine._prefill_jit
        POISON = np.full((3,), 1, np.int32)

        def flaky_prefill(p, batch, last_pos):
            if int(np.asarray(batch["tokens"]).sum()) == 3:  # the poison prompt
                raise RuntimeError("injected prefill failure")
            return real_prefill(p, batch, last_pos=last_pos)

        engine._prefill_jit = lambda p, batch, last_pos: flaky_prefill(p, batch, last_pos)
        bad = engine.submit(POISON, 4)
        good = engine.submit(np.arange(2, 6, dtype=np.int32) % cfg.vocab_size, 4)
        with pytest.raises(RuntimeError, match="injected prefill failure"):
            bad.result(60)
        assert len(good.result(120)) == 4  # admitted after the failure
        engine.drain(60)
    finally:
        engine.close(drain=False)


# ---------------------------------------------------------------------------
# §13: paged serving — streaming, deadlines, backpressure, preemption
# ---------------------------------------------------------------------------


def test_streaming_iterator_matches_result():
    """Tokens arrive per decode tick through the blocking iterator and match
    the final result; TTFT and per-token marks are recorded."""
    cfg, model, params = _build("tinyllama-1.1b")
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    with ServeEngine(model, params, max_slots=2, max_len=16) as engine:
        h = engine.submit(prompt, 6)
        streamed = list(h)  # blocks per token, ends at completion
        assert streamed == list(map(int, h.result(0)))
        assert len(streamed) == 6
        assert h.ttft is not None and h.ttft >= 0.0
        assert len(h.token_times) == 6
        assert h.token_times == sorted(h.token_times)
        assert h.first_token_t == h.token_times[0]
        # a second iteration replays the now-complete stream
        assert list(h) == streamed


def test_streaming_async_for():
    """§10 asyncio bridge: ``async for`` delivers the same tokens without
    blocking the event loop."""
    import asyncio

    cfg, model, params = _build("tinyllama-1.1b")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=4).astype(np.int32) for _ in range(2)]
    with ServeEngine(model, params, max_slots=2, max_len=16) as engine:
        sync_outs = engine.generate(prompts, 5, timeout=300)

        async def consume(p):
            h = engine.submit(p, 5)
            return [tok async for tok in h]

        async def main():
            return await asyncio.gather(*(consume(p) for p in prompts))

        async_outs = asyncio.run(main())
    for s, a in zip(sync_outs, async_outs):
        assert a == list(map(int, s))


def test_deadline_miss_fails_fast():
    """A request whose deadline lapses before its prefill starts resolves
    with DeadlineExceeded; deadline-free traffic behind it still completes."""
    import threading as th

    cfg, model, params = _build("tinyllama-1.1b")
    with ThreadPool(1, name="serve-dl") as pool:
        engine = ServeEngine(model, params, max_slots=1, max_len=16, pool=pool)
        gate = th.Event()
        pool.submit(lambda: gate.wait(30))  # stall the only worker
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        doomed = engine.submit(prompt, 4, deadline=0.05)
        ok = engine.submit(prompt, 4)
        time.sleep(0.3)  # let the deadline lapse while the worker is held
        gate.set()
        from repro.serve import DeadlineExceeded

        with pytest.raises(DeadlineExceeded):
            doomed.result(60)
        assert len(ok.result(120)) == 4
        assert engine.stats()["deadline_misses"] == 1
        engine.drain(60)
        engine.close(drain=False)


def test_deadline_bands_promote_with_urgency():
    """§13 -> §9 mapping: waiting prefills are graded into priority bands by
    remaining deadline headroom; resumes are always urgent."""
    from repro.serve import PREFILL_PRIORITY, PREFILL_SOON, PREFILL_URGENT
    from repro.serve.engine import GenRequest, _Pending

    cfg, model, params = _build("tinyllama-1.1b")
    with ServeEngine(model, params, max_slots=1, max_len=8) as engine:
        req = GenRequest(np.arange(3, dtype=np.int32), 2, deadline=1.0)
        now = 100.0
        fresh = _Pending(object.__new__(RequestHandle), req, now + 1.0, 0)
        assert engine._band(fresh, now) == PREFILL_PRIORITY
        assert engine._band(fresh, now + 0.6) == PREFILL_SOON
        assert engine._band(fresh, now + 0.8) == PREFILL_URGENT
        nodeadline = _Pending(object.__new__(RequestHandle), GenRequest(req.prompt, 2), None, 1)
        assert engine._band(nodeadline, now) == PREFILL_PRIORITY
        resumed = _Pending(object.__new__(RequestHandle), req, now + 1.0, 2)
        resumed.tokens = [7]
        assert engine._band(resumed, now) == PREFILL_URGENT


def test_bounded_admit_queue_rejects_with_queue_full():
    """Backpressure: beyond max_waiting queued requests, submit raises
    QueueFull instead of growing the queue without bound."""
    import threading as th

    from repro.serve import QueueFull

    cfg, model, params = _build("tinyllama-1.1b")
    with ThreadPool(1, name="serve-bp") as pool:
        engine = ServeEngine(
            model, params, max_slots=1, max_len=16, pool=pool,
            prefill_lookahead=0, max_waiting=2,
        )
        gate = th.Event()
        pool.submit(lambda: gate.wait(30))
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        handles = [engine.submit(prompt, 3) for _ in range(3)]  # 1 inflight + 2 waiting
        with pytest.raises(QueueFull):
            engine.submit(prompt, 3)
        gate.set()
        for h in handles:
            assert len(h.result(120)) == 3
        st = engine.stats()
        assert st["rejected"] == 1
        assert st["completed"] == 3
        engine.close(drain=False)


def test_page_pressure_preempts_and_resumes_bit_identical():
    """The §13 tentpole invariant: with the page pool oversubscribed, the
    engine preempts the youngest resident to the admit queue and resumes it
    by re-prefill — and every request's tokens still equal the sequential
    single-request reference exactly. Preemption moves work, never drops it."""
    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 24
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(3)]
    budgets = [12, 11, 10]
    refs = [
        sequential_decode(model, params, p, b, MAX_LEN) for p, b in zip(prompts, budgets)
    ]
    # 2 residents x 6 pages/seq would need 12 pages; grant only 6 (the
    # one-full-sequence floor) so concurrent growth must hit page pressure
    # whichever order the prefills land in
    with ServeEngine(
        model, params, max_slots=2, max_len=MAX_LEN, page_size=4, num_pages=6
    ) as engine:
        outs = engine.generate(prompts, budgets, timeout=300)
        stats = engine.stats()
    for ref, out in zip(refs, outs):
        assert list(map(int, out)) == ref  # token-for-token across preemption
    assert stats["preemptions"] >= 1
    assert stats["completed"] == 3
    assert stats["kv"]["pages_live"] == 0  # everything returned to the pool


def test_close_drain_never_strands_racing_submits():
    """Regression (§13 satellite): ``close(drain=True)`` used to mark the
    engine closed only *after* draining, so a submit landing in that window
    was admitted onto a pool about to be torn down — its prefill was
    abandoned and the handle never resolved. Now close rejects first, then
    drains: every accepted handle must resolve."""
    cfg, model, params = _build("tinyllama-1.1b")
    engine = ServeEngine(model, params, max_slots=1, max_len=24)
    prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
    first = engine.submit(prompt, 16)  # a long decode keeps the tick busy
    accepted, stop = [first], False

    def spam():
        while not stop:
            try:
                accepted.append(engine.submit(prompt, 2))
            except RuntimeError:
                return  # engine closed: the race window is shut
            time.sleep(0.002)

    t = threading.Thread(target=spam)
    t.start()
    time.sleep(0.05)
    engine.close(drain=True)  # races the spammer
    stop = True
    t.join(60)
    with pytest.raises(RuntimeError):
        engine.submit(prompt, 2)  # closed engines reject
    for h in accepted:  # nobody stranded: every accepted handle resolved
        assert len(h.result(10)) >= 1


def test_close_drain_waits_for_queued_low_priority_prefill():
    """The documented race shape: a low-priority prefill queued behind the
    decode tick on a single worker. close(drain=True) must complete it, not
    abandon it."""
    cfg, model, params = _build("tinyllama-1.1b")
    with ThreadPool(1, name="serve-close") as pool:
        engine = ServeEngine(model, params, max_slots=1, max_len=24, pool=pool)
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        h1 = engine.submit(prompt, 12)  # decode tick occupies the worker
        h2 = engine.submit(prompt, 3)  # prefill waits at PREFILL_PRIORITY
        engine.close(drain=True)
        assert len(h1.result(5)) == 12
        assert len(h2.result(5)) == 3


def test_async_cancellation_before_first_token_frees_everything():
    """§13 satellite: cancelling the awaitable before the first token
    releases the request (no pages were or will be held) and the handle
    resolves with CancelledError — never with tokens."""
    import asyncio
    import threading as th

    cfg, model, params = _build("tinyllama-1.1b")
    with ThreadPool(1, name="serve-cx") as pool:
        engine = ServeEngine(model, params, max_slots=1, max_len=16, pool=pool)
        gate = th.Event()
        pool.submit(lambda: gate.wait(30))  # the prefill can never start
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size

        async def main():
            task = asyncio.ensure_future(engine.submit_async(prompt, 4))
            await asyncio.sleep(0.05)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task

        asyncio.run(main())
        gate.set()
        engine.drain(60)
        st = engine.stats()
        assert st["completed"] == 0 and st["tokens_out"] == 0
        kvs = st["kv"]
        assert kvs["live"] == 0 and kvs["pages_live"] == 0
        assert kvs["page_allocs"] == 0  # never even touched the page pool
        engine.close(drain=False)


def test_cancel_mid_prefill_never_joins():
    """Cancelling while the prefill task is queued/running drops its result
    instead of joining the batch; the future resolves with CancelledError."""
    import threading as th

    cfg, model, params = _build("tinyllama-1.1b")
    with ThreadPool(1, name="serve-cx2") as pool:
        engine = ServeEngine(model, params, max_slots=1, max_len=16, pool=pool)
        gate = th.Event()
        pool.submit(lambda: gate.wait(30))
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        h = engine.submit(prompt, 4)  # prefill task queued behind the gate
        assert h.cancel()
        gate.set()
        with pytest.raises(CancelledError):
            h.result(30)
        engine.drain(60)
        assert engine.stats()["kv"]["pages_live"] == 0
        # the stream surface agrees: iteration raises, yields nothing
        with pytest.raises(CancelledError):
            list(h)
        engine.close(drain=False)


def test_prefill_circuit_breaker_trips_then_recovers():
    """§14 degradation: sustained prefill failure trips a circuit breaker —
    submissions during the cooldown fail fast with QueueFull instead of
    queueing doomed work — and a post-cooldown success re-closes it."""
    from repro.serve import QueueFull

    cfg, model, params = _build("tinyllama-1.1b")
    engine = ServeEngine(
        model, params, max_slots=1, max_len=16,
        prefill_retries=0, breaker_threshold=2, breaker_cooldown=0.5,
    )
    try:
        real_prefill = engine._prefill_jit
        engine._prefill_jit = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("prefill is down")
        )
        prompt = np.arange(4, dtype=np.int32) % cfg.vocab_size
        for _ in range(2):  # two consecutive exhausted failures -> trip
            h = engine.submit(prompt, 4)
            with pytest.raises(RuntimeError, match="prefill is down"):
                h.result(60)
        with pytest.raises(QueueFull, match="circuit breaker open"):
            engine.submit(prompt, 4)
        s = engine.stats()
        assert s["breaker_trips"] == 1
        assert s["rejected"] >= 1
        # heal the backend, wait out the cooldown: half-open admits again
        engine._prefill_jit = real_prefill
        time.sleep(0.6)
        good = engine.submit(prompt, 4)
        assert len(good.result(120)) == 4
        assert engine.stats()["breaker_trips"] == 1  # did not re-trip
    finally:
        engine.close(drain=False)


def test_transient_prefill_failures_leave_outputs_bit_identical():
    """§14 acceptance: with fault injection upstream of prefill, retried
    requests complete and their token streams are bit-identical to the
    sequential no-fault reference."""
    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 16
    rng = np.random.default_rng(14)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(3)]
    refs = [sequential_decode(model, params, p, 4, MAX_LEN) for p in prompts]
    with ServeEngine(model, params, max_slots=2, max_len=MAX_LEN) as engine:
        real_prefill = engine._prefill_jit
        calls = [0]
        lock = threading.Lock()

        def flaky_prefill(p, batch, last_pos):
            with lock:
                calls[0] += 1
                fail = calls[0] <= 2  # first two attempts die in-flight
            if fail:
                raise RuntimeError("transient prefill fault")
            return real_prefill(p, batch, last_pos=last_pos)

        engine._prefill_jit = lambda p, batch, last_pos: flaky_prefill(p, batch, last_pos)
        outs = engine.generate(prompts, 4, timeout=300)
        s = engine.stats()
    for ref, out in zip(refs, outs):
        assert list(map(int, out)) == ref  # bit-identical despite retries
    assert s["pool"]["retries"] >= 2  # the recovery went through §14 retry
    assert s["breaker_trips"] == 0  # transient, never sustained


# ---------------------------------------------------------------------------
# The first token goes out when its prefill completes; the join only places
# the sequence in a slot
# ---------------------------------------------------------------------------


def _held_resident(engine, prompt, budget):
    """Submit a resident and hold every decode tick at its dispatch until the
    returned event is set: the resident has joined and streamed its first
    token, and the engine's slot stays taken mid-decode."""
    entered, release = threading.Event(), threading.Event()
    real_tick = engine._tick_jit

    def held_tick(*args):
        entered.set()
        release.wait(120)
        return real_tick(*args)

    engine._tick_jit = held_tick
    h = engine.submit(prompt, budget)
    assert entered.wait(300), "the resident's first tick never dispatched"
    return h, release


def test_first_token_streams_while_waiting_to_join():
    """A request prefilled behind a resident of the only slot has its first
    token while the resident is still decoding; it joins only when the
    slot frees, and both answers equal the sequential reference."""
    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 24
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(2)]
    budgets = [6, 4]
    refs = [sequential_decode(model, params, p, b, MAX_LEN) for p, b in zip(prompts, budgets)]
    engine = ServeEngine(model, params, max_slots=1, max_len=MAX_LEN)
    try:
        resident, release = _held_resident(engine, prompts[0], budgets[0])
        waiting = engine.submit(prompts[1], budgets[1])
        stream = waiting.iter_tokens(timeout=300)
        assert next(stream) == refs[1][0]
        assert waiting.prefill_done_t <= waiting.first_token_t
        # the resident holds the slot and has only its own first token
        assert not resident.done() and len(resident.token_times) == 1
        assert len(engine._joinq) == 1 and engine.stats()["kv"]["live"] == 1
        release.set()
        assert [refs[1][0]] + list(stream) == refs[1]
        assert list(map(int, waiting.result(0))) == refs[1]
        assert list(map(int, resident.result(300))) == refs[0]
        assert resident.token_times[-1] < waiting.token_times[1]  # joined after
    finally:
        release.set()
        engine.close(drain=False)


def test_budget_of_one_completes_at_prefill_without_a_slot():
    """With the only slot held, a one-token request completes at its
    prefill: no slot, no page and no cache write."""
    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 24
    rng = np.random.default_rng(22)
    prompts = [rng.integers(0, cfg.vocab_size, size=6).astype(np.int32) for _ in range(2)]
    ref = sequential_decode(model, params, prompts[1], 1, MAX_LEN)
    engine = ServeEngine(model, params, max_slots=1, max_len=MAX_LEN)
    try:
        resident, release = _held_resident(engine, prompts[0], 5)
        writes = []
        real_write = engine.kv.write
        engine.kv.write = lambda *a: (writes.append(a[0]), real_write(*a))[1]
        before = engine.stats()["kv"]
        one = engine.submit(prompts[1], 1)
        assert list(map(int, one.result(300))) == ref
        assert list(one) == ref
        after = engine.stats()
        assert not resident.done()
        assert after["completed"] == 1 and after["first_at_prefill"] == 2
        assert after["kv"]["page_allocs"] == before["page_allocs"]
        assert after["kv"]["allocs"] == before["allocs"]
        assert writes == []
        release.set()
        assert len(resident.result(300)) == 5
        engine.drain(60)
        assert engine.stats()["kv"]["pages_live"] == 0
    finally:
        release.set()
        engine.close(drain=False)


def test_cancel_after_first_token_before_join():
    """A request whose first token streamed but which has not joined can
    still be cancelled: its stream yields that token and then raises, its
    cache never takes a page, and the resident's answer is unchanged."""
    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 24
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(2)]
    ref = sequential_decode(model, params, prompts[0], 6, MAX_LEN)
    first = sequential_decode(model, params, prompts[1], 1, MAX_LEN)
    engine = ServeEngine(model, params, max_slots=1, max_len=MAX_LEN)
    try:
        resident, release = _held_resident(engine, prompts[0], 6)
        h = engine.submit(prompts[1], 5)
        assert [next(h.iter_tokens(timeout=300))] == first
        assert h.cancel()
        got = []
        with pytest.raises(CancelledError):
            for tok in h:
                got.append(tok)
        assert got == first
        assert len(engine._joinq) == 0
        release.set()
        assert list(map(int, resident.result(300))) == ref
        engine.drain(60)
        st = engine.stats()
        assert st["kv"]["pages_live"] == 0 and st["completed"] == 1
        assert not h.cancel()  # already resolved
    finally:
        release.set()
        engine.close(drain=False)


def test_streams_under_preemption_hold_each_token_once():
    """Under page pressure a preempted request resumes by re-prefill, which
    streams nothing: every handle's stream equals its result and the
    reference, with no duplicated first token."""
    cfg, model, params = _build("tinyllama-1.1b")
    MAX_LEN = 24
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32) for _ in range(3)]
    budgets = [12, 11, 10]
    refs = [
        sequential_decode(model, params, p, b, MAX_LEN) for p, b in zip(prompts, budgets)
    ]
    with ServeEngine(
        model, params, max_slots=2, max_len=MAX_LEN, page_size=4, num_pages=6
    ) as engine:
        handles = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
        streams = [list(h.iter_tokens(timeout=300)) for h in handles]
        engine.drain(60)
        stats = engine.stats()
    assert stats["preemptions"] >= 1
    for h, s, ref in zip(handles, streams, refs):
        assert s == list(map(int, h.result(0))) == ref
        assert len(h.token_times) == len(ref)
    assert stats["first_at_prefill"] == 3  # resumes push nothing
    assert stats["tokens_out"] == sum(budgets)


def test_first_at_prefill_counts_fresh_prefills():
    """``stats()["first_at_prefill"]`` counts one first token per fresh
    request whose prefill completed: not one cancelled mid-prefill."""
    cfg, model, params = _build("tinyllama-1.1b")
    rng = np.random.default_rng(24)
    prompts = [rng.integers(0, cfg.vocab_size, size=4).astype(np.int32) for _ in range(4)]
    budgets = [1, 3, 2, 5]
    with ThreadPool(1, name="serve-fp") as pool:
        engine = ServeEngine(model, params, max_slots=2, max_len=16, pool=pool)
        gate = threading.Event()
        pool.submit(lambda: gate.wait(30))
        dropped = engine.submit(prompts[0], 4)  # prefill queued behind the gate
        assert dropped.cancel()
        gate.set()
        outs = engine.generate(prompts, budgets, timeout=300)
        engine.drain(60)
        st = engine.stats()
        engine.close()
    assert [len(o) for o in outs] == budgets
    assert st["first_at_prefill"] == len(prompts)
    assert st["tokens_out"] == sum(budgets)
    assert st["completed"] == len(prompts)


def test_streams_stay_ordered_under_thread_churn():
    """Prefills push first tokens from pool workers while ticks push the
    rest: with more workers than slots and a short switch interval, every
    stream still equals its result, one token per budget."""
    import sys

    cfg, model, params = _build("tinyllama-1.1b")
    rng = np.random.default_rng(25)
    prompts = [rng.integers(0, cfg.vocab_size, size=4).astype(np.int32) for _ in range(12)]
    budgets = [int(b) for b in rng.integers(1, 5, size=12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPool(4, name="serve-churn") as pool:
            with ServeEngine(model, params, max_slots=2, max_len=16, pool=pool) as engine:
                handles = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
                streams = [list(h.iter_tokens(timeout=300)) for h in handles]
                engine.drain(60)
                st = engine.stats()
    finally:
        sys.setswitchinterval(interval)
    for h, s, b in zip(handles, streams, budgets):
        assert s == list(map(int, h.result(0))) and len(s) == b
    assert st["first_at_prefill"] == len(prompts)
    assert st["tokens_out"] == sum(budgets)
