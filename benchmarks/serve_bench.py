"""Serving benchmark: overload Poisson trace through three servers.

Replays one arrival trace (Poisson interarrivals, per-request token budgets,
arrival rate deliberately beyond the service rate — an *overload* trace)
through three servers over the same model and params:

* **static**  — the classic batch server (what examples/serve_lm.py used to
  be): wait until ``batch`` requests have arrived, prefill them together,
  decode the whole batch in lockstep until the *longest* member finishes,
  repeat. Slots of finished sequences burn compute; late arrivals wait for
  the next batch to form.
* **continuous-flat** — ``repro.serve.ServeEngine`` with the whole-slot
  ``SlotKVCache`` (one ``max_len`` reservation per sequence, unbounded
  admit queue): iteration-level batching on the work-stealing pool.
* **continuous-paged** — the same engine with the §13 paged KV pool plus
  admission control: a bounded admit queue (``max_waiting = 2×slots``,
  ``QueueFull`` backpressure — the client retries, modelling a closed
  loop) and per-request deadlines grading the §9 prefill bands.

Every continuous request is **streamed**, so the report carries end-to-end
latency percentiles: TTFT (submit → first token) and inter-token latency
(gaps between ``RequestHandle.token_times``), p50/p90/p99 in ms. Static
has no per-request delivery times — it reports wall/throughput only.

All servers count only each request's own budgeted tokens, so tokens/s
isolates scheduling quality. A verification pass checks both engines'
outputs for every request are bit-identical (token-for-token) to
sequential single-request decode; ``max_len`` is rounded up to a page
multiple so all four programs attend over equally-sized caches (in bf16,
reduction tiling over differently-padded widths can flip greedy argmax at
a near-tie, which is numerics, not scheduling).

    PYTHONPATH=src python benchmarks/serve_bench.py [--arch tinyllama-1.1b]
        [--quick] [--requests 32] [--slots 8]
        [--out benchmarks/artifacts/BENCH_serve.json]

``--quick`` presets CI-sized dimensions (the committed gate baseline
``benchmarks/BENCH_serve_quick.json`` is a ``--quick`` run; the serve gate
in ``check_graph_regression.py`` compares quick-vs-quick). Runs on CPU
with the arch's reduced config; emits a JSON report.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models.lm import extend_caches
from repro.serve import QueueFull, ServeEngine


def make_trace(rng, n, prompt_len, min_new, max_new, mean_gap_s):
    """(prompts, budgets, arrival_times) — Poisson arrivals, varied budgets."""
    prompts = [rng.integers(0, 2**31 - 1, size=prompt_len) for _ in range(n)]
    budgets = [int(rng.integers(min_new, max_new + 1)) for _ in range(n)]
    gaps = rng.exponential(mean_gap_s, size=n)
    arrivals = np.cumsum(gaps)
    return prompts, budgets, arrivals


def clip_vocab(prompts, vocab):
    return [np.asarray(p % vocab, np.int32) for p in prompts]


def _pcts(xs_s: list) -> dict:
    """p50/p90/p99/max of a list of seconds, reported in ms."""
    a = np.asarray(xs_s, np.float64) * 1e3
    return {
        "p50": round(float(np.percentile(a, 50)), 2),
        "p90": round(float(np.percentile(a, 90)), 2),
        "p99": round(float(np.percentile(a, 99)), 2),
        "max": round(float(a.max()), 2),
    }


def latency_summary(handles) -> dict:
    """TTFT + inter-token latency percentiles from streamed handles."""
    ttfts = [h.ttft for h in handles]
    assert all(t is not None for t in ttfts), "a request never delivered a token"
    itls = []
    for h in handles:
        ts = h.token_times
        itls.extend(b - a for a, b in zip(ts, ts[1:]))
    out = {"ttft_ms": _pcts(ttfts)}
    if itls:
        out["itl_ms"] = _pcts(itls)
    return out


# ---------------------------------------------------------------------------
# static-batch baseline
# ---------------------------------------------------------------------------


class StaticBatchServer:
    """Batched prefill + lockstep decode until the longest member finishes."""

    def __init__(self, model, params, batch, prompt_len, max_new):
        self.model, self.params, self.batch = model, params, batch
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step, donate_argnums=(2,))
        self.prompt_len, self.max_new = prompt_len, max_new

    def run_group(self, prompts, budgets):
        """Decode one full batch; returns per-request generated ids."""
        B = len(prompts)
        toks = jnp.asarray(np.stack(prompts))  # (B, S) — equal lengths
        logits, caches = self._prefill(self.params, {"tokens": toks})
        caches = extend_caches(caches, self.max_new)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        outs = [[int(tok[i, 0])] for i in range(B)]
        ticks = max(budgets)
        for i in range(ticks - 1):  # static: everyone decodes to the longest
            logits, caches = self._decode(
                self.params, tok, caches, jnp.asarray(self.prompt_len + i, jnp.int32)
            )
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            for b in range(B):
                if len(outs[b]) < budgets[b]:  # budget reached -> discard
                    outs[b].append(int(tok[b, 0]))
        jax.block_until_ready(tok)
        return outs

    def serve(self, prompts, budgets, arrivals, t0):
        """Replay the trace: form full batches in arrival order."""
        outs = [None] * len(prompts)
        for g0 in range(0, len(prompts), self.batch):
            idx = list(range(g0, min(g0 + self.batch, len(prompts))))
            # batch formation: wait for the last member to arrive
            wait = t0 + arrivals[idx[-1]] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            group = self.run_group([prompts[i] for i in idx], [budgets[i] for i in idx])
            for i, o in zip(idx, group):
                outs[i] = o
        return outs


# ---------------------------------------------------------------------------
# sequential single-request reference (bit-identity oracle)
# ---------------------------------------------------------------------------


def sequential_reference(model, params, prompts, budgets, width=None):
    """Decode each request alone, one token at a time.

    ``width``: KV capacity to provision (default: exactly prompt+budget).
    The bit-identity check passes the engine's ``max_len`` so both programs
    attend over equally-sized (identically masked) caches — in bf16, the
    reduction tiling over differently-padded cache widths can flip greedy
    argmax at a near-tie, which is numerics, not scheduling.
    """
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))
    outs = []
    for prompt, budget in zip(prompts, budgets):
        logits, caches = prefill(params, {"tokens": jnp.asarray(prompt[None, :])})
        extra = (width - int(prompt.size)) if width is not None else budget
        caches = extend_caches(caches, extra)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out = [int(tok[0, 0])]
        for i in range(budget - 1):
            logits, caches = decode(
                params, tok, caches, jnp.asarray(prompt.size + i, jnp.int32)
            )
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            out.append(int(tok[0, 0]))
        outs.append(out)
    return outs


# ---------------------------------------------------------------------------
# continuous engine client
# ---------------------------------------------------------------------------


def serve_continuous(engine, prompts, budgets, arrivals, t0, deadline=None):
    """Replay the trace; returns (handles, outputs, admit_retries).

    ``QueueFull`` backpressure is handled as a closed loop: the feeder
    retries the rejected submit after a short sleep — work is delayed at
    the client, never dropped.
    """
    handles = [None] * len(prompts)
    retries = 0

    def feeder():
        nonlocal retries
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            wait = t0 + arrivals[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            while True:
                try:
                    handles[i] = engine.submit(p, n, deadline=deadline)
                    break
                except QueueFull:
                    retries += 1
                    time.sleep(0.002)

    th = threading.Thread(target=feeder)
    th.start()
    th.join()
    outs = [list(map(int, h.result(600))) for h in handles]
    return handles, outs, retries


def run_engine(model, params, args, layout, trace, max_len, buckets):
    """One timed replay through a fresh engine; returns (row, outputs)."""
    prompts, budgets, arrivals = trace
    kw = {}
    deadline = None
    if layout == "paged":
        kw.update(page_size=args.page_size, max_waiting=2 * args.slots)
        deadline = args.deadline_s
    engine = ServeEngine(
        model,
        params,
        max_slots=args.slots,
        max_len=max_len,
        kv_layout=layout,
        prefill_buckets=buckets,
        **kw,
    )
    engine.generate(prompts[: args.slots], 2)  # warmup compiles
    pre = engine.stats()
    t0 = time.perf_counter()
    handles, outs, retries = serve_continuous(
        engine, prompts, budgets, arrivals, t0, deadline=deadline
    )
    engine.drain(600)
    wall = time.perf_counter() - t0
    stats = engine.stats()
    engine.close()

    assert all(len(o) == b for o, b in zip(outs, budgets))
    total_tokens = sum(budgets)
    ticks = stats["ticks"] - pre["ticks"]
    row = {
        "server": f"continuous-{layout}",
        "wall_s": round(wall, 4),
        "tokens_per_s": round(total_tokens / wall, 2),
        "ticks": ticks,
        # occupancy over the timed replay only (warmup ticks excluded)
        "mean_occupancy": round(
            (
                stats["mean_occupancy"] * stats["ticks"]
                - pre["mean_occupancy"] * pre["ticks"]
            )
            / max(ticks, 1),
            3,
        ),
        "completed": stats["completed"] - pre["completed"],
        "preemptions": stats["preemptions"],
        "rejected": stats["rejected"],
        "deadline_misses": stats["deadline_misses"],
        "admit_retries": retries,
        "pool_steals": stats["pool"]["steals"],
        "kv": {
            "page_size": stats["kv"]["page_size"],
            "pages_total": stats["kv"]["pages_total"],
            # flat slots are one page each, so slot peak == page peak there
            "peak_pages_live": stats["kv"].get("peak_pages_live", stats["kv"]["peak_live"]),
            "fragmentation": stats["kv"]["fragmentation"],
        },
        **latency_summary(handles),
    }
    return row, outs


QUICK = dict(requests=24, slots=4, prompt_len=16, min_new=8, max_new=16, mean_gap_ms=2.0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_NAMES)
    ap.add_argument("--quick", action="store_true", help="CI-sized preset (see QUICK)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--min-new", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--mean-gap-ms", type=float, default=3.0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument(
        "--deadline-s",
        type=float,
        default=600.0,
        help="per-request TTFT deadline on the paged server (generous by "
        "default: exercises the §9 deadline bands without ever shedding "
        "work, so throughput stays comparable across servers)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.quick:
        for k, v in QUICK.items():
            setattr(args, k, v)
    enable_compile_cache()

    cfg = get_reduced(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(args.seed)
    prompts, budgets, arrivals = make_trace(
        rng, args.requests, args.prompt_len, args.min_new, args.max_new,
        args.mean_gap_ms / 1e3,
    )
    prompts = clip_vocab(prompts, cfg.vocab_size)
    trace = (prompts, budgets, arrivals)
    total_tokens = sum(budgets)
    # round up to a page multiple so flat slots, paged gathers and the
    # sequential reference all attend over the same cache width (bit-identity)
    need = args.prompt_len + args.max_new + 1
    max_len = -(-need // args.page_size) * args.page_size
    buckets = (args.prompt_len,) if ServeEngine.supports_prefill_buckets(cfg) else None

    # -- static baseline (warmup compiles, then timed replay) ---------------
    static = StaticBatchServer(model, params, args.slots, args.prompt_len, args.max_new)
    static.run_group(prompts[: args.slots], [2] * args.slots)  # warmup
    t0 = time.perf_counter()
    static_outs = static.serve(prompts, budgets, arrivals, t0)
    static_wall = time.perf_counter() - t0
    assert all(len(o) == b for o, b in zip(static_outs, budgets))

    # -- continuous engines (same warmup treatment, same trace) -------------
    flat_row, flat_outs = run_engine(model, params, args, "flat", trace, max_len, buckets)
    paged_row, paged_outs = run_engine(
        model, params, args, "paged", trace, max_len, buckets
    )

    identical = None
    if not args.no_verify:
        refs = sequential_reference(model, params, prompts, budgets, width=max_len)
        identical = all(r == c for r, c in zip(refs, flat_outs)) and all(
            r == c for r, c in zip(refs, paged_outs)
        )

    report = {
        "meta": {
            "arch": cfg.name,
            "quick": args.quick,
            "requests": args.requests,
            "slots": args.slots,
            "prompt_len": args.prompt_len,
            "max_len": max_len,
            "page_size": args.page_size,
            "budgets": {
                "min": args.min_new,
                "max": args.max_new,
                "total_tokens": total_tokens,
            },
            "mean_gap_ms": args.mean_gap_ms,
            "seed": args.seed,
        },
        "rows": [
            {
                "server": "static",
                "wall_s": round(static_wall, 4),
                "tokens_per_s": round(total_tokens / static_wall, 2),
            },
            flat_row,
            paged_row,
        ],
        "speedup_vs_static": round(static_wall / paged_row["wall_s"], 3),
        "paged_over_flat_tokens_per_s": round(
            paged_row["tokens_per_s"] / flat_row["tokens_per_s"], 3
        ),
        "outputs_match_sequential_decode": identical,
    }
    print(json.dumps(report, indent=2))
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2))
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
