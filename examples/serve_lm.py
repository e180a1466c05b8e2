"""Serving example: a thin client of the continuous-batching engine.

Submits a handful of prompts with different lengths and token budgets to
``repro.serve.ServeEngine`` — prefill runs as low-priority tasks on the
work-stealing pool, decode ticks at high priority, and sequences join/retire
between ticks (iteration-level batching). KV storage is the §13 paged pool,
the admit queue is bounded (``QueueFull`` backpressure), every request
carries a TTFT deadline, and the first request is **streamed** token by
token while the rest resolve through their futures.

    PYTHONPATH=src python examples/serve_lm.py [--arch mamba2-1.3b] [--new 16]

Uses the arch's REDUCED config so it runs in seconds on CPU; pass
--full to build the real config (needs memory/patience).
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import QueueFull, ServeEngine

# the engine serves text-prompt families; encdec/vlm need non-token inputs
SERVABLE = tuple(
    n for n in ARCH_NAMES
    if not get_config(n).is_encdec and get_config(n).family != "vlm"
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=SERVABLE)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--deadline", type=float, default=60.0,
                    help="per-request TTFT deadline (seconds)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    model = build_model(cfg)
    print(f"arch={cfg.name} family={cfg.family}")
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(
            0, cfg.vocab_size, size=int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        )
        for _ in range(args.requests)
    ]
    budgets = [int(rng.integers(max(2, args.new // 2), args.new + 1)) for _ in range(args.requests)]

    max_len = args.prompt_len + args.new + 1
    buckets = None
    if ServeEngine.supports_prefill_buckets(cfg):
        buckets = (args.prompt_len // 2, args.prompt_len)

    t0 = time.perf_counter()
    with ServeEngine(
        model, params, max_slots=args.slots, max_len=max_len,
        prefill_buckets=buckets,
        max_waiting=4 * args.slots,  # bounded admit queue: QueueFull past this
    ) as engine:
        handles = []
        for p, n in zip(prompts, budgets):
            while True:
                try:
                    handles.append(engine.submit(p, n, deadline=args.deadline))
                    break
                except QueueFull:  # backpressure: shed upstream or retry
                    time.sleep(0.002)

        # stream the first request token-by-token as its decode ticks land;
        # `async for tok in handle` is the asyncio equivalent
        streamed = []
        for tok in handles[0]:
            streamed.append(int(tok))
        print(f"request 0 streamed {len(streamed)} tokens, "
              f"TTFT {handles[0].ttft * 1e3:.1f} ms")

        outs = [h.result(600) for h in handles]
        wall = time.perf_counter() - t0
        stats = engine.stats()

    assert streamed == list(map(int, outs[0]))  # stream and future agree
    total = sum(len(o) for o in outs)
    ttfts = sorted(h.ttft for h in handles)
    print(f"{len(outs)} requests, {total} tokens in {wall * 1e3:.1f} ms "
          f"(incl. compile) -> {total / max(wall, 1e-9):,.0f} tok/s")
    print(f"TTFT p50={ttfts[len(ttfts) // 2] * 1e3:.1f} ms "
          f"max={ttfts[-1] * 1e3:.1f} ms "
          f"deadline_misses={stats['deadline_misses']} rejected={stats['rejected']}")
    kv = stats["kv"]
    print(f"ticks={stats['ticks']} mean_occupancy={stats['mean_occupancy']:.2f} "
          f"preemptions={stats['preemptions']} "
          f"pages={kv['pages_live']}/{kv['pages_total']} live "
          f"(peak {kv.get('peak_pages_live', kv['peak_live'])}) "
          f"pool_steals={stats['pool']['steals']}")
    print("generated token ids (first request):", list(map(int, outs[0])))


if __name__ == "__main__":
    main()
