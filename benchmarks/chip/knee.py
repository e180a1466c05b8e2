"""Find a serve cell's knee once, by a sweep on the chip.

    python benchmarks/chip/knee.py --workload phi4mini.chat --seed 1 --seconds 20 --rates 3,4,5,6

One process builds the cell's engine, then offers the cell's mix open loop
at each rate for ``--seconds`` and waits for it to drain before the next.
For each rate it prints one JSON line: tokens served per second, TTFT and
inter-token percentiles, and the prefill backlog (requests due but not yet
given their first token) at each quarter of the window. The knee is the
highest rate at which the backlog does not grow across the window; the cell
runs at four fifths of it. The cell's runs never call this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def backlog(records, t) -> int:
    return sum(1 for r in records if r.due <= t and (r.first is None or r.first > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args(argv)

    import numpy as np

    import harness
    import traffic as traffic_gen

    cell = harness.load_cell(args.workload)
    cell.device = harness.device_info(cell.chips)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell.seed, cell.t_start, cell.trace = args.seed, T_START, False
    serve = cell.driver()
    engine = serve.build(cell)
    print(f"setup_s {time.perf_counter() - T_START:.3f}", flush=True)
    vocab = cell.published["vocab_size"]
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        reqs = traffic_gen.make_requests(mix, cell.seed, args.seconds, vocab)
        records, marks = serve.window(engine, reqs, args.seconds)
        t0, t1 = marks["t0"], marks["t_end"]
        ttft = [(r.first if r.first is not None and r.first <= t1 else t1) - r.due for r in records]
        gaps = [b - a for r in records for a, b in zip(r.times, r.times[1:])]
        quarters = [backlog(records, t0 + q * (t1 - t0)) for q in (0.25, 0.5, 0.75, 1.0)]
        _outputs, failed = serve.collect(records, reqs, t1)
        print(json.dumps({
            "rate_per_s": rate,
            "requests": len(reqs),
            "failed": failed,
            "tokens_per_s": sum(len(r.times) for r in records) / args.seconds,
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "itl_p50_ms": 1e3 * float(np.percentile(gaps, 50)) if gaps else None,
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if gaps else None,
            "backlog_quarters": quarters,
            "compiles_in_window": marks["compiles"],
        }), flush=True)
    stats = engine.stats()
    serve.free(engine)
    print(json.dumps({k: stats[k] for k in ("requests", "completed", "ticks", "preemptions")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
