"""Readings that set a cell's correctness limits: the program's, its
control's and, for training, planted faults', seed by seed, in one process.

    python benchmarks/chip/control.py --workload phi4mini.chat --seeds 11,12,13 --seconds 10

Serving: for each seed it builds the cell's engine, offers the cell's mix
for a short window at the cell's own load, waits for every answer, frees the
engine and then reads, over the same seeded sample of requests a run
compares:

* ``program``: the widest gap of a served token below the float32
  reference's best (what a run compares with its limit);
* ``control``: the same gap for the token that the reference computed with
  fp8 weights (``quant="fp8"``) puts first at each of those positions: the
  step below the configuration's bf16 that would tempt a later change.

Training: the program's first steps as a run takes them, then the reference
put in the program's place with fp8 weights (``control``), with half of each
batch left out (``half_batch``), and with the tensor-parallel exchange left
out (``no_exchange``: each row-parallel sum over one chip's share), each read
against the float32 reference by the numbers a run compares.

One JSON line per seed. Each limit lies between the program's largest
reading and the smallest of those that must fail (PERF.md). The cell's runs
never call this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def readings(cell, serve, seconds: float) -> dict:
    """One seed: the program's widest gap and the fp8 control's."""
    import jax
    import jax.numpy as jnp

    import traffic as traffic_gen

    engine = serve.build(cell)
    reqs = traffic_gen.make_requests(cell.traffic, cell.seed, seconds, cell.published["vocab_size"])
    records, marks = serve.window(engine, reqs, seconds)
    outputs, failed = serve.collect(records, reqs, marks["t_end"])
    serve.free(engine)
    live = sum(a.nbytes for a in jax.live_arrays())
    toks, pos, served, mask = serve.sample_rows(cell, reqs, outputs)
    ref = cell.reference()
    args = (cell.seed, cell.dims(), cell.published, toks, pos)
    logits = ref.logits_at(*args)
    program = serve.masked_max(serve.token_gaps(logits, served), mask)
    control_first = jnp.argmax(ref.logits_at(*args, quant="fp8"), axis=-1)
    control = serve.masked_max(serve.token_gaps(logits, control_first), mask)
    return {
        "seed": cell.seed, "program": program, "control": control,
        "tokens": int(mask.sum()), "failed": failed, "live_bytes_after_free": live,
    }


def train_readings(cell, train, only=None) -> dict:
    """One seed of a train cell: the program's gaps, the control's and the
    planted faults' (those named in ``only``, when given)."""
    from repro.data import Prefetcher

    trainer, step_fn, shardings, params, opt, data = train.build(cell)
    prefetch = Prefetcher(data, pool=trainer.pool, depth=trainer.tcfg.prefetch_depth)
    try:
        params, opt, prog = train.first_steps(
            cell, trainer, step_fn, shardings, params, opt, prefetch
        )
    finally:
        prefetch.close()
        trainer.close()
    del params, opt
    gc.collect()
    ref = train.reference(cell, data)
    dims, tp = cell.dims(), int(cell.traffic["model_parallel"])
    heads_padded = max(cell.config["assumed"].get("kv_pad_to", 0), dims.kv_heads) * (
        dims.heads // dims.kv_heads
    )
    faults = {
        "control": {"quant": "fp8"},
        "half_batch": {"half": True},
        "no_exchange": {"keep": (heads_padded // tp, dims.d_ff // tp)},
    }
    flat = lambda g: {k: v for k, (v, _w) in g.items()}  # noqa: E731
    out = {"seed": cell.seed, "program": flat(train.gaps(prog, ref))}
    for name, variant in faults.items():
        if only and name not in only:
            continue
        out[name] = flat(train.gaps(train.reference(cell, data, **variant), ref))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--only", help="train: comma-separated faults to read (default all)")
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)
    cell.device = harness.device_info(cell.chips)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = cell.driver()
    cell.trace, cell.t_start = False, T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        cell.seed = seed
        if cell.config["entry"] == "train":
            with tempfile.TemporaryDirectory(prefix="bench-") as cell.scratch:
                only = args.only.split(",") if args.only else None
                print(json.dumps(train_readings(cell, driver, only)), flush=True)
        else:
            print(json.dumps(readings(cell, driver, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
