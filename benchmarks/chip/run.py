"""Run one benchmark cell once on the chip and print its result line.

    python benchmarks/chip/run.py --workload phi4mini.chat --seed 7 --seconds 45 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, optionally ``breakdown``, and ``checks`` last: each
number compared for ``correct`` with its limit). Without a TPU, with fewer
chips than the cell asks for, or on a chip with no row in ``peaks.py``, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    from peaks import UnknownDevice

    cell = harness.load_cell(args.workload)
    try:
        cell.device = harness.device_info(cell.chips)
    except (harness.NoChip, UnknownDevice) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    # the compile cache lives inside the checkout, at a path that never moves
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell.seed, cell.seconds, cell.trace, cell.t_start = (
        args.seed, args.seconds, bool(args.trace), T_START,
    )
    result, checks = cell.driver().run(cell)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
