"""Benchmark harness. One section per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable tables).

Sections:
  fig1_fig2   paper Figs. 1-2: fib task-graph wall/CPU time across executors
  shapes      chain/wide/wavefront task graphs (Taskflow suite shapes)
  overlap     GIL-releasing overlap (the TPU-host regime)
  pipeline    task-graph-derived 1F1B vs GPipe schedule quality

Env:
  BENCH_FAST=1   smaller fib sizes / fewer repeats (CI mode)
"""
from __future__ import annotations

import os


def _emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.3f},{derived}")


def section_paper(fast: bool) -> None:
    from benchmarks.paper_bench import run_all

    rows = run_all(fast=fast)
    print("\n# paper Figs.1-2 (fib task graphs) + graph shapes + overlap")
    print(f"{'bench':<20}{'executor':<13}{'tasks':>7}{'wall_ms':>10}{'cpu_ms':>10}{'us/task':>9}")
    for r in rows:
        extra = f"  speedup={r['speedup_vs_serial']:.1f}x" if "speedup_vs_serial" in r else ""
        print(
            f"{r['bench']:<20}{r['executor']:<13}{r['tasks']:>7}"
            f"{r['wall_ms']:>10.2f}{r['cpu_ms']:>10.2f}{r['us_per_task']:>9.2f}{extra}"
        )
    print("\n# CSV")
    for r in rows:
        _emit(
            f"{r['bench']}/{r['executor']}",
            r["us_per_task"],
            f"wall_ms={r['wall_ms']:.2f};cpu_ms={r['cpu_ms']:.2f};tasks={r['tasks']}",
        )


def section_pipeline_schedules() -> None:
    from repro.core import (
        gpipe_schedule,
        peak_activation_buffers,
        pipeline_schedule,
        pipeline_task_graph,
    )

    print("\n# task-graph-derived pipeline schedules (1F1B from the paper's policy)")
    print(
        f"{'S':>3}{'M':>5}{'1f1b_ticks':>12}{'gpipe_ticks':>12}"
        f"{'1f1b_peak':>11}{'gpipe_peak':>11}{'bubble':>9}"
    )
    for S, M in [(2, 8), (4, 16), (8, 32), (16, 64)]:
        t1 = pipeline_task_graph(S, M)
        r1 = pipeline_schedule(S, M)
        p1 = max(peak_activation_buffers(t1, r1, S))
        t2 = pipeline_task_graph(S, M, memory_limited=False)
        r2 = gpipe_schedule(S, M)
        p2 = max(peak_activation_buffers(t2, r2, S))
        bubble = r1.makespan / (2 * M) - 1
        print(f"{S:>3}{M:>5}{r1.makespan:>12.0f}{r2.makespan:>12.0f}{p1:>11}{p2:>11}{bubble:>9.1%}")
        _emit(
            f"pipeline/S{S}xM{M}",
            r1.makespan,
            f"gpipe_ticks={r2.makespan:.0f};peak_1f1b={p1};peak_gpipe={p2};bubble={bubble:.3f}",
        )


def main() -> None:
    fast = os.environ.get("BENCH_FAST", "0") == "1"
    section_paper(fast)
    section_pipeline_schedules()


if __name__ == "__main__":
    main()
