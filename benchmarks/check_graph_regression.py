"""CI perf-regression gate for the task-graph scheduler (DESIGN.md §9/§11).

Compares a fresh ``graph_bench`` run against a committed baseline and
fails (exit 1) when any work-stealing row regresses by more than
``--threshold``× in ``overhead_us_per_task``, or when the process
backend's cpu-bound row drops below ``--min-process-speedup`` versus the
best thread-backend row (the §11 gate: the backend built for CPU-bound
bodies must never be slower than the backend it exists to beat — the
floor is deliberately a sanity bound, not the ≥2× headline, because
shared CI runners undercut real parallelism unpredictably; dedicated
multi-core hosts show the headline figure).

The cpu-bound shape is excluded from the *overhead* gate: its wall time
is compute, so "overhead over the serial floor" there measures parallel
speedup jitter, not scheduler cost.

The §12 replay gate runs entirely inside the fresh payload: the chain
shape's ``ws-replay`` row must beat its own ``ws-fast`` row within the
noise envelope (``--replay-slack-us``) — the fused-segment dispatch that
replay exists for must stay cheaper than live dispatch of the same chain,
on the same host, in the same run. Fusion-poor shapes (wavefront,
random-dag) legitimately track live dispatch, so only the chain shapes
participate. Additionally ``--full-baseline`` (default: the committed
full-size ``BENCH_graph.json``) enforces the absolute §12 acceptance
figure: the committed chain ``ws-replay`` overhead at the gate thread
count must stay at or below ``--replay-chain-max-us``.

Rows are matched by **shape prefix** (``chain(1024)`` and ``chain(8192)``
both match ``chain``), so a baseline at one size can in principle gate a
run at another. In practice CI gates quick-vs-quick: per-task overhead at
quick sizes carries un-amortized fixed costs (pool spin-up, root
scheduling) that the full-size ``BENCH_graph.json`` rows do not, so the
committed gate baseline is ``benchmarks/BENCH_graph_quick.json`` — quick
sizes, with each overhead recorded as the noise envelope (max) of several
runs. Only ws-fast rows at the baseline's default thread count
participate. The absolute slack (``--slack-us``) keeps near-zero-overhead
rows from failing on jitter — at ~1 µs overheads a 1.5× ratio is smaller
than CI-runner noise, while the regression class this gate exists for
(a lock back on the task path) shows up at 5–10 µs.

The §16 socket gate runs entirely inside the fresh payload and never
passes vacuously: every shape named in ``--socket-shapes`` (default
``chain,cpu-bound`` — pass an empty string to disarm) must carry a
``ws-socket`` row, the cpu-bound socket row must finish within
``--max-socket-vs-process``× of the same run's ``ws-process`` wall (the
transport may tax compute, not swallow it), and the chain socket row's
``us_per_task`` — the pure per-task TCP round-trip — must stay under
``--max-socket-us-per-task``. Both bounds are deliberately generous
sanity rails for shared runners: the regression class they exist for
(a serialized dispatcher, a lost-wakeup stall in the slot handoff, a
cache gone quadratic) shows up as a 10–100× blowout, not a 2× dip.

    PYTHONPATH=src python benchmarks/check_graph_regression.py \
        --baseline benchmarks/BENCH_graph_quick.json \
        --new benchmarks/artifacts/BENCH_graph.json --slack-us 1.5
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

DEFAULT_THREADS = 4


def shape_prefix(bench: str) -> str:
    """``chain(8192)`` -> ``chain``; ``wavefront(64x64)`` -> ``wavefront``."""
    return bench.split("(", 1)[0]


def ws_rows(payload: dict, threads: int, executor: str = "ws-fast") -> dict[str, float]:
    """Map shape-prefix -> overhead_us_per_task for one executor's rows.

    Rows written before the --threads sweep carry no ``threads`` field;
    they were all recorded at the default worker count. The cpu-bound
    shape never carries an overhead figure (module docs).
    """
    out: dict[str, float] = {}
    for row in payload["rows"]:
        if row.get("executor") != executor:
            continue
        if row.get("threads", DEFAULT_THREADS) != threads:
            continue
        if "overhead_us_per_task" not in row:
            continue
        out[shape_prefix(row["bench"])] = row["overhead_us_per_task"]
    return out


def socket_gate(payload: dict, args) -> list[str]:
    """§16 socket-transport gate (module docs). Returns failure labels."""
    wanted = [s.strip() for s in args.socket_shapes.split(",") if s.strip()]
    if not wanted:
        return []
    failures: list[str] = []
    sock: dict[str, dict] = {}
    proc_wall: dict[str, float] = {}
    for row in payload["rows"]:
        prefix = shape_prefix(row["bench"])
        if row.get("executor") == "ws-socket":
            sock[prefix] = row
        elif row.get("executor") == "ws-process":
            proc_wall[prefix] = row["wall_ms"]
    for shape in wanted:
        row = sock.get(shape)
        if row is None:
            print(f"FAIL: socket: no ws-socket {shape} row in the fresh run")
            failures.append(f"socket {shape} (missing)")
            continue
        if shape in proc_wall:
            ratio = row["wall_ms"] / proc_wall[shape]
            limit = args.max_socket_vs_process
            verdict = "ok" if ratio <= limit else "REGRESSION"
            print(
                f"{shape:<18}ws-socket wall {ratio:.2f}x of ws-process "
                f"(max {limit:.2f}x)  {verdict}"
            )
            if ratio > limit:
                failures.append(f"socket {shape} vs process")
        else:
            per_task = row["us_per_task"]
            limit = args.max_socket_us_per_task
            verdict = "ok" if per_task <= limit else "REGRESSION"
            print(
                f"{shape:<18}ws-socket {per_task:.1f}us/task round-trip "
                f"(max {limit:.1f}us)  {verdict}"
            )
            if per_task > limit:
                failures.append(f"socket {shape} round-trip")
    return failures


def process_speedups(payload: dict) -> dict[str, float]:
    """Map shape-prefix -> speedup_vs_thread for ws-process rows."""
    return {
        shape_prefix(row["bench"]): row["speedup_vs_thread"]
        for row in payload["rows"]
        if row.get("executor") == "ws-process" and "speedup_vs_thread" in row
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", required=True, help="committed BENCH_graph.json")
    ap.add_argument("--new", required=True, help="freshly generated BENCH_graph.json")
    ap.add_argument("--threads", type=int, default=DEFAULT_THREADS)
    ap.add_argument("--threshold", type=float, default=1.5, help="max allowed ratio")
    ap.add_argument("--slack-us", type=float, default=1.0, help="absolute noise floor (µs)")
    ap.add_argument(
        "--min-process-speedup",
        type=float,
        default=0.9,
        help="floor for ws-process speedup_vs_thread on the cpu-bound shape "
        "(sanity bound for shared runners; see module docs)",
    )
    ap.add_argument(
        "--replay-slack-us",
        type=float,
        default=0.5,
        help="noise envelope for the §12 gate: the fresh chain ws-replay row "
        "must not exceed the fresh chain ws-fast row by more than this (µs)",
    )
    ap.add_argument(
        "--replay-chain-max-us",
        type=float,
        default=0.06,
        help="absolute §12 acceptance bound on the committed full-size chain "
        "ws-replay overhead (µs/task)",
    )
    ap.add_argument(
        "--full-baseline",
        default=str(pathlib.Path(__file__).resolve().parent.parent / "BENCH_graph.json"),
        help="committed full-size BENCH_graph.json for the absolute replay "
        "bound (pass an empty string to skip)",
    )
    ap.add_argument(
        "--socket-shapes",
        default="chain,cpu-bound",
        help="comma-separated shape prefixes that must carry a ws-socket row "
        "in the fresh run (§16 gate; empty string disarms it)",
    )
    ap.add_argument(
        "--max-socket-vs-process",
        type=float,
        default=3.0,
        help="max allowed ratio of the cpu-bound ws-socket wall over the same "
        "run's ws-process wall (generous rail; see module docs)",
    )
    ap.add_argument(
        "--max-socket-us-per-task",
        type=float,
        default=2000.0,
        help="ceiling on the chain ws-socket us_per_task — the per-task TCP "
        "round-trip (generous rail; see module docs)",
    )
    args = ap.parse_args()

    baseline = ws_rows(json.loads(pathlib.Path(args.baseline).read_text()), args.threads)
    new_payload = json.loads(pathlib.Path(args.new).read_text())
    fresh = ws_rows(new_payload, args.threads)

    if not baseline:
        print("no ws-fast baseline rows found — nothing to gate")
        return 0

    failures: list[str] = []
    compared = 0
    print(f"{'shape':<18}{'baseline us':>12}{'new us':>10}{'limit us':>10}  verdict")
    for shape, base in sorted(baseline.items()):
        if shape not in fresh:
            print(f"{shape:<18}{base:>12.2f}{'—':>10}{'—':>10}  missing in new run (skipped)")
            continue
        compared += 1
        new = fresh[shape]
        limit = base * args.threshold + args.slack_us
        verdict = "ok" if new <= limit else "REGRESSION"
        print(f"{shape:<18}{base:>12.2f}{new:>10.2f}{limit:>10.2f}  {verdict}")
        if new > limit:
            failures.append(shape)

    for shape in sorted(set(fresh) - set(baseline)):
        print(f"{shape:<18}{'—':>12}{fresh[shape]:>10.2f}{'—':>10}  new shape (no baseline)")

    # §11 gate: the process backend must beat (or at worst match, within
    # the configured floor) the thread backend on the cpu-bound shape
    speedup_failures: list[str] = []
    speedups = process_speedups(new_payload)
    for shape, speed in sorted(speedups.items()):
        verdict = "ok" if speed >= args.min_process_speedup else "REGRESSION"
        print(
            f"{shape:<18}ws-process speedup_vs_thread "
            f"{speed:.2f}x (floor {args.min_process_speedup:.2f}x)  {verdict}"
        )
        if speed < args.min_process_speedup:
            speedup_failures.append(shape)

    # §12 gate A: chain replay beats chain live, fresh run vs itself
    replay_failures: list[str] = []
    fresh_replay = ws_rows(new_payload, args.threads, executor="ws-replay")
    for shape in sorted(fresh_replay):
        if not shape.startswith("chain"):
            continue  # fusion-poor shapes track live dispatch (module docs)
        if shape not in fresh:
            continue
        live, replayed = fresh[shape], fresh_replay[shape]
        limit = live + args.replay_slack_us
        verdict = "ok" if replayed <= limit else "REGRESSION"
        print(
            f"{shape:<18}ws-replay {replayed:.2f}us vs ws-fast {live:.2f}us "
            f"(limit {limit:.2f}us)  {verdict}"
        )
        if replayed > limit:
            replay_failures.append(shape)
    if not any(s.startswith("chain") for s in fresh_replay):
        print("FAIL: no fresh chain ws-replay row — the §12 gate compared nothing")
        replay_failures.append("chain (missing)")

    # §12 gate B: the committed full-size chain replay figure holds
    if args.full_baseline:
        full_path = pathlib.Path(args.full_baseline)
        full_replay = ws_rows(
            json.loads(full_path.read_text()), args.threads, executor="ws-replay"
        )
        chain_full = {s: v for s, v in full_replay.items() if s.startswith("chain")}
        if not chain_full:
            print(f"FAIL: no chain ws-replay row in {full_path}")
            replay_failures.append("chain (full baseline missing)")
        for shape, ovh in sorted(chain_full.items()):
            verdict = "ok" if ovh <= args.replay_chain_max_us else "REGRESSION"
            print(
                f"{shape:<18}committed ws-replay {ovh:.3f}us "
                f"(max {args.replay_chain_max_us:.3f}us)  {verdict}"
            )
            if ovh > args.replay_chain_max_us:
                replay_failures.append(f"{shape} (committed)")

    # §16 gate: the socket transport holds its rails inside the fresh run
    socket_failures = socket_gate(new_payload, args)

    if failures or speedup_failures or replay_failures or socket_failures:
        if replay_failures:
            print(f"\nFAIL: §12 replay gate: {', '.join(replay_failures)}")
        if socket_failures:
            print(f"\nFAIL: §16 socket gate: {', '.join(socket_failures)}")
        if failures:
            print(
                f"\nFAIL: overhead regression >{args.threshold}x in: "
                f"{', '.join(failures)}"
            )
        if speedup_failures:
            print(
                "\nFAIL: §11 process backend below the "
                f"{args.min_process_speedup:.2f}x speedup floor in: "
                f"{', '.join(speedup_failures)}"
            )
        return 1
    if compared == 0:
        # never fail open: a gate that compared nothing (renamed shapes,
        # thread-count mismatch, empty run) must not pass vacuously
        print("\nFAIL: no baseline shape matched the new run — the gate compared nothing")
        return 1
    print(f"\nOK: no scheduler-overhead regression ({compared} shapes compared)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
