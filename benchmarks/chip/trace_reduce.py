"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The trace is read with ``jax.profiler.ProfileData`` and nothing else. On a
TPU each chip is a plane ``/device:TPU:<n>`` with two lines this reads:

* ``XLA Modules``: one event per run of a jitted program, named
  ``jit_<function>(<fingerprint>)``; :func:`program_name` drops the
  fingerprint, so ``jax.jit(model.prefill)`` reads as ``jit_prefill``;
* ``XLA Ops``: one event per HLO operation, named by its HLO text
  (``%fusion.13 = bf16[...] fusion(...), kind=...``); :func:`op_label`
  shortens that to the op's name and opcode.

Host threads are lines of the plane ``/host:CPU``; ``TraceAnnotation``
spans appear there under their own names. All events share one clock, in
nanoseconds from the start of the trace.

Busy time is the union of op intervals, so nested events (a ``while`` and
the ops of its body) count once. A collective's exposed time is the part of
its interval during which no other (compute) op runs on that chip.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

from jax.profiler import ProfileData

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
    "collective-broadcast", "send", "recv",
)
# ops that only wrap other ops: their interval is covered by their body's
# events, so they are left out of per-op times (not of the busy union)
CONTAINERS = ("while", "conditional", "call")

_OPCODE = re.compile(r"\s([a-z][\w.\-]*)\(")


@dataclass
class Op:
    label: str
    opcode: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur

    @property
    def collective(self) -> bool:
        return any(self.opcode.startswith(c) for c in COLLECTIVES) or any(
            c in self.label for c in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")
        )


@dataclass
class Program:
    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Device:
    programs: list[Program] = field(default_factory=list)
    ops: list[Op] = field(default_factory=list)


@dataclass
class Trace:
    devices: dict[int, Device]
    host: list[tuple[str, int, int]]  # (name, start, dur) of host-thread events


def program_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def op_label(hlo_text: str) -> tuple[str, str]:
    """``("%fusion.13", "fusion")`` from an op's HLO text."""
    head, _, rest = hlo_text.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    return head.strip(), (m.group(1) if m else head.strip().lstrip("%").split(".")[0])


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str, host_prefixes: tuple[str, ...] = ()) -> Trace:
    """Read one ``.xplane.pb``; keep host events whose name starts with one of
    ``host_prefixes``."""
    pd = ProfileData.from_file(path)
    devices: dict[int, Device] = {}
    host: list[tuple[str, int, int]] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.programs += [
                        Program(program_name(e.name), int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        label, opcode = op_label(e.name)
                        dev.ops.append(Op(label, opcode, int(e.start_ns), int(e.duration_ns)))
        elif plane.name == "/host:CPU" and host_prefixes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefixes):
                        host.append((e.name, int(e.start_ns), int(e.duration_ns)))
    for dev in devices.values():
        dev.programs.sort(key=lambda p: p.start)
        dev.ops.sort(key=lambda o: o.start)
    host.sort(key=lambda h: h[1])
    return Trace(devices, host)


# -- interval arithmetic ------------------------------------------------------------


def merge(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``, sorted."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def gaps(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_ns(dev: Device, lo: int, hi: int) -> int:
    return total(merge(((o.start, o.end) for o in dev.ops), lo, hi))


def collective_ns(dev: Device, lo: int, hi: int) -> tuple[int, int]:
    """(collective time, exposed collective time) on one chip in ``[lo, hi]``."""
    coll = merge(((o.start, o.end) for o in dev.ops if o.collective), lo, hi)
    compute = merge(
        ((o.start, o.end) for o in dev.ops if not o.collective and o.opcode not in CONTAINERS),
        lo, hi,
    )
    exposed = 0
    for s, e in coll:
        covered = total(merge(compute, s, e))
        exposed += (e - s) - covered
    return total(coll), exposed


def top_ops(dev: Device, lo: int, hi: int, n: int = 10) -> list[list]:
    """The ``n`` ops (by label) with the most device time, ``[[name, s], ...]``;
    each op is named ``<program>/<op>`` by the program run it falls in."""
    acc: dict[str, int] = {}
    progs = dev.programs
    j = 0
    for o in dev.ops:
        if o.opcode in CONTAINERS or o.start < lo or o.start > hi:
            continue
        while j < len(progs) and progs[j].end < o.start:
            j += 1
        prog = progs[j].name if j < len(progs) and progs[j].start <= o.start else "?"
        key = f"{prog}/{o.label}"
        acc[key] = acc.get(key, 0) + o.dur
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
