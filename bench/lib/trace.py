"""Reduce a profiler trace to intervals, and intervals to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps,
for the measured window only, three lists of ``(name, start_s, end_s)``
on the trace's own clock: the device's operations (by ``label``: name and
result type), the device's program executions (XLA modules), and the
benchmark's host spans (``jax.profiler.TraceAnnotation`` names that start
with ``bench.``).  Everything after that is plain arithmetic on
intervals, checked on synthetic traces by the tests.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Iterable, List, Optional, Tuple

Interval = Tuple[str, float, float]

#: the host span that brackets the traced part of the window
WINDOW_SPAN = "bench.window"
#: the paged-attention kernel's custom call, as the device trace names it
KERNEL = "paged_prefill_fwd"

# "%name.12 = bf16[32,4,8,128]{...} custom-call(...)": the op's name and
# its result's type
_OP = re.compile(r"%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?\s*=\s*"
                 r"\(?([a-z0-9]+\[[0-9,]*\])")


@dataclasses.dataclass
class Reduced:
    lo: float                   # traced window, trace clock
    hi: float
    ops: List[Interval]         # device operations
    modules: List[Interval]     # device program executions
    spans: List[Interval]       # host spans of the benchmark
    devices: int = 1

    @property
    def window_s(self) -> float:
        return self.hi - self.lo


def _device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name and \
        "NON_CORE" not in name


def load(trace_dir: str) -> Reduced:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: List[Interval] = []
    modules: List[Interval] = []
    spans: List[Interval] = []
    devices = 0
    for plane in data.planes:
        if _device_plane(plane.name):
            devices += 1
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if into is None:
                    continue
                name = label if into is ops else str
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    into.append((name(ev.name), s,
                                 s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s,
                                      s + ev.duration_ns * 1e-9))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = win[0][1], win[0][2]

    def inside(events):
        return sorted((e for e in events if e[2] > lo and e[1] < hi),
                      key=lambda e: e[1])
    return Reduced(lo=lo, hi=hi, ops=inside(ops), modules=inside(modules),
                   spans=inside(spans), devices=max(devices, 1))


# ------------------------------------------------------------ arithmetic --

def clip(events: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``events`` inside [lo, hi]."""
    out = []
    for n, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((n, s, e))
    return out


def merged(events: Iterable[Interval], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """The union of ``events`` inside [lo, hi], as disjoint intervals."""
    out: List[List[float]] = []
    for _, s, e in sorted(clip(events, lo, hi), key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(events: Iterable[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which at least one event runs."""
    return sum(e - s for s, e in merged(events, lo, hi))


def idle_gaps(events: Iterable[Interval], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi] in which no event runs."""
    out, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gaps: Iterable[Tuple[float, float]],
              spans: List[Interval]) -> List[Tuple[str, float]]:
    """Idle seconds by the host span they fell in, most first.  A gap's
    seconds go to the spans that overlap it, each its own share; the rest
    goes to ``"none"`` (the host was in no span of the benchmark)."""
    total = defaultdict(float)
    for gs, ge in gaps:
        covered = 0.0
        for n, s, e in spans:
            if e <= gs or s >= ge or n == WINDOW_SPAN:
                continue
            part = min(e, ge) - max(s, gs)
            total[n.split(".", 1)[1]] += part
            covered += part
        if ge - gs > covered:
            total["none"] += ge - gs - covered
    return sorted(total.items(), key=lambda kv: -kv[1])


def top_ops(ops: Iterable[Interval], lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """Device seconds by operation name inside [lo, hi], most first."""
    total = defaultdict(float)
    for name, s, e in clip(ops, lo, hi):
        total[name] += e - s
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def label(text: str) -> str:
    """A device operation's name without its instance number, with its
    result's type: ``"copy bf16[32,3624,2,8,4,128]"``."""
    m = _OP.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def kernel_rows(op: str) -> Optional[int]:
    """Query rows per KV head of a paged-attention kernel call, from the
    label of its result ``[B, Kv, rows, hd]``: the chunk length times the
    query heads per KV head.  None for any other operation."""
    name, _, typ = op.partition(" ")
    if name != KERNEL or "[" not in typ:
        return None
    dims = typ.split("[")[1].rstrip("]").split(",")
    return int(dims[2]) if len(dims) == 4 else None


@dataclasses.dataclass
class StepRun:
    """One execution of a step program: it calls the paged kernel."""
    start: float
    end: float
    chunk: int              # tokens per lane the step feeds (1: decode)
    kernel_s: float         # device seconds in its kernel calls


def step_runs(tr: Reduced, groups: int) -> List[StepRun]:
    """Every program execution in the traced window that calls the paged
    kernel, with the chunk length its kernel calls show (``groups``: query
    heads per KV head).  The step programs have no stable names, so a
    decode step is known by its kernel's one query row per head."""
    calls = [o for o in tr.ops if KERNEL in o[0]]
    starts = [o[1] for o in calls]
    out = []
    for _, s, e in tr.modules:
        if not (tr.lo <= s and e <= tr.hi):
            continue
        inside = [o for o in calls[bisect.bisect_left(starts, s):
                                   bisect.bisect_right(starts, e)]
                  if o[2] <= e]
        rows = kernel_rows(inside[0][0]) if inside else None
        if rows is None:
            continue
        out.append(StepRun(s, e, max(1, rows // groups),
                           sum(b - a for _, a, b in inside)))
    return out
