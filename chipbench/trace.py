"""Reduction of a profiler trace (``.xplane.pb``) to device time.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Each
device is a plane (``/device:TPU:0`` ...) whose ``XLA Ops`` line holds
one event per operation that ran on it and whose ``XLA Modules`` line
holds one event per compiled program.  The host is a plane too; the
benchmark writes a marker annotation there to put its own host clock
on the trace's clock.

Busy time is the length of the union of a device's op intervals inside
the window; idle gaps are the complement of that union in the window.
Per-program and per-op time are sums of event durations by name.  All
times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open ``(start, end)`` intervals."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], t0: int, t1: int) -> List[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(merged: Sequence[Interval], t0: int, t1: int) -> List[Interval]:
    """Complement of a sorted disjoint union inside ``[t0, t1)``."""
    out, cur = [], t0
    for s, e in clip(merged, t0, t1):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def line_events(plane, line_name: str, *, fallback: bool = True
                ) -> List[Tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every event on the plane's line
    ``line_name``; where the plane has no such line, of all its lines
    (``fallback``) or of none."""
    lines = [ln for ln in plane.lines if ln.name == line_name]
    if not lines and fallback:
        lines = list(plane.lines)
    out = []
    for ln in lines:
        for ev in ln.events:
            s = int(ev.start_ns)
            out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def find_host_event(pd, name: str) -> Tuple[int, int]:
    """``(start_ns, end_ns)`` of the first event called ``name`` on a
    host plane."""
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == name:
                    s = int(ev.start_ns)
                    return s, s + int(ev.duration_ns)
    raise KeyError(f"no host event {name!r} in the trace")


def device_planes(pd, prefix: str = "/device:TPU:") -> list:
    return sorted((p for p in pd.planes if p.name.startswith(prefix)),
                  key=lambda p: p.name)


def sum_by_name(events: Iterable[Tuple[str, int, int]], t0: int,
                t1: int) -> Dict[str, int]:
    """Nanoseconds per event name, each event clipped to the window."""
    out: Dict[str, int] = {}
    for name, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out[name] = out.get(name, 0) + (e - s)
    return out


def short_name(op: str) -> str:
    """An op event's name is its HLO text (``%fusion.3 = f32[...] ...``);
    keep the instruction name."""
    return op.split(" = ", 1)[0]


def ops_within(ops, mods, module_needles: Sequence[str], op_needle: str,
               t0: int, t1: int) -> int:
    """Nanoseconds of ops whose text contains ``op_needle`` and that run
    inside a program whose name contains one of ``module_needles``."""
    spans = union(clip(((s, e) for n, s, e in mods
                        if any(k in n for k in module_needles)), t0, t1))
    inside = [(s, e) for n, s, e in ops if op_needle in n]
    return sum(length(clip(inside, a, b)) for a, b in spans)


def ops_by_program(ops, mods, op_needle: str, t0: int, t1: int
                   ) -> Dict[str, int]:
    """Nanoseconds of ops whose text contains ``op_needle``, by the name
    of the program each runs inside (the one whose event holds its
    start)."""
    inside = sorted((s, e) for n, s, e in ops if op_needle in n)
    if not inside:
        return {}
    starts = [s for s, _ in inside]
    out: Dict[str, int] = {}
    for name, a, b in mods:
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
        ns = length(clip(inside[lo:hi], max(a, t0), min(b, t1)))
        if ns > 0:
            out[name] = out.get(name, 0) + ns
    return out


KERNEL_PROGRAMS = ("_fedagg_call",)


def reduce_device(plane, t0: int, t1: int) -> dict:
    """One device's numbers over the window ``[t0, t1)``: busy time,
    idle gaps, time per op and per program name, the time of the Pallas
    merge kernels (the custom calls inside the fedagg programs) and the
    custom calls' time by the program they run in."""
    ops = line_events(plane, OPS_LINE)
    mods = line_events(plane, MODULES_LINE, fallback=False)
    busy = union(clip(((s, e) for _, s, e in ops), t0, t1))
    return {"name": plane.name,
            "busy_ns": length(busy),
            "gaps": gaps(busy, t0, t1),
            "ops_ns": sum_by_name(((short_name(n), s, e) for n, s, e in ops),
                                  t0, t1),
            "modules_ns": sum_by_name(mods, t0, t1),
            "fedagg_kernel_ns": ops_within(ops, mods, KERNEL_PROGRAMS,
                                           "custom-call", t0, t1),
            "custom_call_ns": ops_by_program(ops, mods, "custom-call", t0,
                                             t1)}


def time_in(names_ns: Dict[str, int], needles: Sequence[str]) -> int:
    """Nanoseconds of the events whose name contains any of ``needles``."""
    return sum(ns for n, ns in names_ns.items()
               if any(k in n for k in needles))


def label_gaps(gap_list: Sequence[Interval], spans: Sequence[Tuple[str, int, int]],
               top: int = 10) -> List[list]:
    """The ``top`` longest gaps as ``[label, seconds]``, each labelled
    by the innermost host span open at its midpoint (``"host"`` where
    none is)."""
    out = []
    for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) // 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        label = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "host"
        out.append([label, (e - s) / 1e9])
    return out
