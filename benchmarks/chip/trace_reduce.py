"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load_events` reads a ``.xplane.pb`` with JAX's own
``ProfileData`` into a small dict of plain lists; every reduction below
works on that dict, so the tests check them on a recorded trace kept
beside them (``tests/data/recorded_trace.json``).

Device planes are ``/device:TPU:<n>``.  A device is busy while one of its
executables runs (the ``XLA Modules`` line).  Host spans are the harness's own
``TraceAnnotation`` names, all starting with ``bench.``.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# The span executables are jitted from ``functools.partial`` objects, which
# XLA names ``jit__unknown``; the program's only such executables on the
# served path are the span programs.
SPAN_MODULE = re.compile(r"^jit__unknown\(")
HOST_PREFIX = "bench."
TOP = 10

Interval = Tuple[float, float]


def load_events(path: str) -> dict:
    """``{"devices": {plane: {"modules": [[name, start_ns, dur_ns]...],
    "ops": [[name, total_ns, count]...]}}, "host": [[name, start_ns,
    dur_ns]...]}``; ops are summed by name as they are read, so a long
    trace never sits in memory event by event."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            mods, ops = [], collections.defaultdict(lambda: [0.0, 0])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend([e.name, e.start_ns, e.duration_ns]
                                for e in line.events)
                elif line.name == "XLA Ops":
                    for e in line.events:
                        agg = ops[op_name(e.name)]
                        agg[0] += e.duration_ns
                        agg[1] += 1
            devices[plane.name] = {
                "modules": mods,
                "ops": [[k, v[0], v[1]] for k, v in ops.items()]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """``%fusion.12 = u32[...] fusion(...)`` -> ``fusion.12``: a TPU trace
    names each operation by its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window(ev: dict, name: str = "bench.window") -> Optional[Interval]:
    """The traced window: the harness's own span around it."""
    spans = [(s, s + d) for n, s, d in ev["host"] if n == name]
    return (min(s for s, _ in spans), max(e for _, e in spans)) if spans \
        else None


def busy(dev: dict) -> List[Interval]:
    """Merged intervals in which an executable ran on one device."""
    return merge([(s, s + d) for _, s, d in dev["modules"]])


def busy_seconds(ev: dict, win: Interval) -> float:
    """Busy seconds inside the window, averaged over the devices."""
    devs = list(ev["devices"].values())
    if not devs:
        return 0.0
    tot = sum(sum(e - s for s, e in clip(busy(d), *win)) for d in devs)
    return tot / len(devs) / 1e9


def span_seconds(ev: dict, win: Interval) -> float:
    """Seconds the span program's executables ran inside the window,
    averaged over the devices."""
    devs = list(ev["devices"].values())
    if not devs:
        return 0.0
    tot = 0.0
    for d in devs:
        runs = [(s, s + du) for n, s, du in d["modules"]
                if SPAN_MODULE.search(n)]
        tot += sum(e - s for s, e in clip(merge(runs), *win))
    return tot / len(devs) / 1e9


def top_ops(ev: dict, k: int = TOP) -> List[list]:
    """The device operations that took the most time, seconds summed over
    the devices."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for d in ev["devices"].values():
        for name, ns, _ in d["ops"]:
            tot[name] += ns
    return [[n, ns / 1e9] for n, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(ev: dict, win: Interval, k: int = TOP) -> List[list]:
    """The longest idle gaps of the first device inside the window, each
    named by the harness span that overlaps it most (``other`` where none
    does), in seconds."""
    if not ev["devices"]:
        return []
    dev = ev["devices"][sorted(ev["devices"])[0]]
    b = clip(busy(dev), *win)
    gaps, t = [], win[0]
    for s, e in b:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if win[1] > t:
        gaps.append((t, win[1]))
    spans = [(n[len(HOST_PREFIX):], s, s + d) for n, s, d in ev["host"]
             if n != "bench.window"]
    out = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        best, cover = "other", 0.0
        for n, s, e in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > cover:
                best, cover = n, ov
        out.append([best, (ge - gs) / 1e9])
    return out
