"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read: device busy intervals, kernel time by stable name,
and the window the benchmark marked with a ``TraceAnnotation``.

A TPU trace holds one plane per chip (``/device:TPU:<k>``) whose
``XLA Ops`` line has one event per executed HLO operation, and host planes
whose lines carry the runtime's and the benchmark's annotations. All
timestamps are nanoseconds on one clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def annotation(profile, name: str) -> tuple[float, float]:
    """(start, end) ns of the host event called ``name`` (the first one)."""
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise KeyError(f"no host event {name!r} in the trace")


def op_label(text: str) -> str:
    """The HLO instruction name of a device op event, whose name is the
    instruction's text (``%load_prop_pallas.1 = (f32[...]) custom-call(...)``
    -> ``load_prop_pallas.1``). A Pallas kernel's instruction is named
    after its kernel function, so its label is stable across compiles."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def device_ops(profile) -> dict[int, list[tuple[str, float, float]]]:
    """{chip id: [(op name, start ns, end ns), ...]} from the ``XLA Ops``
    line of every TPU plane."""
    out: dict[int, list] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append((op_label(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Parts of (already merged) intervals that lie inside [lo, hi]."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_time_ns(ops, lo: float, hi: float) -> dict[str, float]:
    """Device time per op name inside [lo, hi] (clipped at the edges)."""
    out: dict[str, float] = {}
    for name, s, e in ops:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            out[name] = out.get(name, 0.0) + (e2 - s2)
    return out


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Gaps inside [lo, hi] in which no op ran."""
    gaps, t = [], lo
    for s, e in clip(merge(intervals), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def reduce(path: str, window_name: str) -> dict:
    """Everything the per-layer metrics read from one trace: the window,
    each chip's merged busy intervals inside it, and each chip's op list."""
    profile = load(path)
    lo, hi = annotation(profile, window_name)
    ops = device_ops(profile)
    if not ops or not any(ops.values()):
        raise ValueError(f"{path}: no device op in the trace")
    return {"window_ns": (lo, hi),
            "busy": {d: clip(merge((s, e) for _, s, e in o), lo, hi)
                     for d, o in ops.items()},
            "ops": ops}
