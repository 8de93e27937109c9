"""Arithmetic over the program's spans (``repro.obs``) and the device
trace, shared by the per-layer metric readers.

A run's ``spans`` are ``(name, start, end, thread, depth)`` tuples in
``time.monotonic_ns``; ``mono_window_ns`` is the measured window on that
clock; ``trace['mono_offset_ns']`` carries a monotonic time onto the
profiler trace's clock.
"""
from __future__ import annotations


def in_window(rec: dict, name: str) -> list[tuple[float, float]]:
    """(start, end) of every span ``name`` that ends inside the window."""
    lo, hi = rec["mono_window_ns"]
    return [(s, e) for n, s, e, *_ in rec.get("spans") or ()
            if n == name and lo <= e <= hi]


def total_ns(rec: dict, name: str) -> float:
    return sum(e - s for s, e in in_window(rec, name))


def covered_ns(outer: tuple[float, float], inner) -> float:
    """Time of ``outer`` covered by the union of ``inner`` intervals."""
    lo, hi = outer
    parts = sorted((max(s, lo), min(e, hi)) for s, e in inner
                   if e > lo and s < hi)
    out, end = 0.0, lo
    for s, e in parts:
        s = max(s, end)
        if e > s:
            out += e - s
            end = e
    return out


def self_ns(rec: dict, name: str, children: tuple[str, ...]) -> float:
    """Total time of span ``name`` in the window minus the parts that its
    ``children`` spans cover."""
    kids = [iv for c in children for iv in in_window(rec, c)]
    return sum((e - s) - covered_ns((s, e), kids)
               for s, e in in_window(rec, name))


def device_done_ns(rec: dict, start_mono: float, end_mono: float) -> float:
    """Monotonic time, inside [start, end], at which the last device op
    that ran in that interval ended (on any chip); ``start`` when none ran."""
    tr = rec["trace"]
    off = tr["mono_offset_ns"]
    lo, hi = start_mono + off, end_mono + off
    last = lo
    for ivs in tr["busy"].values():
        for s, e in ivs:
            if s < hi and e > lo:
                last = max(last, min(e, hi))
    return last - off


def device_waits(rec: dict, wait_span: str) -> list[tuple[float, float, float]]:
    """(start, device done, end) of every ``wait_span`` in the window: the
    host waits on the device from start to device done."""
    return [(s, device_done_ns(rec, s, e), e)
            for s, e in in_window(rec, wait_span)]


def idle_share(rec: dict) -> float | None:
    """Device idle share (%) of the traced window, averaged over the chips
    used: 1 - (union of device op intervals) / window."""
    tr = rec.get("trace")
    if not tr:
        return None
    lo, hi = tr["window_ns"]
    chips = rec["device_ids"]
    busy = [sum(e - s for s, e in tr["busy"].get(d, [])) for d in chips]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))


def breakdown(rec: dict, top: int = 10) -> dict:
    """The device ops that took most time (seconds per chip) and the
    longest idle gaps by what the host was doing: each gap is put under
    the innermost program span that covers its middle."""
    import xplane
    tr = rec["trace"]
    lo, hi = tr["window_ns"]
    chips = rec["device_ids"]
    ops: dict[str, float] = {}
    for d in chips:
        for name, t in xplane.op_time_ns(tr["ops"].get(d, []), lo, hi).items():
            ops[name] = ops.get(name, 0.0) + t / len(chips)
    gaps: dict[str, float] = {}
    off = tr["mono_offset_ns"]
    sp = rec.get("spans") or []
    for d in chips:
        for s, e in xplane.idle_gaps(tr["busy"].get(d, []), lo, hi):
            mid = (s + e) / 2 - off
            cover = [x for x in sp if x[1] <= mid <= x[2]]
            name = max(cover, key=lambda x: x[4])[0] if cover else "(no span)"
            gaps[name] = gaps.get(name, 0.0) + (e - s) / len(chips)
    rank = lambda m: sorted(([k, v / 1e9] for k, v in m.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
