"""The reduction from a profiler trace to busy intervals and kernel time."""
import json
from pathlib import Path


import xplane

DATA = Path(__file__).resolve().parent / "data"


def test_merge_clip_and_gaps():
    ivs = [(5, 7), (0, 2), (1, 3), (6, 9)]
    assert xplane.merge(ivs) == [(0, 3), (5, 9)]
    assert xplane.clip(xplane.merge(ivs), 2, 6) == [(2, 3), (5, 6)]
    assert xplane.idle_gaps(ivs, -1, 10) == [(-1, 0), (3, 5), (9, 10)]
    assert xplane.op_time_ns([("a", 0, 4), ("b", 3, 8), ("a", 9, 12)],
                             2, 10) == {"a": 3, "b": 5}


def test_recorded_trace():
    """A TPU trace recorded by ``record_trace.py``: two calls of the fused
    kernel with a sleep between them inside the window."""
    facts = json.loads((DATA / "small.json").read_text())
    tr = xplane.reduce(str(DATA / "small.xplane.pb"), "bench.window")
    lo, hi = tr["window_ns"]
    assert hi - lo >= facts["sleep_s"] * 1e9
    assert list(tr["ops"]) == [0]
    calls = [op for op in tr["ops"][0]
             if op[0].startswith(facts["kernel"])]
    assert len(calls) == facts["kernel_calls"]
    assert all(lo <= s < e <= hi for _, s, e in calls)
    busy = sum(e - s for s, e in tr["busy"][0])
    assert 0 < busy < hi - lo - facts["sleep_s"] * 1e9
    assert sum(e - s for _, s, e in calls) <= busy
    gaps = xplane.idle_gaps(tr["busy"][0], lo, hi)
    assert max(e - s for s, e in gaps) >= facts["sleep_s"] * 1e9
    assert abs(busy + sum(e - s for s, e in gaps) - (hi - lo)) < 1.0
