"""The readers of the search's stage spans, on hand-placed spans: each
counts only spans that end inside the window and finds nothing where its
span is missing."""
import pytest

import harness

MS = 1_000_000   # ns


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def span(name, start_ms, end_ms, depth=0):
    return (name, start_ms * MS, end_ms * MS, "MainThread", depth)


def rec_of():
    """A 1,000 ms window with two generations inside it. The third
    generation's device wait ends inside the window and counts; the
    generation and its stages end after the window closes and do not."""
    return {"mono_window_ns": (0, 1000 * MS), "spans": [
        # generation 1 waits 0-100 (device 0-60, reports 60-95), then
        # selects, ranks, varies and repairs
        span("opt.device_wait", 0, 100),
        span("genomes.finish", 5, 95, 1),
        span("genomes.block", 5, 60, 2),
        span("genomes.reports", 60, 95, 2),
        span("opt.generation", 100, 400),
        span("opt.select", 100, 130, 1),
        span("opt.rank", 130, 150, 1),
        span("opt.vary", 150, 200, 1),
        span("space.repair", 200, 380, 1),
        span("repair.degree_cap", 200, 250, 2),
        span("repair.reach", 250, 300, 2),
        span("repair.connect", 300, 380, 2),
        span("opt.dispatch", 380, 400, 1),
        # generation 2
        span("opt.device_wait", 400, 500),
        span("genomes.finish", 410, 490, 1),
        span("genomes.block", 410, 450, 2),
        span("genomes.reports", 450, 490, 2),
        span("opt.generation", 500, 800),
        span("opt.select", 500, 520, 1),
        span("opt.rank", 520, 530, 1),
        span("opt.vary", 530, 600, 1),
        span("space.repair", 600, 780, 1),
        span("opt.dispatch", 780, 800, 1),
        # generation 3: the wait ends inside the window, the rest after
        span("opt.device_wait", 800, 900),
        span("genomes.finish", 810, 890, 1),
        span("genomes.block", 810, 860, 2),
        span("genomes.reports", 860, 890, 2),
        span("opt.generation", 900, 1200),
        span("opt.select", 900, 1100, 1),
        span("opt.rank", 1100, 1110, 1),
        span("opt.vary", 1110, 1150, 1),
        span("space.repair", 1150, 1190, 1),
    ]}


@pytest.mark.parametrize("name, want", [
    # blocks 55 + 40 + 50 ms of a 1,000 ms window
    ("search.block_share", 100.0 * (55 + 40 + 50) / 1000),
    # two generations end in the window; per generation:
    ("search.reports_ms_per_gen", (35 + 40 + 30) / 2),
    ("search.repair_ms_per_gen", (180 + 180) / 2),
    ("search.vary_ms_per_gen", (50 + 70) / 2),
    ("search.rank_ms_per_gen", (30 + 20 + 20 + 10) / 2),
])
def test_reader_hand_computed(name, want):
    assert reader(name)(rec_of()) == pytest.approx(want)


@pytest.mark.parametrize("name, missing", [
    ("search.block_share", ("genomes.block",)),
    ("search.reports_ms_per_gen", ("genomes.reports",)),
    ("search.repair_ms_per_gen", ("space.repair",)),
    ("search.vary_ms_per_gen", ("opt.vary",)),
    ("search.rank_ms_per_gen", ("opt.rank", "opt.select")),
    ("search.vary_ms_per_gen", ("opt.generation",)),
])
def test_reader_finds_nothing_without_its_span(name, missing):
    rec = rec_of()
    rec["spans"] = [s for s in rec["spans"] if s[0] not in missing]
    assert reader(name)(rec) is None


@pytest.mark.parametrize("name", ["search.block_share",
                                  "search.reports_ms_per_gen",
                                  "search.repair_ms_per_gen",
                                  "search.vary_ms_per_gen",
                                  "search.rank_ms_per_gen"])
def test_reader_untraced_run(name):
    """An untraced run records no spans: nothing to read."""
    assert reader(name)({"mono_window_ns": (0, 1)}) is None
    assert reader(name)({"mono_window_ns": (0, 1), "spans": []}) is None


def test_rank_reader_reads_either_stage():
    """SA has a selection but no ranking of its parents."""
    rec = rec_of()
    rec["spans"] = [s for s in rec["spans"] if s[0] != "opt.rank"]
    assert reader("search.rank_ms_per_gen")(rec) == pytest.approx(
        (30 + 20) / 2)
