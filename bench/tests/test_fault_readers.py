"""The fault cell's per-layer readers on hand-placed spans and a
hand-made trace: each counts only what lies in the window, finds nothing
where its span is missing (as in a program that does not yet have it),
and the roofline reader's degraded diameter is the longest finite
shortest path of every (design, scenario) graph."""
import numpy as np
import pytest

import compare_faults
import harness

MS = 1_000_000   # ns


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def span(name, start_ms, end_ms, depth=0):
    return (name, start_ms * MS, end_ms * MS, "MainThread", depth)


def rec_of(with_new_spans=True):
    """A 1,000 ms window with two fault generations; a third reduction
    ends after the window and must not count."""
    spans = [
        span("opt.generation", 300, 500),
        span("opt.finalize", 300, 320, 1),
        span("faults.reduce", 302, 312, 2),
        span("opt.generation", 800, 950),
        span("faults.reduce", 802, 810, 2),
        span("faults.reduce", 990, 1100, 2),     # ends after the window
    ]
    if not with_new_spans:
        spans = [s for s in spans if s[0] != "faults.reduce"]
    return {"mono_window_ns": (0, 1000 * MS), "spans": spans}


def test_reduce_per_generation():
    assert reader("faults.reduce_ms_per_gen").read(rec_of()) == \
        pytest.approx((10 + 8) / 2)


def test_reduce_reader_finds_nothing_without_its_span():
    reduce = reader("faults.reduce_ms_per_gen")
    assert reduce.read(rec_of(with_new_spans=False)) is None
    assert reduce.read({"mono_window_ns": (0, 1)}) is None


def _diameter_by_scipy(bits, link_fail, node_fail, n):
    from scipy.sparse.csgraph import shortest_path

    pu, pv = np.triu_indices(n, 1)
    best = 0
    for b in bits:
        for lf, nf in zip(link_fail, node_fail):
            on = np.nonzero(b & ~lf)[0]
            a = np.zeros((n, n))
            a[pu[on], pv[on]] = 1
            a = np.maximum(a, a.T)
            a[nf, :] = 0
            a[:, nf] = 0
            d = shortest_path(a, directed=False, unweighted=True)
            best = max(best, int(d[np.isfinite(d)].max()))
    return best


def test_degraded_diameter_matches_a_graph_library():
    from repro.faults.model import single_chiplet_faults, single_link_faults
    from repro.opt import AdjacencySpace

    n = 16
    space = AdjacencySpace(n_chiplets=n, max_degree=4)
    bits = space.sample(np.random.default_rng(2), 12).astype(bool)
    dia = reader("faults.load_propagate_roofline").degraded_diameter
    for sc in (single_link_faults(space, top_k=40),
               single_link_faults(space, top_k=40, include_pristine=False),
               single_chiplet_faults(space)):
        want = _diameter_by_scipy(bits, sc.link_fail, sc.node_fail, n)
        assert dia(bits, sc.link_fail, sc.node_fail, n) == want


def test_roofline_reader_finds_nothing_outside_a_fault_run():
    roof = reader("faults.load_propagate_roofline")
    assert roof.read({"trace": {"window_ns": (0, 1), "ops": {}}}) is None


def test_degraded_rows_marks_scenarios_that_change_a_design():
    """A scenario degrades a design where it kills a link the design has
    or any chiplet; one that kills only absent links leaves it whole."""
    bits = np.array([[1, 0, 1, 0], [0, 1, 0, 0]], bool)
    link_fail = np.array([[0, 0, 0, 0], [1, 0, 0, 0], [0, 0, 0, 1],
                          [0, 0, 0, 0]], bool)
    node_fail = np.zeros((4, 3), bool)
    node_fail[3, 2] = True
    want = np.array([[False, True, False, True],
                     [False, False, False, True]])
    got = compare_faults.degraded_rows(bits, link_fail, node_fail)
    np.testing.assert_array_equal(got, want)
