"""The fault cell driven end to end on the CPU, past the look for a chip,
with the timed path broken underneath: ``correct`` has to come out false
for every fault the cell can have. The run keeps the cell's own traffic
file and scenario model, its ``check_designs`` included, and cuts only n
and the population so that a test run holds it. (The cell's program
exchanges nothing between chips, so the fault "exchange between chips
left out" does not apply.)"""
import time

import jax
import numpy as np
import pytest

import compare
import compare_faults
import harness
import reference_faults

WORKLOAD = "adj64-faults.search"
N, POP = 16, 64


def drive(seconds: float = 0.6, n: int | None = N,
          pop: int | None = POP) -> dict:
    """One run of the cell on the CPU; ``None`` keeps the cell's own n or
    population."""
    cell = harness.load_cell(WORKLOAD)
    if n is not None:
        cell["config"]["space"]["n_chiplets"] = n
    if pop is not None:
        cell["traffic"]["population"] = pop
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    run = harness.Run(cell, 2**31 + 23, seconds, False, jax.devices()[:1],
                      time.perf_counter(), check_chip=False)
    try:
        driver.run(run)
    finally:
        run.close()
    return run.rec


def break_grid(monkeypatch, alter):
    """Route every fault grid through ``alter(grid, state)`` where it is
    produced (the device pipeline's finisher)."""
    from repro.dse import genomes

    original = genomes.AdjacencyPipeline.evaluate_faults_async
    state = {}

    def evaluate_faults_async(self, g, link_fail, node_fail):
        pending = original(self, g, link_fail, node_fail)
        inner = pending._finisher

        def finish():
            grid = inner()
            grid.latency = np.array(grid.latency)
            grid.throughput = np.array(grid.throughput)
            return alter(grid, state)

        pending._finisher = finish
        return pending

    monkeypatch.setattr(genomes.AdjacencyPipeline, "evaluate_faults_async",
                        evaluate_faults_async)


def ignore_masks(monkeypatch):
    """Every scenario given the pristine design's answer: the grid is
    evaluated under its first scenario (the pristine one) alone and that
    column stands for all of them."""
    from repro.dse import genomes

    original = genomes.AdjacencyPipeline.evaluate_faults_async

    def evaluate_faults_async(self, g, link_fail, node_fail):
        assert not link_fail[0].any() and not node_fail[0].any()
        F = len(link_fail)
        pending = original(self, g, link_fail[:1], node_fail[:1])
        inner = pending._finisher

        def finish():
            grid = inner()
            for col in ("latency", "throughput", "reachable_fraction"):
                setattr(grid, col, np.repeat(getattr(grid, col), F, axis=1))
            return grid

        pending._finisher = finish
        return pending

    monkeypatch.setattr(genomes.AdjacencyPipeline, "evaluate_faults_async",
                        evaluate_faults_async)


def stale(grid, state):
    """A step that returns its state unchanged: the previous evaluation's
    answers for this one's designs."""
    prev = state.get("prev")
    state["prev"] = grid
    if prev is None or prev.latency.shape != grid.latency.shape:
        return grid
    grid.latency = prev.latency.copy()
    grid.throughput = prev.throughput.copy()
    return grid


def half_batch(grid, state):
    """Half of the batch left out, the mean of the rest in its place."""
    h = len(grid.latency) // 2
    if h:
        grid.latency[h:] = grid.latency[:h].mean(axis=0)
        grid.throughput[h:] = grid.throughput[:h].mean(axis=0)
    return grid


def one_scenario(grid, state):
    """One scenario's answer altered in every evaluated batch, at a design
    drawn anew for each batch, never in the pristine scenario (the first)
    nor in the design's worst: only the expected columns can show it."""
    rng = state.setdefault("rng", np.random.default_rng(7))
    row = int(rng.integers(len(grid.latency)))
    lat = grid.latency[row]
    choices = np.nonzero(lat < lat.max())[0]
    choices = choices[choices > 0]
    if len(choices):
        col = int(rng.choice(choices))
        lat[col] = lat[col] * np.float32(0.9)
    return grid


def test_fault_cell_sound_run_is_correct():
    rec = drive()
    assert rec["correct"], rec["checks"]
    assert rec["compare_s"] > 0
    assert len(rec["fault_link_fail"]) == 33
    assert rec["grid_program_bytes"]["argument_bytes"] > 0
    assert 0 < rec["degraded_row_share"] <= rec["degraded_design_share"]


@pytest.mark.parametrize("fault", [stale, half_batch, one_scenario])
def test_fault_cell_fault_is_caught(monkeypatch, fault):
    break_grid(monkeypatch, fault)
    rec = drive()
    assert not rec["correct"], rec["checks"]


def test_fault_cell_masks_ignored_is_caught_at_the_cells_size(
        monkeypatch):
    """At the cell's own n and population, where the 32 failed links are
    the longest of 2,016 slots: the search's designs still hold some of
    them, so a grid that ignores the scenario masks is caught. (The
    grid program's memory is read on the chip; here it is left out, as
    the full-size program is not compiled for the CPU.)"""
    from repro.dse import genomes

    monkeypatch.delattr(genomes.AdjacencyPipeline, "faults_memory")
    ignore_masks(monkeypatch)
    rec = drive(n=None, pop=None)
    assert rec["n_chiplets"] == 64 and rec["attempted"] % 256 == 0
    assert rec["compared_degraded_designs"] > 0
    assert rec["degraded_row_share"] > 0
    assert not rec["correct"], rec["checks"]


def test_one_scenario_fault_shows_in_the_expected_columns_alone(
        monkeypatch):
    """The one-scenario fault leaves every worst-case and pristine column
    as the reference has it; the expected latency gives it away."""
    seen = {}
    program = compare_faults.compare_designs

    def spy(designs, config, *a, **kw):
        seen["designs"] = list(designs)
        return program(seen["designs"], config, *a, **kw)

    monkeypatch.setattr(compare_faults, "compare_designs", spy)
    break_grid(monkeypatch, one_scenario)
    rec = drive()
    assert not rec["correct"], rec["checks"]
    cell = harness.load_cell(WORKLOAD)
    config = cell["config"]
    config["space"]["n_chiplets"] = N
    worst = expected = 0.0
    for d in seen["designs"]:
        ref = reference_faults.evaluate(d["bits"], config)
        worst = max(worst, compare.rel_gap(d["worst_latency"],
                                           ref["worst_latency"]),
                    compare.rel_gap(d["pristine_latency"],
                                    ref["pristine_latency"]))
        expected = max(expected, compare.rel_gap(d["expected_latency"],
                                                 ref["expected_latency"]))
    assert worst < cell["limits"]["lat_rel_err"] < expected


def test_control_is_rejected():
    """The reference one precision lower, put in the program's place."""
    cell = harness.load_cell(WORKLOAD)
    config = cell["config"]
    config["space"]["n_chiplets"] = N
    from repro.opt import AdjacencySpace
    space = AdjacencySpace(n_chiplets=N, max_degree=8)
    genomes = space.sample(np.random.default_rng(3), 6)
    designs = [{"bits": g} for g in genomes]
    got = compare_faults.compare_designs(
        compare_faults.control_designs(designs, config), config)
    assert got["lat_rel_err"] > cell["limits"]["lat_rel_err"]
    assert got["thr_rel_err"] > cell["limits"]["thr_rel_err"]

