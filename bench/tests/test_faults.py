"""A run driven end to end on the CPU, past the look for a chip, with the
timed path broken underneath: ``correct`` has to come out false for every
fault the cells can have. The run keeps the cell's own traffic file, its
``check_designs`` included, and cuts only n and the population so that a
test run holds it. (The cells' programs exchange nothing between chips,
so the fault "exchange between chips left out" does not apply.)"""
import time

import jax
import numpy as np
import pytest

import harness

N, POP = 16, 64


def drive(workload: str, seconds: float = 0.6) -> dict:
    cell = harness.load_cell(workload)
    cell["config"]["space"]["n_chiplets"] = N
    cell["traffic"]["population"] = POP
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    run = harness.Run(cell, 2**31 + 11, seconds, False, jax.devices()[:1],
                      time.perf_counter(), check_chip=False)
    try:
        driver.run(run)
    finally:
        run.close()
    return run.rec


def break_results(monkeypatch, alter):
    """Route every evaluation's results through ``alter(result, state)``
    where they are produced (the device pipeline's finisher)."""
    from repro.dse import genomes

    original = genomes.AdjacencyPipeline.evaluate_async
    state = {}

    def evaluate_async(self, g):
        pending = original(self, g)
        inner = pending._finisher

        def finish():
            res = inner()
            res.latency = np.array(res.latency)
            res.throughput = np.array(res.throughput)
            return alter(res, state)

        pending._finisher = finish
        return pending

    monkeypatch.setattr(genomes.AdjacencyPipeline, "evaluate_async",
                        evaluate_async)


def stale(res, state):
    """A step that returns its state unchanged: the previous evaluation's
    answers for this one's designs."""
    prev = state.get("prev")
    state["prev"] = res
    if prev is None or len(prev.latency) != len(res.latency):
        return res
    res.latency, res.throughput = prev.latency.copy(), prev.throughput.copy()
    return res


def half_batch(res, state):
    """Half of the batch left out, the mean of the rest in its place."""
    h = len(res.latency) // 2
    if h:
        res.latency[h:] = res.latency[:h].mean()
        res.throughput[h:] = res.throughput[:h].mean()
    return res


def one_answer(res, state):
    """One answer of every evaluated batch altered where it is produced,
    at a row drawn anew for each batch."""
    rng = state.setdefault("rng", np.random.default_rng(7))
    res.latency[rng.integers(len(res.latency))] *= np.float32(0.9)
    return res


@pytest.mark.parametrize("workload", ["adj64.search", "adj256.search"])
def test_search_sound_run_is_correct(workload):
    rec = drive(workload)
    assert rec["correct"], rec["checks"]


@pytest.mark.parametrize("workload", ["adj64.search", "adj256.search"])
@pytest.mark.parametrize("fault", [stale, half_batch, one_answer])
def test_search_fault_is_caught(monkeypatch, fault, workload):
    break_results(monkeypatch, fault)
    rec = drive(workload)
    assert not rec["correct"], rec["checks"]
