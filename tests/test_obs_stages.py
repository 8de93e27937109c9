"""Spans of a search generation's stages and the counters beside them.

An ``AsyncStepper`` run with the tracer on names every stage of a
generation: ranking, variation, repair (degree cap, reachability,
connection) and environmental selection inside ``opt.generation``, and the
device block apart from the host reports inside ``genomes.finish``. The
counters measure what they say: ``opt.async.wait_s`` is the device block
alone, ``opt.evals_completed`` the results handed to the optimizer. With
JAX loaded, every span also lands in the profiler's own trace. Tracing
changes nothing the search computes.
"""
import glob
import os
import sys

import numpy as np
import pytest

from repro.dse.engine import DseEngine
from repro.obs import metrics as obs_metrics
from repro.obs.trace import (TRACER, Tracer, _NULL_SPAN, disable_tracing,
                             enable_tracing, span)
from repro.opt import (AdjacencySpace, AsyncStepper, EvolutionarySearch,
                       OptRunner, PopulationEvaluator, SimulatedAnnealing)

N, POP = 16, 16
RUNS = {"nsga2": (EvolutionarySearch, "pop_size", 4),
        "sa": (SimulatedAnnealing, "n_chains", 2)}
STAGES = {"nsga2": ("opt.rank", "opt.vary", "space.repair", "opt.select"),
          "sa": ("opt.vary", "space.repair", "opt.select")}
EPS_US = 0.01   # float rounding of the exported microsecond timestamps


@pytest.fixture(scope="module")
def space():
    return AdjacencySpace(n_chiplets=N)


@pytest.fixture(scope="module")
def engine():
    return DseEngine()


def _counter(name: str) -> float:
    return sum(c.value for c in obs_metrics.REGISTRY.series("Counter", name))


def _optimizer(algo, space, engine, seed=3):
    cls, size_kw, _ = RUNS[algo]
    return cls(space, PopulationEvaluator(space, engine=engine), seed=seed,
               **{size_kw: POP})


def _run(algo, space, engine, traced: bool) -> dict:
    """One ``AsyncStepper`` run; its spans (when traced), counter deltas
    and final optimizer state."""
    opt = _optimizer(algo, space, engine)
    gens = RUNS[algo][2]
    before = {c: _counter(c) for c in ("opt.async.wait_s",
                                       "opt.evals_completed")}
    if traced:
        enable_tracing()
    try:
        AsyncStepper(opt, gens).run()
    finally:
        disable_tracing()
    return {"spans": TRACER.to_dicts() if traced else [],
            "counters": {c: _counter(c) - v for c, v in before.items()},
            "state": opt.state(), "gens": gens}


@pytest.fixture(scope="module")
def traced(space, engine):
    return {algo: _run(algo, space, engine, True) for algo in RUNS}


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _end(s):
    return s["ts_us"] + s["dur_us"]


def _inside(inner, outer) -> bool:
    return (inner["thread"] == outer["thread"]
            and outer["ts_us"] - EPS_US <= inner["ts_us"]
            and _end(inner) <= _end(outer) + EPS_US)


def _within_any(s, outers) -> bool:
    return any(_inside(s, o) for o in outers)


@pytest.mark.parametrize("algo", sorted(RUNS))
def test_host_stages_nest_inside_the_generation(traced, algo):
    spans = traced[algo]["spans"]
    gens = _named(spans, "opt.generation")
    assert len(gens) == RUNS[algo][2]
    for name in STAGES[algo]:
        # the first population is sampled (and repaired) before the first
        # generation span opens; every later stage lies inside one
        stage = [s for s in _named(spans, name)
                 if s["ts_us"] >= gens[0]["ts_us"]]
        assert stage, name
        assert all(_within_any(s, gens) for s in stage), name
    repairs = _named(spans, "space.repair")
    for name in ("repair.degree_cap", "repair.reach", "repair.connect"):
        assert all(_within_any(s, repairs) for s in _named(spans, name))
    assert _named(spans, "repair.degree_cap")
    assert len(_named(spans, "repair.reach")) == len(repairs)
    for r in repairs:
        assert r["attrs"]["genomes"] == POP
        assert 0 <= r["attrs"]["connected"] <= POP


@pytest.mark.parametrize("algo", sorted(RUNS))
def test_device_block_precedes_the_reports(traced, algo):
    spans = traced[algo]["spans"]
    waits = _named(spans, "opt.device_wait")
    fins = _named(spans, "genomes.finish")
    blocks = _named(spans, "genomes.block")
    reports = _named(spans, "genomes.reports")
    assert len(waits) == len(fins) == len(blocks) == len(reports) \
        == RUNS[algo][2]
    for fin, blk, rep in zip(fins, blocks, reports):
        assert _within_any(fin, waits)
        assert _inside(blk, fin) and _inside(rep, fin)
        assert _end(blk) <= rep["ts_us"] + EPS_US


@pytest.mark.parametrize("algo", sorted(RUNS))
def test_async_counters_measure_block_and_completed_results(traced, algo):
    run = traced[algo]
    spans = run["spans"]
    block_s = sum(s["dur_us"] for s in _named(spans, "genomes.block")) / 1e6
    wait_span_s = sum(s["dur_us"]
                      for s in _named(spans, "opt.device_wait")) / 1e6
    wait_s = run["counters"]["opt.async.wait_s"]
    assert abs(wait_s - block_s) < 1e-3, (wait_s, block_s)
    assert wait_s < wait_span_s
    assert run["counters"]["opt.evals_completed"] == run["gens"] * POP


@pytest.mark.parametrize("algo", sorted(RUNS))
def test_tracing_changes_nothing_the_search_computes(traced, space, engine,
                                                     algo):
    quiet = _run(algo, space, engine, False)
    assert quiet["state"] == traced[algo]["state"]   # archive, RNG stream
    assert quiet["counters"]["opt.evals_completed"] == \
        traced[algo]["counters"]["opt.evals_completed"]


def test_drain_counts_the_in_flight_generation(space, engine):
    opt = _optimizer("nsga2", space, engine)
    stepper = AsyncStepper(opt, 4)
    n0, w0 = _counter("opt.evals_completed"), _counter("opt.async.wait_s")
    enable_tracing()
    try:
        stepper.step()
        stepper.step()
        stepper.drain()
    finally:
        disable_tracing()
    blocks = _named(TRACER.to_dicts(), "genomes.block")
    assert opt.generation == 3 and len(blocks) == 3
    assert _counter("opt.evals_completed") - n0 == 3 * POP
    assert abs(_counter("opt.async.wait_s") - w0
               - sum(b["dur_us"] for b in blocks) / 1e6) < 1e-3


def test_sync_runner_counts_completed_evaluations(space, engine):
    opt = _optimizer("nsga2", space, engine)
    n0 = _counter("opt.evals_completed")
    OptRunner(opt).run(3)
    assert _counter("opt.evals_completed") - n0 == 3 * POP


def test_spans_land_in_the_jax_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    enable_tracing()
    try:
        with span("stage.outer"):
            with span("stage.inner"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        disable_tracing()
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert {"stage.outer", "stage.inner"} <= names
    assert [s["name"] for s in TRACER.to_dicts()] == ["stage.outer",
                                                      "stage.inner"]


def test_profiler_annotation_is_bound_only_when_jax_is_loaded(monkeypatch):
    import jax
    tr = Tracer()
    tr.enable()
    assert tr._annotation is jax.profiler.TraceAnnotation
    monkeypatch.delitem(sys.modules, "jax")
    tr.enable()
    assert tr._annotation is None
    with tr.span("plain"):
        pass
    assert [e["name"] for e in tr.to_dicts()] == ["plain"]
    tr.disable()
    assert tr.span("off") is _NULL_SPAN


def test_block_span_in_the_faults_and_parametric_finishers(engine):
    from repro.faults.model import make_scenarios
    from repro.opt import ParametricSpace
    adj = AdjacencySpace(n_chiplets=8)
    par = ParametricSpace(chiplet_counts=(16,))
    sc = make_scenarios(adj, "single", top_k=2)
    rng = np.random.default_rng(1)
    enable_tracing()
    try:
        p_faults = engine.evaluate_genomes_faults_async(
            adj, adj.sample(rng, 4), sc.link_fail, sc.node_fail)
        p_faults.result()
        p_par = engine.evaluate_genomes_async(par, par.sample(rng, 4))
        p_par.result()
    finally:
        disable_tracing()
    spans = TRACER.to_dicts()
    for outer, pending in (("genomes.finish_faults", p_faults),
                           ("genomes.finish", p_par)):
        fin, = _named(spans, outer)
        blk = [b for b in _named(spans, "genomes.block") if _inside(b, fin)]
        assert len(blk) == 1
        assert pending.block_s >= blk[0]["dur_us"] / 1e6


def test_fault_run_names_its_reduction_reports_and_rows(engine):
    """A traced fault-aware ``AsyncStepper`` run: ``faults.reduce`` inside
    each generation's finalize, ``genomes.reports`` after the block inside
    ``genomes.finish_faults``, the dispatch span's padded ``rows``, and the
    counters ``faults.rows`` (P * F per generation, unpadded),
    ``faults.disconnected_rows`` and ``faults.infeasible``, which the
    telemetry prints."""
    from repro.faults.model import single_link_faults
    from repro.faults.objectives import REACH_EPS, FaultSetup
    from repro.obs import report as obs_report

    space = AdjacencySpace(n_chiplets=N)
    sc = single_link_faults(space, top_k=8)
    F, pop, gens = sc.n_scenarios, 6, 3             # P pads to 8
    ev = PopulationEvaluator(space, engine=engine,
                             faults=FaultSetup(scenarios=sc))
    want = {"faults.rows": 0, "faults.disconnected_rows": 0,
            "faults.infeasible": 0}
    finalize = ev._finalize_faults

    def keep(genomes, grid):
        out = finalize(genomes, grid)
        want["faults.rows"] += grid.latency.size
        want["faults.disconnected_rows"] += int(
            (grid.reachable_fraction < 1.0 - REACH_EPS).sum())
        want["faults.infeasible"] += int(
            (out.extra["disconnect_prob"] > 1e-12).sum())
        return out

    ev._finalize_faults = keep
    opt = EvolutionarySearch(space, ev, seed=3, pop_size=pop)
    before = {c: _counter(c) for c in want}
    enable_tracing()
    try:
        AsyncStepper(opt, gens).run()
    finally:
        disable_tracing()
    spans = TRACER.to_dicts()
    assert want["faults.rows"] == gens * pop * F
    assert {c: _counter(c) - v for c, v in before.items()} == want
    fins = _named(spans, "genomes.finish_faults")
    assert len(fins) == gens
    for fin in fins:
        blk, = [b for b in _named(spans, "genomes.block") if _inside(b, fin)]
        rep, = [r for r in _named(spans, "genomes.reports")
                if _inside(r, fin)]
        assert _end(blk) <= rep["ts_us"] + EPS_US
    finals = [s for s in _named(spans, "opt.finalize")
              if s["attrs"]["path"] == "faults"]
    reduces = _named(spans, "faults.reduce")
    assert len(reduces) == len(finals) == gens
    assert all(_within_any(r, finals) for r in reduces)
    assert all(r["attrs"]["rows"] == pop * F for r in reduces)
    dispatches = _named(spans, "genomes.dispatch_faults")
    assert [d["attrs"]["rows"] for d in dispatches] == [8 * F] * gens
    tele = obs_report.telemetry(obs_metrics.REGISTRY.snapshot())
    assert tele["faults"]["rows"] >= want["faults.rows"]
