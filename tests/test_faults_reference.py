"""The fused [P, F] fault grid and its robust columns against the
benchmark's plain fault reference (``bench/reference_faults.py``: float64
NumPy, nothing of the program imported), on seeded random genomes at a
small size: n=16, P=8, nine single-link scenarios (the pristine design
and the eight longest traces) plus one scenario with a dead chiplet.

Tolerances are those of ``tests/test_faults.py`` against
``faults/reference.py``: the program computes the grid in float32 with
another summation order than the float64 reference, which leaves
relative gaps of a few 1e-7 (rtol 1e-5 on latency and throughput); a
delivered share of exactly 1 is compared with an absolute floor (atol
1e-6) because float32 rounding of a sum of 240 shares moves it by ~1e-7.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.dse.engine import DseEngine
from repro.faults.model import FaultScenarios, single_link_faults
from repro.faults.objectives import FaultSetup, RobustObjectives
from repro.opt import PopulationEvaluator
from repro.opt.space import AdjacencySpace

BENCH = Path(__file__).resolve().parents[1] / "bench"
N, POP, TOP_K, DEAD = 16, 8, 8, 5


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, str(BENCH))
    try:
        import reference_faults
    finally:
        sys.path.remove(str(BENCH))
    return reference_faults


@pytest.fixture(scope="module")
def config():
    cfg = json.loads((BENCH / "configs" / "adj64-faults.json").read_text())
    cfg["space"]["n_chiplets"] = N
    cfg["faults"]["top_k"] = TOP_K
    return cfg


@pytest.fixture(scope="module")
def space():
    return AdjacencySpace(n_chiplets=N, max_degree=8)


@pytest.fixture(scope="module")
def scenarios(space):
    """Nine single-link scenarios and one with chiplet ``DEAD`` dead."""
    sc = single_link_faults(space, top_k=TOP_K)
    G = space.genome_length
    return FaultScenarios(
        link_fail=np.concatenate([sc.link_fail, np.zeros((1, G), bool)]),
        node_fail=np.concatenate([sc.node_fail,
                                  np.eye(N, dtype=bool)[DEAD:DEAD + 1]]),
        weights=np.full(sc.n_scenarios + 1, 1.0 / (sc.n_scenarios + 1)),
        names=sc.names + (f"chiplet[{DEAD}]",))


@pytest.fixture(scope="module")
def genomes(space):
    return space.repair(space.sample(np.random.default_rng(11), POP))


def test_reference_builds_the_programs_scenarios(ref, config, space):
    link_fail, node_fail, weights = ref.scenarios(config)
    sc = single_link_faults(space, top_k=TOP_K)
    assert link_fail.shape == (TOP_K + 1, space.genome_length)
    np.testing.assert_array_equal(link_fail, sc.link_fail)
    np.testing.assert_array_equal(node_fail, sc.node_fail)
    np.testing.assert_allclose(weights, sc.weights, rtol=1e-15)


def test_fault_grid_matches_the_plain_reference(ref, config, space,
                                                scenarios, genomes):
    grid = DseEngine().evaluate_genomes_faults_async(
        space, genomes, scenarios.link_fail, scenarios.node_fail).result()
    assert grid.latency.shape == (POP, TOP_K + 2)
    for i, g in enumerate(genomes):
        want = ref.evaluate(g, config, scenarios.link_fail,
                            scenarios.node_fail, scenarios.weights)["grid"]
        np.testing.assert_allclose(grid.latency[i], want["latency"],
                                   rtol=1e-5)
        np.testing.assert_allclose(grid.throughput[i], want["throughput"],
                                   rtol=1e-5)
        np.testing.assert_allclose(grid.reachable_fraction[i],
                                   want["reachable_fraction"],
                                   rtol=1e-5, atol=1e-6)
    # the dead chiplet strands its own traffic: 2 (n - 1) of n (n - 1)
    np.testing.assert_allclose(grid.reachable_fraction[:, -1],
                               1.0 - 2.0 / N, rtol=1e-6)


@pytest.mark.parametrize("mode", ["worst", "expected"])
def test_robust_columns_match_the_reference_fold(ref, config, space,
                                                 scenarios, genomes, mode):
    """What the optimizer receives (``PopulationEvaluator`` with a
    ``FaultSetup``) against the reference's fold of its own grid."""
    setup = FaultSetup(scenarios=scenarios,
                       objectives=RobustObjectives(mode=mode))
    ev = PopulationEvaluator(space, faults=setup)(genomes)
    for i, g in enumerate(genomes):
        want = ref.evaluate(g, config, scenarios.link_fail,
                            scenarios.node_fail, scenarios.weights)
        for col in ("worst_latency", "expected_latency", "pristine_latency",
                    "worst_throughput", "expected_throughput",
                    "pristine_throughput"):
            assert ev.extra[col][i] == pytest.approx(want[col], rel=1e-5)
        for col in ("disconnect_prob", "min_reachable_fraction"):
            assert ev.extra[col][i] == pytest.approx(want[col], abs=1e-6)
        assert ev.latency[i] == pytest.approx(want[f"{mode}_latency"],
                                              rel=1e-5)
        assert ev.throughput[i] == pytest.approx(
            want[f"{mode}_throughput"], rel=1e-5)
        # every design loses traffic with the dead chiplet: infeasible
        assert want["disconnect_prob"] > 0 and not ev.feasible[i]
