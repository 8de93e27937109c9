"""The plain reference against the program at a small size, and the
control (the reference one precision lower) against the reference."""
import json

import numpy as np
import pytest

import compare
import reference
from harness import BENCH


@pytest.fixture(scope="module")
def designs():
    from repro.dse.engine import DseEngine
    from repro.opt import AdjacencySpace

    config = json.loads((BENCH / "configs" / "adj64.json").read_text())
    config["space"]["n_chiplets"] = 24
    space = AdjacencySpace(n_chiplets=24, max_degree=8)
    genomes = space.sample(np.random.default_rng(3), 12)
    res = DseEngine().evaluate_genomes(space, genomes)
    out = []
    for i, g in enumerate(genomes):
        d = {"bits": g, "latency": res.latency[i],
             "throughput": res.throughput[i]}
        for col in compare.REPORT_COLUMNS:
            d[col] = getattr(res.reports, col)[i]
        out.append(d)
    return config, out


def test_program_matches_reference(designs):
    config, ds = designs
    got = compare.compare_designs(ds, config)
    assert got["repair_violations"] == 0
    assert got["lat_rel_err"] < 1e-5
    assert got["thr_rel_err"] < 1e-5
    assert got["report_rel_err"] < 1e-12


def test_control_is_rejected(designs):
    config, ds = designs
    limits = json.loads((BENCH / "limits" / "adj64.search.json").read_text())
    got = compare.compare_designs(compare.control_designs(ds, config), config)
    assert got["lat_rel_err"] > limits["lat_rel_err"]
    assert got["thr_rel_err"] > limits["thr_rel_err"]
    assert got["report_rel_err"] > limits["report_rel_err"]


def test_repair_violation_is_counted(designs):
    config, ds = designs
    bad = dict(ds[0])
    bits = np.zeros_like(bad["bits"])
    bits[:12] = 1                       # chiplet 0 with 12 links, rest apart
    bad["bits"] = bits
    assert compare.compare_designs([bad], config)["repair_violations"] == 1


def test_phy_offsets_follow_the_paper():
    assert reference.phy_offsets(4, 2.0) == [(1.0, 2.0), (2.0, 1.0),
                                             (1.0, 0.0), (0.0, 1.0)]
    assert len(reference.phy_offsets(8, 2.0)) == 8
    assert reference.phy_offsets(9, 9.0)[0] == (0.0, 9.0)
