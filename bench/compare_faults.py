"""The comparison that decides ``correct`` in the fault cells: the robust
columns of designs the timed path evaluated, against the plain reference
(``reference_faults.py``) under the configuration's scenarios.

Compared numbers (each with its limit from ``limits/<cell>.json``):

* ``lat_rel_err`` / ``thr_rel_err``: the widest relative gap between the
  program's and the reference's latency / throughput over the worst-case,
  expected and pristine columns of the sample. The expected columns are
  there so that a wrong answer in one scenario shows even where that
  scenario is not the worst;
* ``reach_abs_err``: the widest absolute gap over ``disconnect_prob`` and
  ``min_reachable_fraction``;
* ``report_rel_err``: the widest relative gap over the pristine report
  columns (as ``compare.py``);
* ``repair_violations``: sampled designs whose largest degree exceeds the
  repaired bound (max_degree + 1) or whose link graph is disconnected.

A design that holds none of the failed links reads its pristine answer in
every scenario, so only designs that some scenario degrades test the
grid's masks; ``degraded_designs`` counts them among the compared.
"""
from __future__ import annotations

import numpy as np

import compare
import reference_faults

LAT_COLUMNS = ("worst_latency", "expected_latency", "pristine_latency")
THR_COLUMNS = ("worst_throughput", "expected_throughput",
               "pristine_throughput")
REACH_COLUMNS = ("disconnect_prob", "min_reachable_fraction")
COLUMNS = LAT_COLUMNS + THR_COLUMNS + REACH_COLUMNS
NUMBERS = ("lat_rel_err", "thr_rel_err", "reach_abs_err", "report_rel_err",
           "repair_violations")


def compare_designs(designs, config: dict, proxy_dtype=np.float64,
                    report_dtype=np.float64) -> dict:
    """designs: iterable of dicts with ``bits`` (0/1 genome), the
    program's robust ``COLUMNS`` and its report columns. Returns the
    compared numbers; the dtypes select the reference's precision (the
    control runs it lower)."""
    link_fail, node_fail, weights = reference_faults.scenarios(config)
    bound = int(config["space"]["max_degree"]) + 1
    lat = thr = reach = rep = 0.0
    violations = count = hit = 0
    for d in designs:
        hit += bool(degraded_rows(np.asarray(d["bits"])[None], link_fail,
                                  node_fail).any())
        ref = reference_faults.evaluate(d["bits"], config, link_fail,
                                        node_fail, weights, proxy_dtype,
                                        report_dtype)
        count += 1
        if ref["max_degree"] > bound or not ref["connected"]:
            violations += 1
            continue
        for col in LAT_COLUMNS:
            lat = compare._worst(lat, compare.rel_gap(d[col], ref[col]))
        for col in THR_COLUMNS:
            thr = compare._worst(thr, compare.rel_gap(d[col], ref[col]))
        for col in REACH_COLUMNS:
            reach = compare._worst(reach, _abs_gap(d[col], ref[col]))
        for col in compare.REPORT_COLUMNS:
            rep = compare._worst(rep, compare.rel_gap(d[col], ref[col]))
    if count == 0:          # nothing compared proves nothing
        lat = thr = reach = rep = float("nan")
    return {"lat_rel_err": lat, "thr_rel_err": thr, "reach_abs_err": reach,
            "report_rel_err": rep, "repair_violations": float(violations),
            "designs": count, "degraded_designs": hit}


def degraded_rows(bits, link_fail, node_fail) -> np.ndarray:
    """[R, F]: True where scenario f changes the link graph of design r
    (``bits`` [R, G]): it kills a link the design has, or a chiplet. A
    scenario that kills only links a design lacks leaves it pristine."""
    hit = (np.asarray(bits, bool)[:, None, :]
           & np.asarray(link_fail, bool)[None, :, :]).any(axis=2)
    return hit | np.asarray(node_fail, bool).any(axis=1)[None, :]


def control_designs(designs, config: dict) -> list[dict]:
    """The control: the reference in the next precision below the one the
    configuration states (proxies float32 -> bfloat16, reports float64 ->
    float32), put in the program's place on the same designs."""
    import ml_dtypes
    link_fail, node_fail, weights = reference_faults.scenarios(config)
    out = []
    for d in designs:
        low = reference_faults.evaluate(d["bits"], config, link_fail,
                                        node_fail, weights,
                                        ml_dtypes.bfloat16, np.float32)
        out.append({"bits": d["bits"],
                    **{k: low.get(k, float("nan")) for k in
                       COLUMNS + compare.REPORT_COLUMNS}})
    return out


def _abs_gap(got: float, want: float) -> float:
    """|got - want|; NaN when either side is not finite."""
    got, want = float(got), float(want)
    if not (np.isfinite(got) and np.isfinite(want)):
        return float("nan")
    return abs(got - want)


def record(run, numbers: dict) -> None:
    """Put the compared numbers beside their limits into the run."""
    for name in NUMBERS:
        run.check(name, float(numbers[name]), run.limits[name])
