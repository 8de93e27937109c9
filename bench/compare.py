"""The comparison that decides ``correct``: designs the timed path
evaluated, against the plain reference (``reference.py``).

Compared numbers (each with its limit from ``limits/<cell>.json``):

* ``lat_rel_err`` / ``thr_rel_err``: the widest relative gap between the
  program's latency / throughput proxy and the reference's, over the
  sample;
* ``report_rel_err``: the widest relative gap over the report columns
  (chiplet area, interposer area, power, cost, connected-pair fraction);
* ``repair_violations``: sampled designs whose largest degree exceeds the
  repaired bound (max_degree + 1) or whose link graph is disconnected.
"""
from __future__ import annotations

import numpy as np

import reference

REPORT_COLUMNS = ("total_chiplet_area", "interposer_area", "power", "cost",
                  "reachable_fraction")


def rel_gap(got: float, want: float) -> float:
    """|got - want| / |want|; NaN when either side is not finite."""
    got, want = float(got), float(want)
    if not (np.isfinite(got) and np.isfinite(want)):
        return float("nan")
    return abs(got - want) / max(abs(want), 1e-300)


def compare_designs(designs, config: dict, proxy_dtype=np.float64,
                    report_dtype=np.float64) -> dict:
    """designs: iterable of dicts with ``bits`` (0/1 genome) and the
    program's ``latency``, ``throughput`` and report columns. Returns the
    compared numbers; the dtypes select the reference's precision (the
    control runs it lower)."""
    bound = int(config["space"]["max_degree"]) + 1
    lat = thr = rep = 0.0
    violations = 0
    count = 0
    for d in designs:
        ref = reference.evaluate(d["bits"], config, proxy_dtype,
                                 report_dtype)
        count += 1
        if ref["max_degree"] > bound or not ref["connected"]:
            violations += 1
            continue
        lat = _worst(lat, rel_gap(d["latency"], ref["latency"]))
        thr = _worst(thr, rel_gap(d["throughput"], ref["throughput"]))
        for col in REPORT_COLUMNS:
            rep = _worst(rep, rel_gap(d[col], ref[col]))
    if count == 0:          # nothing compared proves nothing
        lat = thr = rep = float("nan")
    return {"lat_rel_err": lat, "thr_rel_err": thr, "report_rel_err": rep,
            "repair_violations": float(violations), "designs": count}


def control_designs(designs, config: dict) -> list[dict]:
    """The control: the reference in the next precision below the one the
    configuration states (proxies float32 -> bfloat16, reports float64 ->
    float32), put in the program's place on the same designs."""
    import ml_dtypes
    out = []
    for d in designs:
        low = reference.evaluate(d["bits"], config, ml_dtypes.bfloat16,
                                 np.float32)
        out.append({"bits": d["bits"], **{k: low[k] for k in
                    ("latency", "throughput") + REPORT_COLUMNS}})
    return out


def _worst(acc: float, gap: float) -> float:
    """Running maximum in which a NaN (a non-finite value) wins."""
    if np.isnan(acc) or np.isnan(gap):
        return float("nan")
    return max(acc, gap)


def record(run, numbers: dict) -> None:
    """Put the compared numbers beside their limits into the run."""
    for name in ("lat_rel_err", "thr_rel_err", "report_rel_err",
                 "repair_violations"):
        run.check(name, float(numbers[name]), run.limits[name])


def sample_rows(n_rows: int, k: int, seed: int, always=()) -> list[int]:
    """k row indices drawn from the seed among n_rows, plus ``always``."""
    rng = np.random.default_rng([seed, 0x5EED])
    keep = list(dict.fromkeys(int(i) for i in always))
    pool = np.setdiff1d(np.arange(n_rows), keep)
    take = min(max(k - len(keep), 0), len(pool))
    keep += rng.choice(pool, size=take, replace=False).tolist()
    return keep
