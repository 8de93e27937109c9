"""Operations and bytes the load-propagation algorithm needs, counted from
the unpadded node count, the designs and their routed diameter, never from
the program's padding or its fixed hop bound.

Per design with n chiplets and a batch (the rows one chip evaluates in one
call) whose longest routed path has D hops, ``load_propagate`` has to:

* per hop, add the load standing at every (node, destination) into the
  accumulated load W and move it one hop along the routing table: 2 n^2
  additions, for D hops;
* contract W with the next-hop table into the directed link flows: n^2
  additions;
* read the routing table and the initial load and write W and the flows:
  four n x n arrays of 4-byte words.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def load_propagate_cost(n: int, rows: int, diameter: int) -> tuple[float, float]:
    """(operations, bytes) of one load propagation over ``rows`` designs."""
    ops = rows * n * n * (2.0 * diameter + 1.0)
    nbytes = rows * 4.0 * n * n * 4.0
    return ops, nbytes


def routed_diameter(bits: np.ndarray, n: int) -> int:
    """Longest hop-count shortest path of one connected design (the
    routing is shortest-path in hops, so this is its routed diameter)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import shortest_path

    pu, pv = np.triu_indices(n, 1)
    on = np.nonzero(np.asarray(bits) % 2)[0]
    g = coo_matrix((np.ones(len(on)), (pu[on], pv[on])), shape=(n, n))
    dist = shortest_path(g, directed=False, unweighted=True)
    return int(dist[np.isfinite(dist)].max())


def min_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_flop = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "compute")
