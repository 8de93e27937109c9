"""Plain reference for one adjacency-genome design under a batch of fault
scenarios, independent of the program under test (it imports nothing from
``src/``; the pristine design comes from ``reference.py``).

A fault scenario is a set of dead links and dead chiplets. Faults are
runtime events: the design keeps its manufactured geometry (radix, PHY
assignment, link latencies and bandwidths of ``reference.py``), and under
each scenario

* a dead link vanishes from the link graph;
* a dead chiplet loses every incident link, relays nothing, and neither
  sources nor sinks traffic;
* routing is rebuilt on what is left (hop-count shortest paths, next hop
  = lowest-id neighbour among those closest to the destination);
* latency and throughput are taken over the delivered traffic (pairs of
  live chiplets that can still reach each other); a scenario in which
  nothing is delivered scores (``BIG``, 0.0);
* ``reachable_fraction`` is the delivered share of the offered traffic.

``fold`` then reduces a design's scenarios to the robust columns a
fault-aware search optimizes: scenario-weighted expectations, the worst
case (largest latency, smallest throughput), the probability mass of
scenarios that strand any traffic, the smallest delivered share, and the
pristine scenario's values.

Where it departs from the program's own description: it computes in
float64 (``proxy_dtype`` selects another precision for the control) where
the program computes the grid in float32 and folds it in float64; and it
evaluates each distinct degraded link graph of a design once, where the
program evaluates every (design, scenario) row (a scenario that kills only
links the design does not have leaves the same graph, so the answers are
the same by construction).
"""
from __future__ import annotations

import numpy as np

import reference

BIG = 1e18           # the score of a scenario that delivers nothing
REACH_EPS = 1e-6     # reachable fraction below 1 - REACH_EPS: disconnected


def scenarios(config: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(link_fail [F, G], node_fail [F, n], weights [F]) of the
    configuration's ``faults`` block: single-link failures of the
    ``top_k`` pair slots with the longest grid distance on the placement
    grid (ties by slot index), the pristine design first, uniform
    weights."""
    f = config["faults"]
    if f["model"] != "single" or f.get("weights", "uniform") != "uniform":
        raise ValueError("the reference knows uniform single-link faults "
                         "only")
    n = int(config["space"]["n_chiplets"])
    pu, pv = np.triu_indices(n, 1)
    G = len(pu)
    _, cols = reference.grid_dims(n)
    col, row = np.arange(n) % cols, np.arange(n) // cols
    length = np.abs(col[pu] - col[pv]) + np.abs(row[pu] - row[pv])
    slots = sorted(range(G), key=lambda g: (-length[g], g))[:int(f["top_k"])]
    link_fail = np.zeros((len(slots), G), bool)
    link_fail[np.arange(len(slots)), slots] = True
    if f["include_pristine"]:
        link_fail = np.concatenate([np.zeros((1, G), bool), link_fail])
    node_fail = np.zeros((len(link_fail), n), bool)
    weights = np.full(len(link_fail), 1.0 / len(link_fail))
    return link_fail, node_fail, weights


def pristine(bits, config: dict, report_dtype=np.float64) -> dict:
    """``reference.evaluate`` of the undamaged design, with its geometry:
    ``adj``, ``lat_e`` (link latencies) and ``bw_e`` (link bandwidths).

    ``reference.py`` builds the geometry inside ``evaluate`` and hands it
    to its proxies; the geometry is taken there, so both references share
    one construction of it."""
    seen = {}
    proxies = reference._proxies

    def keep(adj, dist, lat_e, bw_e, n, internal, dtype):
        seen.update(adj=adj, lat_e=lat_e, bw_e=bw_e)
        return proxies(adj, dist, lat_e, bw_e, n, internal, dtype)

    reference._proxies = keep
    try:
        out = reference.evaluate(bits, config, np.float64, report_dtype)
    finally:
        reference._proxies = proxies
    out.update(seen)
    return out


def degraded(adj, lat_e, bw_e, link_fail, node_fail, config: dict,
             dtype=np.float64) -> dict:
    """(latency, throughput, reachable_fraction) [F] of one design whose
    pristine link graph is ``adj`` [n, n] under every scenario
    (``link_fail`` [F, G] over pair slots, ``node_fail`` [F, n])."""
    n = len(adj)
    pu, pv = np.triu_indices(n, 1)
    alive = ~np.asarray(node_fail, bool)
    # a dead slot that the design has, or a dead chiplet, changes the graph
    dead_links = np.asarray(link_fail, bool) & adj[pu, pv][None, :]
    key = np.concatenate([dead_links, ~alive], axis=1)
    seen: dict[bytes, int] = {}
    inverse = np.array([seen.setdefault(row.tobytes(), len(seen))
                        for row in np.packbits(key, axis=1)])
    uniq = key[np.unique(inverse, return_index=True)[1]]
    U = len(uniq)
    a = np.broadcast_to(adj, (U, n, n)).copy()
    f_idx, g_idx = np.nonzero(uniq[:, :len(pu)])
    a[f_idx, pu[g_idx], pv[g_idx]] = False
    a[f_idx, pv[g_idx], pu[g_idx]] = False
    live = ~uniq[:, len(pu):]
    a &= live[:, :, None] & live[:, None, :]
    out = _proxies_batch(a, live, lat_e, bw_e, n,
                         config["chiplet"]["internal_latency"], dtype)
    return {k: v[inverse] for k, v in out.items()}


def _hop_distances_batch(a: np.ndarray) -> np.ndarray:
    """All-pairs hop counts of a stack of link graphs [B, n, n] by
    breadth-first frontiers (inf = unreachable)."""
    B, n, _ = a.shape
    af = a.astype(np.float32)
    dist = np.full((B, n, n), np.inf)
    reached = np.broadcast_to(np.eye(n, dtype=bool), (B, n, n)).copy()
    frontier = reached.copy()
    k = 0
    while frontier.any():
        dist[frontier] = k
        frontier = (np.matmul(frontier.astype(np.float32), af) > 0) & ~reached
        reached |= frontier
        k += 1
    return dist


def _proxies_batch(a, live, lat_e, bw_e, n, internal, dtype) -> dict:
    """Degraded latency and throughput proxies and the delivered share of
    a stack of link graphs [U, n, n] with their live chiplets [U, n], by
    the dynamic programming of ``reference._proxies`` over each graph."""
    dist = _hop_distances_batch(a)
    ids = np.arange(n)
    # next hop [U, u, d]: lowest-id neighbour v of u with fewest hops to d
    score = np.where(np.isfinite(dist), dist * (n + 1) + ids[None, :, None],
                     np.inf)                                  # [U, v, d]
    cand = np.where(a[:, :, :, None], score[:, None, :, :], np.inf)
    nh = np.argmin(cand, axis=2)                              # [U, u, d]
    routed = np.isfinite(dist) & (ids[:, None] != ids[None, :])[None]
    nh = np.where(routed, nh, ids[None, :, None])

    traffic = np.full((n, n), 1.0 / (n * (n - 1)))
    np.fill_diagonal(traffic, 0.0)
    t = traffic.astype(dtype)
    deliver = routed & live[:, :, None] & live[:, None, :]
    tm = np.where(deliver, t[None], dtype(0))                 # [U, s, d]
    offered = np.sum(t)
    t_tot = np.sum(tm, axis=(1, 2))

    step = (dtype(internal) + np.where(a, lat_e[None], 0.0)).astype(dtype)
    dmax = int(dist[np.isfinite(dist)].max(initial=0))
    cost = np.zeros(a.shape, dtype)             # cost[f, u, d], path u -> d
    for k in range(1, dmax + 1):
        f, u, d = np.nonzero(dist == k)
        h = nh[f, u, d]
        cost[f, u, d] = step[f, u, h] + cost[f, h, d]
    path = np.where(routed, cost + dtype(internal), dtype(0))
    safe = np.where(t_tot > 0, t_tot, dtype(1))
    latency = np.sum(tm * path, axis=(1, 2)) / safe

    load = tm.copy()                            # load[f, u, d] leaving u
    for k in range(dmax, 0, -1):
        f, u, d = np.nonzero(dist == k)
        np.add.at(load, (f, nh[f, u, d], d), load[f, u, d])
    flow = np.zeros(a.shape, dtype)             # directed u -> next hop
    f, u, d = np.nonzero(routed)
    np.add.at(flow, (f, u, nh[f, u, d]), load[f, u, d])
    und = flow + flow.swapaxes(1, 2)
    bw = np.broadcast_to(bw_e.astype(dtype), und.shape)
    ratio = np.where(und > 0, bw / np.where(und > 0, und, dtype(1)),
                     dtype(np.inf))
    throughput = np.min(ratio, axis=(1, 2)) * t_tot
    some = t_tot > 0
    return {"latency": np.where(some, latency.astype(np.float64), BIG),
            "throughput": np.where(some, throughput.astype(np.float64), 0.0),
            "reachable_fraction": (t_tot / offered).astype(np.float64)}


def fold(grid: dict, weights, pristine_index: int = 0) -> dict:
    """A design's robust columns from its scenarios (``degraded``'s
    output), as the fault-aware search defines them."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    lat, thr = grid["latency"], grid["throughput"]
    reach = grid["reachable_fraction"]
    return {"expected_latency": float(lat @ w),
            "expected_throughput": float(thr @ w),
            "worst_latency": float(lat.max()),
            "worst_throughput": float(thr.min()),
            "pristine_latency": float(lat[pristine_index]),
            "pristine_throughput": float(thr[pristine_index]),
            "disconnect_prob": float((reach < 1.0 - REACH_EPS) @ w),
            "min_reachable_fraction": float(reach.min())}


def evaluate(bits, config: dict, link_fail=None, node_fail=None,
             weights=None, proxy_dtype=np.float64,
             report_dtype=np.float64) -> dict:
    """Reference of one design under the configuration's scenarios (or
    the given ones): the pristine reference's ``max_degree``,
    ``connected`` and report columns, the per-scenario ``grid`` and the
    robust columns of ``fold``. A design that is not connected gets no
    proxies (repair guarantees connection)."""
    if link_fail is None:
        link_fail, node_fail, weights = scenarios(config)
    base = pristine(bits, config, report_dtype)
    out = {k: base[k] for k in ("max_degree", "connected", "n_links",
                                "total_chiplet_area", "interposer_area",
                                "power", "cost", "reachable_fraction")}
    if not base["connected"]:
        return out
    grid = degraded(base["adj"], base["lat_e"], base["bw_e"], link_fail,
                    node_fail, config, proxy_dtype)
    out["grid"] = grid
    out.update(fold(grid, weights))
    return out
