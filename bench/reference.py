"""Plain reference for one adjacency-genome design, independent of the
program under test (it imports nothing from ``src/``).

Given the bit genome of a free-form chiplet topology (one bit per chiplet
pair u < v, in ``numpy.triu_indices(n, 1)`` order) and a configuration file
of ``bench/configs``, it rebuilds the design the way the RapidChiplet paper
describes it and computes, in float64:

* the repair guarantees: the largest chiplet degree and whether the link
  graph is connected;
* the geometry: one square chiplet type whose PHY count is the design's
  radix (largest degree), grid placement, greedy nearest-PHY assignment,
  Manhattan link lengths, link latencies and bump-limited bandwidths;
* the routing: hop-count shortest paths, next hop = lowest-id neighbour
  among those closest to the destination;
* the latency proxy (traffic-weighted mean path cost, every vertex and
  edge weight on the path) and the throughput proxy
  (min over links of bandwidth / undirected flow, times total traffic);
* the report columns: chiplet area, interposer area, power, cost, and the
  fraction of ordered chiplet pairs that are connected.

``proxy_dtype`` / ``report_dtype`` run the same arithmetic in a lower
precision: the control that a sound comparison must reject.
"""
from __future__ import annotations

import math

import numpy as np

PHY_TIE_TOL = 1e-9


def grid_dims(n: int) -> tuple[int, int]:
    """Nearly square rows x cols = n with rows <= cols."""
    r = int(math.floor(math.sqrt(n)))
    while n % r:
        r -= 1
    return r, n // r


def phy_offsets(radix: int, side: float) -> list[tuple[float, float]]:
    """PHY positions on a square chiplet (paper Fig. 3): 4 side midpoints
    up to radix 4, sides and corners up to 8, else evenly round the
    perimeter starting at the top-left corner."""
    w = h = side
    if radix <= 4:
        pts = [(w / 2, h), (w, h / 2), (w / 2, 0.0), (0.0, h / 2)]
        return pts[:radix]
    if radix <= 8:
        pts = [(w / 2, h), (w, h / 2), (w / 2, 0.0), (0.0, h / 2),
               (0.0, 0.0), (w, 0.0), (w, h), (0.0, h)]
        return pts[:radix]
    per = 2 * (w + h)
    out = []
    for i in range(radix):
        s = (i / radix) * per
        if s < w:
            out.append((s, h))
        elif s < w + h:
            out.append((w, h - (s - w)))
        elif s < 2 * w + h:
            out.append((w - (s - w - h), 0.0))
        else:
            out.append((0.0, s - 2 * w - h))
    return out


def die_cost(area: float, wafer_cost: float, wafer_radius: float,
             defect_density: float, critical_level_ratio: float,
             alpha: float, dtype=np.float64) -> float:
    """Wafer cost over good dies: dies per wafer from the usual geometric
    estimate, yield from the negative-binomial model."""
    a = dtype(area)
    r = dtype(wafer_radius)
    dpw = np.pi * r * r / a - np.pi * dtype(2.0) * r / np.sqrt(dtype(2.0) * a)
    dpw = max(np.floor(dpw), dtype(1.0))
    y = (dtype(1.0) + a * dtype(defect_density) * dtype(critical_level_ratio)
         / dtype(alpha)) ** (-dtype(alpha))
    return float(dtype(wafer_cost) / (dpw * y))


def hop_distances(adj: np.ndarray) -> np.ndarray:
    """All-pairs hop counts by breadth-first frontiers (inf = unreachable)."""
    n = len(adj)
    a = adj.astype(np.float32)
    dist = np.full((n, n), np.inf)
    reached = np.eye(n, dtype=bool)
    frontier = reached.copy()
    k = 0
    while frontier.any():
        dist[frontier] = k
        nxt = (frontier.astype(np.float32) @ a) > 0
        frontier = nxt & ~reached
        reached |= frontier
        k += 1
    return dist


def next_hops(adj: np.ndarray, dist: np.ndarray) -> np.ndarray:
    """next_hop[u, d]: the lowest-id neighbour of u with the fewest hops
    to d; next_hop[d, d] = d."""
    n = len(adj)
    nh = np.tile(np.arange(n)[:, None], (1, n))
    for u in range(n):
        nb = np.nonzero(adj[u])[0]
        if len(nb) == 0:
            continue
        score = dist[nb, :] * (n + 1) + nb[:, None]
        pick = nb[np.argmin(score, axis=0)]
        nh[u] = np.where(np.arange(n) == u, u, pick)
    return nh


def evaluate(bits, config: dict, proxy_dtype=np.float64,
             report_dtype=np.float64) -> dict:
    """Reference metrics of one design; see the module docstring."""
    space = config["space"]
    chip = config["chiplet"]
    pkg = config["packaging"]
    tech = config["technology"]
    n = int(space["n_chiplets"])
    if space["traffic_pattern"] != "random_uniform":
        raise ValueError("the reference knows random_uniform traffic only")
    pu, pv = np.triu_indices(n, 1)
    bits = np.asarray(bits, np.int64) % 2
    if bits.shape != (len(pu),):
        raise ValueError(f"genome has {bits.shape} genes, expected "
                         f"{len(pu)} for n={n}")
    set_idx = np.nonzero(bits)[0]
    adj = np.zeros((n, n), bool)
    adj[pu[set_idx], pv[set_idx]] = True
    adj[pv[set_idx], pu[set_idx]] = True
    deg = adj.sum(axis=1)
    dist = hop_distances(adj)
    connected = bool(np.isfinite(dist).all())

    # --- geometry (float64) ---
    radix = max(int(deg.max(initial=0)), 1)
    area = chip["base_area"] + chip["area_per_phy"] * radix
    side = math.sqrt(area)
    phys = phy_offsets(radix, side)
    rows, cols = grid_dims(n)
    pitch = side + chip["spacing"]
    col = np.arange(n) % cols
    row = np.arange(n) // cols
    posx, posy = col * pitch, row * pitch
    grid_len = np.abs(col[pu] - col[pv]) + np.abs(row[pu] - row[pv])
    order = sorted(set_idx.tolist(), key=lambda g: (grid_len[g], g))
    used: dict[int, set] = {}
    pick: dict[tuple[int, int], int] = {}
    for g in order:
        u, v = int(pu[g]), int(pv[g])
        for a, b in ((u, v), (v, u)):
            tx, ty = posx[b] + side / 2, posy[b] + side / 2
            taken = used.setdefault(a, set())
            best, best_d = None, np.inf
            for pi, (ox, oy) in enumerate(phys):
                if pi in taken:
                    continue
                d = abs(posx[a] + ox - tx) + abs(posy[a] + oy - ty)
                if best is None or d < best_d - PHY_TIE_TOL * max(best_d, 1.0):
                    best, best_d = pi, d
            taken.add(best)
            pick[(a, g)] = best
    n_links = len(set_idx)
    lat_e = np.full((n, n), np.inf)
    bw_e = np.zeros((n, n))
    len_sum = 0.0
    bump = chip["bump_area_fraction"]
    for g in set_idx:
        u, v = int(pu[g]), int(pv[g])
        ax, ay = np.add((posx[u], posy[u]), phys[pick[(u, g)]])
        bx, by = np.add((posx[v], posy[v]), phys[pick[(v, g)]])
        if pkg["link_routing"] == "euclidean":
            length = math.hypot(ax - bx, ay - by)
        else:
            length = abs(ax - bx) + abs(ay - by)
        len_sum += length
        lat = (pkg["link_latency_const"] + pkg["link_latency_per_mm"] * length
               + 2 * chip["phy_latency"])
        bws = [max(int(np.floor(area * (bump / deg[x])
                                / pkg["bump_pitch"] ** 2))
                   - pkg["non_data_wires"], 0) for x in (u, v)]
        lat_e[u, v] = lat_e[v, u] = lat
        bw_e[u, v] = bw_e[v, u] = min(bws)

    # --- routing and proxies (proxy_dtype) ---
    out = {"max_degree": int(deg.max(initial=0)), "connected": connected,
           "n_links": n_links}
    if connected:
        out.update(_proxies(adj, dist, lat_e, bw_e, n,
                            chip["internal_latency"], proxy_dtype))
    else:
        out.update(latency=float("nan"), throughput=float("nan"))

    # --- reports (report_dtype) ---
    rd = report_dtype
    ia = float(rd((posx.max() + side) * (posy.max() + side)))
    power = (rd(n) * (rd(chip["base_power"]) + rd(chip["power_per_phy"])
                      * rd(radix))
             + rd(pkg["link_power_const"]) * rd(n_links)
             + rd(pkg["link_power_per_mm"]) * rd(len_sum))
    itech = dict(wafer_cost=tech["wafer_cost"]
                 * tech["interposer_wafer_cost_factor"],
                 wafer_radius=tech["wafer_radius"],
                 defect_density=tech["defect_density"]
                 * tech["interposer_defect_density_factor"],
                 critical_level_ratio=tech["critical_level_ratio"],
                 alpha=tech["clustering_alpha"])
    ctech = dict(wafer_cost=tech["wafer_cost"],
                 wafer_radius=tech["wafer_radius"],
                 defect_density=tech["defect_density"],
                 critical_level_ratio=tech["critical_level_ratio"],
                 alpha=tech["clustering_alpha"])
    cost = (rd(n) * rd(die_cost(area, dtype=rd, **ctech))
            + rd(die_cost(ia, dtype=rd, **itech))
            + rd(pkg["packaging_cost_base"])
            + rd(pkg["packaging_cost_per_mm2"]) * rd(ia))
    comp = np.unique(_components(adj), return_counts=True)[1]
    out.update(
        total_chiplet_area=float(rd(n) * rd(area)),
        interposer_area=ia,
        power=float(power),
        cost=float(cost),
        reachable_fraction=float(np.sum(comp * (comp - 1)) / (n * (n - 1))))
    return out


def _components(adj: np.ndarray) -> np.ndarray:
    """Component label (smallest member) of every vertex."""
    n = len(adj)
    label = np.arange(n)
    while True:
        nb = np.where(adj, label[None, :], n).min(axis=1)
        new = np.minimum(label, nb)
        if np.array_equal(new, label):
            return label
        label = new


def _proxies(adj, dist, lat_e, bw_e, n, internal, dtype) -> dict:
    """Latency and throughput proxies under random-uniform traffic, by
    dynamic programming over destinations in hop order: a path's cost is
    the cost of its first step plus the cost from the next hop, and the
    load that leaves u toward d is u's own traffic plus every load routed
    into u toward d."""
    nh = next_hops(adj, dist)
    traffic = np.full((n, n), 1.0 / (n * (n - 1)))
    np.fill_diagonal(traffic, 0.0)
    t = traffic.astype(dtype)
    step = (dtype(internal) + np.where(adj, lat_e, 0.0)).astype(dtype)
    ids = np.arange(n)
    dmax = int(dist.max())
    cost = np.zeros((n, n), dtype)               # cost[u, d], path u -> d
    for k in range(1, dmax + 1):
        u, d = np.nonzero(dist == k)
        h = nh[u, d]
        cost[u, d] = step[u, h] + cost[h, d]
    path = np.where(ids[:, None] == ids[None, :], dtype(0), cost
                    + dtype(internal))
    total = np.sum(t)
    latency = np.sum(t * path) / total

    load = t.copy()                              # load[u, d] leaving u
    for k in range(dmax, 0, -1):
        u, d = np.nonzero(dist == k)
        np.add.at(load, (nh[u, d], d), load[u, d])
    flow = np.zeros((n, n), dtype)               # directed u -> next hop
    off = ids[:, None] != ids[None, :]
    np.add.at(flow, (np.broadcast_to(ids[:, None], (n, n))[off], nh[off]),
              load[off])
    und = flow + flow.T
    bw = bw_e.astype(dtype)
    ratio = np.where(und > 0, bw / np.where(und > 0, und, dtype(1)),
                     dtype(np.inf))
    throughput = np.min(ratio) * total
    return {"latency": float(latency), "throughput": float(throughput)}
