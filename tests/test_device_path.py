"""Device genome→metrics pipeline tests (ISSUE 4).

Covers: batched on-device routing tables vs the per-destination Dijkstra /
up*/down* references (exact tie-break equivalence on random graphs), proxy
metric equivalence of the host and device paths (adjacency + every
registered parametric topology), the vectorized population repair
(bit-identical to the sequential oracle, property-tested), the scatter-free
flow accumulation, and the jit-cache stability probe (one compile per
bucketed shape across a whole run).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.graph import DenseGraph
from repro.dse.engine import DseEngine
from repro.opt import (
    AdjacencySpace, Budgets, EvolutionarySearch, OptRunner,
    ParametricSpace, PopulationEvaluator,
)
from repro.opt.space import DEFAULT_TOPOLOGIES
from repro.routing.tables import (
    _edge_costs, dijkstra_lowest_id_table_reference,
    updown_random_table, updown_random_table_reference,
)
from repro.routing.device import (
    hops_next_hop_batch, next_hop_lowest_id_batch,
    updown_random_table_via_device,
)


def _random_graph(n: int, rng: np.random.Generator,
                  relay_frac: float = 1.0) -> DenseGraph:
    """Random connected graph with optional non-relay vertices."""
    adj = np.full((n, n), np.inf)
    perm = rng.permutation(n)
    for i in range(1, n):
        j = perm[rng.integers(0, i)]
        adj[perm[i], j] = adj[j, perm[i]] = 1.0
    for _ in range(2 * n):
        u, v = rng.integers(0, n, 2)
        if u != v:
            adj[u, v] = adj[v, u] = 1.0
    relay = rng.random(n) < relay_frac
    return DenseGraph(n=n, n_chiplets=n, node_weight=np.zeros(n),
                      adj_lat=adj, adj_bw=np.ones((n, n)),
                      lengths=np.zeros((n, n)), relay=relay)


# ---------------------------------------------------------------------------
# batched routing tables vs host references (exact tie-break equivalence)
# ---------------------------------------------------------------------------

def test_batched_dijkstra_tables_match_reference_exactly():
    rng = np.random.default_rng(0)
    graphs = [_random_graph(int(rng.integers(5, 20)), rng,
                            relay_frac=1.0 if t % 2 == 0 else 0.7)
              for t in range(6)]
    for g in graphs:
        ref = dijkstra_lowest_id_table_reference(g)
        got = next_hop_lowest_id_batch(
            _edge_costs(g, "hops")[None], np.asarray(g.relay, bool)[None])[0]
        assert np.array_equal(got, ref)


def test_batched_dijkstra_tables_stacked_batch():
    """One batched call over several same-size graphs == per-graph calls."""
    rng = np.random.default_rng(1)
    graphs = [_random_graph(12, rng, relay_frac=0.8) for _ in range(4)]
    costs = np.stack([_edge_costs(g, "hops") for g in graphs])
    relays = np.stack([np.asarray(g.relay, bool) for g in graphs])
    got = next_hop_lowest_id_batch(costs, relays)
    for b, g in enumerate(graphs):
        assert np.array_equal(got[b], dijkstra_lowest_id_table_reference(g))


def test_hops_next_hop_batch_matches_reference_exactly():
    """The specialized all-relay hops builder (BFS matmuls + integer-encoded
    argmin) must reproduce the Dijkstra reference bit for bit."""
    rng = np.random.default_rng(2)
    for _ in range(6):
        n = int(rng.integers(5, 24))
        g = _random_graph(n, rng)
        adj = np.isfinite(g.adj_lat)
        np.fill_diagonal(adj, False)
        got = np.asarray(hops_next_hop_batch(jnp.asarray(adj[None])))[0]
        assert np.array_equal(got, dijkstra_lowest_id_table_reference(g))


def test_updown_via_device_matches_reference_rng_stream():
    """Device phase-automaton relaxation + host seeded choice must equal the
    reference oracle exactly — same candidates, same RNG stream."""
    rng = np.random.default_rng(3)
    for t in range(4):
        n = int(rng.integers(6, 16))
        g = _random_graph(n, rng, relay_frac=1.0 if t % 2 == 0 else 0.75)
        ref = updown_random_table_reference(g, seed=t)
        assert np.array_equal(updown_random_table(g, seed=t), ref)
        assert np.array_equal(updown_random_table_via_device(g, seed=t), ref)


# ---------------------------------------------------------------------------
# proxy-metric equivalence: host path vs device path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,maxd,seed", [(10, 4, 3), (12, 4, 11), (16, 5, 7)])
def test_adjacency_device_metrics_match_host(n, maxd, seed):
    space = AdjacencySpace(n_chiplets=n, max_degree=maxd)
    genomes = space.sample(np.random.default_rng(seed), 6)
    engine = DseEngine()
    host = engine.evaluate_points(space.decode(genomes),
                                  n_pad=space.max_nodes, round_hops=True)
    dev = engine.evaluate_genomes(space, genomes)
    np.testing.assert_allclose(dev.latency, host.latency, rtol=1e-5)
    np.testing.assert_allclose(dev.throughput, host.throughput, rtol=1e-5)


def test_adjacency_device_reports_match_host_reports():
    from repro.core.reports import report_arrays
    space = AdjacencySpace(n_chiplets=12, max_degree=4)
    genomes = space.sample(np.random.default_rng(5), 5)
    engine = DseEngine()
    dev = engine.evaluate_genomes(space, genomes)
    want = report_arrays([pt.build() for pt in space.decode(genomes)])
    np.testing.assert_allclose(dev.reports.total_chiplet_area,
                               want.total_chiplet_area, rtol=1e-12)
    np.testing.assert_allclose(dev.reports.interposer_area,
                               want.interposer_area, rtol=1e-12)
    np.testing.assert_allclose(dev.reports.power, want.power, rtol=1e-12)
    np.testing.assert_allclose(dev.reports.cost, want.cost, rtol=1e-12)


def test_parametric_device_metrics_match_host_all_registered_topologies():
    """Every registered parametric topology (plus a router topology) must
    evaluate identically through the structure-table device path."""
    space = ParametricSpace(topologies=DEFAULT_TOPOLOGIES,
                            chiplet_counts=(16,))
    genomes = space.enumerate_genomes()
    engine = DseEngine()
    host = engine.evaluate_points(space.decode(genomes),
                                  n_pad=space.max_nodes, round_hops=True)
    dev = engine.evaluate_genomes(space, genomes)
    np.testing.assert_allclose(dev.latency, host.latency, rtol=1e-5)
    np.testing.assert_allclose(dev.throughput, host.throughput, rtol=1e-5)


def test_parametric_device_handles_router_topologies_and_updown():
    space = ParametricSpace(topologies=("double_butterfly", "mesh"),
                            chiplet_counts=(16,),
                            routings=("dijkstra_lowest_id", "updown_random"))
    genomes = space.enumerate_genomes()
    engine = DseEngine()
    host = engine.evaluate_points(space.decode(genomes),
                                  n_pad=space.max_nodes, round_hops=True)
    dev = engine.evaluate_genomes(space, genomes)
    np.testing.assert_allclose(dev.latency, host.latency, rtol=1e-5)
    np.testing.assert_allclose(dev.throughput, host.throughput, rtol=1e-5)


def test_updown_adjacency_space_falls_back_to_host_path():
    space = AdjacencySpace(n_chiplets=8, max_degree=3,
                           routing="updown_random")
    engine = DseEngine()
    assert not engine.supports_genomes(space)
    with pytest.raises(ValueError, match="evaluate_points"):
        engine.evaluate_genomes(space, space.sample(np.random.default_rng(0), 2))
    ev = PopulationEvaluator(space, engine=engine)
    assert not ev._use_device_path()
    out = ev(space.sample(np.random.default_rng(1), 3))
    assert np.isfinite(out.latency).all()


def test_evaluate_genomes_rejects_unrepaired_overdegree():
    space = AdjacencySpace(n_chiplets=8, max_degree=2)
    bad = np.ones((1, space.genome_length), np.int64)   # degree 7 everywhere
    with pytest.raises(ValueError, match="repair"):
        DseEngine().evaluate_genomes(space, bad)


# ---------------------------------------------------------------------------
# scatter-free flow accumulation
# ---------------------------------------------------------------------------

def test_edge_flows_load_matches_pair_walk():
    from repro.core.throughput import edge_flows, edge_flows_load
    rng = np.random.default_rng(7)
    for _ in range(3):
        n = int(rng.integers(6, 18))
        g = _random_graph(n, rng)
        adj = np.isfinite(g.adj_lat)
        np.fill_diagonal(adj, False)
        nh = np.asarray(hops_next_hop_batch(jnp.asarray(adj[None])))[0]
        t = rng.random((n, n)).astype(np.float32)
        np.fill_diagonal(t, 0.0)
        f_pairs = np.asarray(edge_flows(jnp.asarray(nh), jnp.asarray(t)))
        f_load = np.asarray(edge_flows_load(jnp.asarray(nh), jnp.asarray(t)))
        np.testing.assert_allclose(f_load, f_pairs, rtol=1e-5, atol=1e-6)


def test_edge_flows_adaptive_matches_fixed_scan():
    from repro.core.throughput import edge_flows
    rng = np.random.default_rng(8)
    n = 12
    g = _random_graph(n, rng)
    adj = np.isfinite(g.adj_lat)
    np.fill_diagonal(adj, False)
    nh = jnp.asarray(np.asarray(
        hops_next_hop_batch(jnp.asarray(adj[None])))[0])
    t = jnp.asarray(rng.random((n, n)).astype(np.float32))
    f_scan = np.asarray(edge_flows(nh, t, max_hops=n - 1))
    f_adap = np.asarray(edge_flows(nh, t, max_hops=n - 1, adaptive=True))
    np.testing.assert_allclose(f_adap, f_scan, rtol=1e-6)


# ---------------------------------------------------------------------------
# vectorized repair (bit-identical to the sequential oracle)
# ---------------------------------------------------------------------------

def test_repair_batch_bit_identical_to_reference():
    for n, maxd, seed in [(8, 1, 0), (10, 4, 1), (12, 3, 2), (5, 2, 4)]:
        space = AdjacencySpace(n_chiplets=n, max_degree=maxd)
        rng = np.random.default_rng(seed)
        for density in (0.0, 0.1, 0.5, 1.0):
            raw = (rng.random((8, space.genome_length))
                   < density).astype(np.int64)
            got = space.repair(raw)
            want = np.stack([space._repair_one(g.copy()) for g in raw % 2])
            assert np.array_equal(got, want), (n, maxd, density)


def test_repair_handles_empty_and_full_genomes():
    space = AdjacencySpace(n_chiplets=9, max_degree=3)
    zeros = np.zeros((2, space.genome_length), np.int64)
    ones = np.ones((2, space.genome_length), np.int64)
    for raw in (zeros, ones):
        got = space.repair(raw)
        want = np.stack([space._repair_one(g.copy()) for g in raw])
        assert np.array_equal(got, want)


def _mixed_population(space: AdjacencySpace, seed: int) -> np.ndarray:
    """16 rows whose link counts differ widely: empty, full, near-tree
    (a random spanning tree less a few links), dense rows the degree cap
    trims, and sparse random rows that are mostly disconnected."""
    n, G = space.n_chiplets, space.genome_length
    rng = np.random.default_rng(seed)
    rows = [np.zeros(G, np.int64), np.ones(G, np.int64)]
    for cut in (0, 1, 3):
        perm = rng.permutation(n)
        tree = np.zeros(G, np.int64)
        for i in range(1, n):
            a, b = sorted((perm[i], perm[rng.integers(0, i)]))
            tree[space._pair_index(a, b)] = 1
        tree[rng.choice(np.nonzero(tree)[0], cut, replace=False)] = 0
        rows.append(tree)
    for density in (0.5, 0.2, space.init_density):
        rows.append((rng.random(G) < density).astype(np.int64))
    while len(rows) < 16:
        density = rng.uniform(0.1, 1.5) / n
        rows.append((rng.random(G) < density).astype(np.int64))
    return np.stack(rows)[rng.permutation(16)]


@pytest.mark.parametrize("n,seed", [(64, 0), (64, 1), (128, 2), (128, 3)])
def test_repair_bit_identical_at_larger_n_with_uneven_link_counts(n, seed):
    """Rows with very different link counts exercise the rank-ordered
    union loop's padding; the output must equal the sequential oracle."""
    space = AdjacencySpace(n_chiplets=n, max_degree=8)
    raw = _mixed_population(space, seed)
    got = space.repair(raw)
    want = np.stack([space._repair_one(g.copy()) for g in raw])
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n", [64, 128])
def test_repair_connect_span_counts_rows_and_steps(n):
    from repro.obs.trace import TRACER, disable_tracing, enable_tracing

    space = AdjacencySpace(n_chiplets=n, max_degree=8)
    raw = _mixed_population(space, n)
    capped = raw.copy()
    space._degree_cap(capped)
    bad = [b for b in capped if not _connected(space, b)]
    assert bad
    enable_tracing()
    try:
        space.repair(raw)
    finally:
        disable_tracing()
    spans = [s for s in TRACER.to_dicts() if s["name"] == "repair.connect"]
    assert len(spans) == 1
    attrs = spans[0]["attrs"]
    assert attrs["rows"] == len(bad)
    assert 0 < attrs["steps"] <= max(int(b.sum()) for b in bad)


def _connected(space: AdjacencySpace, bits: np.ndarray) -> bool:
    n = space.n_chiplets
    adj = np.zeros((n, n), bool)
    adj[space.pair_u, space.pair_v] = bits.astype(bool)
    adj |= adj.T
    seen = {0}
    frontier = [0]
    while frontier:
        u = frontier.pop()
        for v in np.nonzero(adj[u])[0]:
            if v not in seen:
                seen.add(int(v))
                frontier.append(int(v))
    return len(seen) == n


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.integers(4, 12), st.integers(1, 5), st.integers(0, 10_000),
           st.floats(0.0, 1.0))
    def test_repair_property_connected_capped_and_matches_oracle(
            n, maxd, seed, density):
        """Satellite property: repaired genomes are always connected and
        degree-capped (soft cap +1 for connectivity joins), and the
        vectorized path equals the sequential oracle bit for bit."""
        space = AdjacencySpace(n_chiplets=n, max_degree=maxd)
        rng = np.random.default_rng(seed)
        raw = (rng.random((3, space.genome_length)) < density).astype(np.int64)
        got = space.repair(raw)
        want = np.stack([space._repair_one(g.copy()) for g in raw])
        assert np.array_equal(got, want)
        deg = space.degrees(got)
        assert (deg.max(axis=1) <= maxd + 1).all()
        assert (deg.min(axis=1) >= 1).all()
        for bits in got:
            assert _connected(space, bits)
except ImportError:  # pragma: no cover - hypothesis is a test extra
    pass


# ---------------------------------------------------------------------------
# jit-cache stability: one compile per (bucketed P, n) shape per run
# ---------------------------------------------------------------------------

def test_one_compile_per_shape_across_ten_generations():
    import jax
    from repro.dse.genomes import COMPILE_COUNTS, reset_compile_counts

    jax.clear_caches()
    reset_compile_counts()
    space = AdjacencySpace(n_chiplets=11, max_degree=4)
    ev = PopulationEvaluator(space,
                             budgets=Budgets(max_interposer_area=2500.0))
    opt = EvolutionarySearch(space, ev, seed=0, pop_size=10)
    OptRunner(opt).run(10)
    adjacency_keys = {k: v for k, v in COMPILE_COUNTS.items()
                      if k[0] == "adjacency"}
    assert len(adjacency_keys) == 1, adjacency_keys
    assert all(v == 1 for v in adjacency_keys.values()), adjacency_keys
    assert ev.n_evals == 100


def test_one_compile_per_shape_parametric():
    import jax
    from repro.dse.genomes import COMPILE_COUNTS, reset_compile_counts

    jax.clear_caches()
    reset_compile_counts()
    space = ParametricSpace(topologies=("mesh", "torus"), chiplet_counts=(9,))
    ev = PopulationEvaluator(space)
    opt = EvolutionarySearch(space, ev, seed=1, pop_size=6)
    OptRunner(opt).run(10)
    parametric_keys = {k: v for k, v in COMPILE_COUNTS.items()
                       if k[0] == "parametric"}
    assert len(parametric_keys) == 1, parametric_keys
    assert all(v == 1 for v in parametric_keys.values()), parametric_keys


def test_population_bucketing_is_stable():
    from repro.dse.genomes import bucket_population
    assert bucket_population(1) == 8
    assert bucket_population(8) == 8
    assert bucket_population(9) == 16
    assert bucket_population(16) == 16
    assert bucket_population(17) == 32
    assert bucket_population(24) == 32
    assert bucket_population(10, multiple=3) == 18


def test_node_bucketing_is_stable():
    from repro.dse.genomes import NODE_TILE, node_bucket
    assert node_bucket(2) == 8
    assert node_bucket(8) == 8
    assert node_bucket(9) == 16
    assert node_bucket(12) == 16
    assert node_bucket(16) == 16
    assert node_bucket(17) == 32
    assert node_bucket(64) == 64
    # Large-n tier (ISSUE 6): tile multiples, not powers of two — a
    # 576-chiplet HexaMesh pads to 576, not 1024 (3.2x memory otherwise).
    assert node_bucket(33) == 48
    assert node_bucket(144) == 144
    assert node_bucket(250) == 256
    assert node_bucket(576) == 576
    for n in range(9, 600, 7):
        b = node_bucket(n)
        assert b >= n and b % NODE_TILE == 0
        assert b - n < NODE_TILE


def test_degree_cap_scan_cache_does_not_fragment():
    """Repair's degree-cap candidate lists vary in length every call; the
    pow2 bucketing must keep the jitted scan's compile cache to the few
    ladder rungs actually hit (node_bucket's tile-16 padding must NOT leak
    into this path — it would compile once per 16-wide rung)."""
    space = AdjacencySpace(n_chiplets=24, max_degree=2)
    rng = np.random.default_rng(0)
    buckets = set()
    from repro.opt.space import _pow2_bucket
    for density in (0.15, 0.3, 0.45, 0.6, 0.75, 0.9, 1.0):
        raw = (rng.random((4, space.genome_length)) < density).astype(np.int64)
        over = space.degrees(raw) > space.max_degree
        cand = ((raw == 1) & (over[:, space.pair_u] |
                              over[:, space.pair_v])).any(axis=0)
        if cand.any():
            buckets.add(_pow2_bucket(int(cand.sum())))
        space.repair(raw)
    fn = getattr(space, "_cap_fn", None)
    assert fn is not None and len(buckets) >= 1
    assert fn._cache_size() == len(buckets)


def test_parametric_spaces_share_one_compile_across_node_counts():
    """Satellite (ISSUE 5): heterogeneous-n parametric spaces pad to a
    shared node bucket — evaluating spaces with different max node counts
    must reuse ONE compiled program instead of compiling per exact n."""
    import jax
    from repro.dse.genomes import COMPILE_COUNTS, reset_compile_counts

    jax.clear_caches()
    reset_compile_counts()
    engine = DseEngine()
    rng = np.random.default_rng(0)
    # max_nodes 9 and 12 -> both bucket to n=16
    for counts in ((9,), (9, 12)):
        space = ParametricSpace(topologies=("mesh", "torus"),
                                chiplet_counts=counts)
        genomes = space.repair(rng.integers(0, 4, (8, 4)))
        res = engine.evaluate_genomes(space, genomes)
        assert np.isfinite(res.latency).all()
    parametric_keys = {k: v for k, v in COMPILE_COUNTS.items()
                       if k[0] == "parametric"}
    assert len(parametric_keys) == 1, parametric_keys
    assert all(v == 1 for v in parametric_keys.values()), parametric_keys
