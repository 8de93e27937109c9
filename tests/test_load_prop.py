"""The shared load-propagation primitive (ISSUE 5): Pallas kernel vs XLA
fallback vs the independent pair-walk oracle, backend dispatch, the
hop-loop scaffolding shared by the fixed-length and adaptive variants, and
the hops each backend reports (with the genome pipelines' counters)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.load_prop import LOAD_PROP_BACKENDS, default_backend
from repro.kernels.ops import load_propagate, load_propagate_counted


def _random_adj(n: int, rng: np.random.Generator, extra: int = 0):
    """Random spanning tree plus ``extra`` random links: connected."""
    adj = np.zeros((n, n), bool)
    perm = rng.permutation(n)
    for i in range(1, n):
        j = perm[rng.integers(0, i)]
        adj[perm[i], j] = adj[j, perm[i]] = True
    for _ in range(extra):
        u, v = rng.integers(0, n, 2)
        if u != v:
            adj[u, v] = adj[v, u] = True
    return adj


def _random_table(n: int, rng: np.random.Generator):
    """Connected random graph -> (next_hop table, traffic) pair."""
    from repro.routing.device import hops_next_hop_batch

    adj = _random_adj(n, rng, extra=2 * n)
    nh = np.asarray(hops_next_hop_batch(jnp.asarray(adj[None])))[0]
    t = rng.random((n, n)).astype(np.float32)
    np.fill_diagonal(t, 0.0)
    return nh, t


def _load0(nh: np.ndarray, t: np.ndarray) -> np.ndarray:
    l0 = t.T.copy()
    np.fill_diagonal(l0, 0.0)
    return l0.astype(np.float32)


def test_xla_flow_matches_pair_walk_oracle():
    """The primitive's flow must equal the independent scatter pair walk."""
    from repro.core.throughput import edge_flows

    rng = np.random.default_rng(0)
    for _ in range(3):
        n = int(rng.integers(5, 16))
        nh, t = _random_table(n, rng)
        _, flow = load_propagate(jnp.asarray(nh), jnp.asarray(_load0(nh, t)),
                                 backend="xla")
        walk = np.asarray(edge_flows(jnp.asarray(nh), jnp.asarray(t),
                                     use_kernel=True))
        np.testing.assert_allclose(np.asarray(flow), walk,
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("adaptive", [True, False])
def test_pallas_interpret_matches_xla(adaptive):
    rng = np.random.default_rng(1)
    for trial in range(2):
        n = int(rng.integers(5, 12))
        nh, t = _random_table(n, rng)
        l0 = jnp.asarray(_load0(nh, t))
        w_x, f_x = load_propagate(jnp.asarray(nh), l0, backend="xla",
                                  adaptive=adaptive)
        w_p, f_p = load_propagate(jnp.asarray(nh), l0,
                                  backend="pallas_interpret")
        np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_x),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(f_p), np.asarray(f_x),
                                   rtol=1e-5, atol=1e-6)


def test_pallas_interpret_matches_xla_batched_and_unreachable():
    """Batched inputs, including a disconnected design whose unreachable
    pairs accumulate diagonal load for the full hop bound."""
    rng = np.random.default_rng(2)
    n = 8
    nh1, t1 = _random_table(n, rng)
    nh2 = np.tile(np.arange(n, dtype=nh1.dtype)[:, None], (1, n))  # isolated
    t2 = rng.random((n, n)).astype(np.float32)
    np.fill_diagonal(t2, 0.0)
    nhs = jnp.asarray(np.stack([nh1, nh2]))
    l0s = jnp.asarray(np.stack([_load0(nh1, t1), _load0(nh2, t2)]))
    w_x, f_x = load_propagate(nhs, l0s, backend="xla")
    w_p, f_p = load_propagate(nhs, l0s, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(f_p), np.asarray(f_x),
                               rtol=1e-5, atol=1e-6)
    # the isolated design's traffic never drains: every unit pays max_hops
    # self-hops, and the flow sits on the diagonal
    diag = np.diag(np.asarray(f_p)[1])
    np.testing.assert_allclose(diag, t2.sum(axis=1) * (n - 1), rtol=1e-5)


def test_adaptive_equals_fixed_on_connected_designs():
    rng = np.random.default_rng(3)
    n = 12
    nh, t = _random_table(n, rng)
    l0 = jnp.asarray(_load0(nh, t))
    w_a, f_a = load_propagate(jnp.asarray(nh), l0, adaptive=True,
                              backend="xla")
    w_f, f_f = load_propagate(jnp.asarray(nh), l0, adaptive=False,
                              backend="xla")
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_f))
    np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f_f))


def test_default_backend_dispatch(monkeypatch):
    monkeypatch.delenv("REPRO_LOAD_PROP_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    expected = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert default_backend() == expected
    monkeypatch.setenv("REPRO_LOAD_PROP_BACKEND", "pallas_interpret")
    assert default_backend() == "pallas_interpret"
    monkeypatch.setenv("REPRO_LOAD_PROP_BACKEND", "bogus")
    with pytest.raises(ValueError, match="REPRO_LOAD_PROP_BACKEND"):
        default_backend()
    monkeypatch.delenv("REPRO_LOAD_PROP_BACKEND")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_backend() == "pallas"
    assert set(LOAD_PROP_BACKENDS) == {
        "pallas", "pallas_interpret", "xla",
        "pallas_tiled", "pallas_tiled_interpret", "xla_blocked"}


def test_edge_flows_default_path_uses_primitive():
    """edge_flows (default) and edge_flows_load are the same primitive now;
    both must still match the scatter pair walk."""
    from repro.core.throughput import edge_flows, edge_flows_load

    rng = np.random.default_rng(4)
    n = 10
    nh, t = _random_table(n, rng)
    f_def = np.asarray(edge_flows(jnp.asarray(nh), jnp.asarray(t)))
    f_load = np.asarray(edge_flows_load(jnp.asarray(nh), jnp.asarray(t)))
    f_walk = np.asarray(edge_flows(jnp.asarray(nh), jnp.asarray(t),
                                   use_kernel=True))
    np.testing.assert_allclose(f_def, f_walk, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(f_load, f_walk, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# adaptive hop loops: bit-identical to the fixed n - 1 loop, and the hops
# every backend reports
# ---------------------------------------------------------------------------

def _table(adj: np.ndarray) -> np.ndarray:
    from repro.routing.device import hops_next_hop_batch
    return np.asarray(hops_next_hop_batch(jnp.asarray(adj[None])))[0]


def _star(n: int) -> np.ndarray:
    adj = np.zeros((n, n), bool)
    adj[0, 1:] = adj[1:, 0] = True
    return adj


def _path(n: int) -> np.ndarray:
    adj = np.zeros((n, n), bool)
    i = np.arange(n - 1)
    adj[i, i + 1] = adj[i + 1, i] = True
    return adj


def _uniform_load(n: int, live: np.ndarray) -> np.ndarray:
    """Dest-major load: every off-diagonal pair of ``live`` nodes carries a
    positive amount, every other pair none."""
    rng = np.random.default_rng(n + int(live.sum()))
    t = rng.random((n, n)).astype(np.float32) + 0.5
    t *= live[:, None] & live[None, :]
    np.fill_diagonal(t, 0.0)
    return t.T.copy()


def _drain_hops(nh: np.ndarray, load: np.ndarray, max_hops: int) -> int:
    """Hops until every loaded pair (load[d, u] > 0) has arrived, by walking
    the table; ``max_hops`` when a loaded pair self-loops short of its
    destination and never drains."""
    longest = 0
    for d, u in zip(*np.nonzero(load)):
        cur, hops = u, 0
        while cur != d:
            nxt = nh[cur, d]
            if nxt == cur:
                return max_hops
            cur, hops = nxt, hops + 1
        longest = max(longest, hops)
    return longest


def _batch_mixed(n, rng):
    """A star (D=2), a path (D=n-1) and two random connected tables."""
    live = np.ones(n, bool)
    nhs = [_table(_star(n)), _table(_path(n))]
    loads = [_uniform_load(n, live), _uniform_load(n, live)]
    for _ in range(2):
        nh, t = _random_table(n, rng)
        nhs.append(nh)
        loads.append(_load0(nh, t))
    return nhs, loads


def _batch_padded(n, rng):
    """Designs on the first n - 3 nodes, padded to n with source rows that
    self-loop and carry no load (a router-padded table)."""
    m = n - 3
    live = np.arange(n) < m
    nhs, loads = [], []
    for adj_m in (_star(m), _path(m), _random_adj(m, rng, extra=m)):
        adj = np.zeros((n, n), bool)
        adj[:m, :m] = adj_m
        nhs.append(_table(adj))
        loads.append(_uniform_load(n, live))
    return nhs, loads


def _batch_degraded(n, rng):
    """A design cut into two components by failed links, its load masked
    to the reachable pairs as the fault grid masks it, beside a pristine
    design."""
    adj = _path(n)
    adj[n // 2 - 1, n // 2] = adj[n // 2, n // 2 - 1] = False
    nh = _table(adj)
    ids = np.arange(n)
    reach = (nh != ids[:, None]) | (ids[:, None] == ids[None, :])
    load = _uniform_load(n, np.ones(n, bool)) * reach.T
    assert (load > 0).sum() < n * (n - 1)      # some pairs carry nothing
    nh2, t2 = _random_table(n, rng)
    return [nh, nh2], [load.astype(np.float32), _load0(nh2, t2)]


def _batch_isolated(n, rng):
    """The isolated design of the batched/unreachable test: its traffic
    self-loops and never drains, so it runs all max_hops."""
    nh1, t1 = _random_table(n, rng)
    nh2 = np.tile(np.arange(n, dtype=nh1.dtype)[:, None], (1, n))
    t2 = rng.random((n, n)).astype(np.float32)
    np.fill_diagonal(t2, 0.0)
    return [nh1, nh2], [_load0(nh1, t1), _load0(nh2, t2)]


_BATCHES = {"mixed": _batch_mixed, "padded": _batch_padded,
            "degraded": _batch_degraded, "isolated": _batch_isolated}
# tiles small enough that the tiled backends run several destination tiles
_TILES = {"pallas_tiled_interpret": 32, "xla_blocked": 4}


@pytest.mark.parametrize("backend", ["pallas_interpret",
                                     "pallas_tiled_interpret", "xla",
                                     "xla_blocked"])
@pytest.mark.parametrize("batch", sorted(_BATCHES))
def test_adaptive_hops_bit_identical_to_fixed(monkeypatch, backend, batch):
    """W and flow are equal bit for bit with the loop stopped at the drain
    and run the fixed n - 1 hops; the kernels report each design's own
    drain hops, the XLA loops the batch's largest."""
    if backend in _TILES:
        monkeypatch.setenv("REPRO_LOAD_PROP_TILE", str(_TILES[backend]))
    n = 12
    nhs, loads = _BATCHES[batch](n, np.random.default_rng(5))
    nh, l0 = jnp.asarray(np.stack(nhs)), jnp.asarray(np.stack(loads))
    w_a, f_a, h_a = load_propagate_counted(nh, l0, adaptive=True,
                                           backend=backend)
    w_f, f_f, h_f = load_propagate_counted(nh, l0, adaptive=False,
                                           backend=backend)
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_f))
    np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f_f))
    own = np.array([_drain_hops(h, l, n - 1) for h, l in zip(nhs, loads)])
    if batch == "mixed":
        assert list(own[:2]) == [2, n - 1]          # the star, the path
    if batch == "isolated":
        assert own[1] == n - 1                      # never drains
    want = np.full_like(own, own.max()) if backend.startswith("xla") else own
    np.testing.assert_array_equal(np.asarray(h_a), want)
    np.testing.assert_array_equal(np.asarray(h_f), np.full(len(nhs), n - 1))


# ---------------------------------------------------------------------------
# the genome pipelines' hop counters against a SciPy BFS
# ---------------------------------------------------------------------------

@pytest.fixture
def interpreted_pipelines(monkeypatch):
    """Pipelines traced afresh with the Pallas kernel in interpret mode
    (the jitted programs are cached per geometry, not per backend)."""
    from repro.dse import genomes

    monkeypatch.setenv("REPRO_LOAD_PROP_BACKEND", "pallas_interpret")
    genomes._adjacency_eval_fn.cache_clear()
    genomes._adjacency_faults_fn.cache_clear()
    yield
    genomes._adjacency_eval_fn.cache_clear()
    genomes._adjacency_faults_fn.cache_clear()


def _counter(name: str) -> float:
    from repro.obs import metrics as obs_metrics
    return sum(c.value for c in obs_metrics.REGISTRY.series("Counter", name))


def _bfs_diameters(space, genomes, link_fail, traffic) -> np.ndarray:
    """[P, F] longest hop distance over the pairs that carry traffic and
    can reach each other, by SciPy's BFS on each degraded adjacency."""
    from scipy.sparse.csgraph import shortest_path

    n = space.n_chiplets
    out = np.zeros((len(genomes), len(link_fail)), np.int64)
    for p, g in enumerate(genomes):
        for f, dead in enumerate(link_fail):
            adj = np.zeros((n, n), bool)
            on = (np.asarray(g) % 2).astype(bool) & ~dead
            adj[space.pair_u[on], space.pair_v[on]] = True
            dist = shortest_path(adj, unweighted=True, directed=False)
            ok = np.isfinite(dist) & (traffic > 0)
            out[p, f] = int(dist[ok].max()) if ok.any() else 0
    return out


@pytest.mark.parametrize("faults", [False, True], ids=["pristine", "faults"])
def test_pipeline_hop_counters_equal_routed_diameters(interpreted_pipelines,
                                                      faults):
    from repro.dse.genomes import AdjacencyPipeline
    from repro.faults.model import single_link_faults
    from repro.opt.space import AdjacencySpace
    from repro.utils.jaxcompat import make_auto_mesh

    space = AdjacencySpace(n_chiplets=16, max_degree=4)
    pipe = AdjacencyPipeline(space, make_auto_mesh((1,), ("data",)))
    genomes = space.repair(space.sample(np.random.default_rng(7), 8))
    h0 = _counter("kernels.load_propagate.hops")
    d0 = _counter("kernels.load_propagate.designs")
    if faults:
        sc = single_link_faults(space, top_k=3)
        link_fail = sc.link_fail
        pipe.evaluate_faults(genomes, sc.link_fail, sc.node_fail)
    else:
        link_fail = np.zeros((1, space.genome_length), bool)
        pipe.evaluate(genomes)
    want = _bfs_diameters(space, genomes, link_fail,
                          np.asarray(pipe._traffic))
    assert _counter("kernels.load_propagate.designs") - d0 == want.size
    assert _counter("kernels.load_propagate.hops") - h0 == want.sum()
    assert want.max() < pipe.max_hops == 15
    assert len(set(want.ravel())) > 1       # per design, not a batch max
    if faults:
        assert (want[:, 1:] != want[:, :1]).any()   # a scenario degrades
