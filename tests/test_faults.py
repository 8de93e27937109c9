"""Fault-aware evaluation + crash-proof harness tests (ISSUE 9).

Covers: the fused [P, F] fault grid vs the all-numpy host oracle for every
registered fault model (<= 1e-5), the pristine scenario reproducing the
unfaulted pipeline exactly, enumeration samplers vs loop oracles, the
robust-objective grid reductions, non-finite quarantine, the kernel
backend fallback ladder under forced (chaos) failures, the watchdog/retry
harness, graceful SIGTERM shutdown, sha256-checksummed optimizer
snapshots with warn-then-fall-back resume (including SIGKILL mid-write),
per-shard checksums in the array checkpoint format, and the
``reachable_fraction`` report column on partitioned topologies.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.dse.genomes import AdjacencyPipeline
from repro.faults.harness import (
    BackendChaosError, CheckpointCorruptError, WatchdogTimeout,
    call_with_retry, drain_quarantine, graceful_shutdown, json_digest,
    maybe_chaos_fail, quarantine_nonfinite, reset_fallback_warnings,
    run_with_fallback,
)
from repro.faults.model import (
    MODELS, double_link_faults, make_scenarios, single_chiplet_faults,
    single_link_faults,
)
from repro.faults.objectives import (
    FaultSetup, RobustObjectives, reduce_grid, robust_columns,
)
from repro.faults.reference import degraded_reference_grid
from repro.opt import (
    Budgets, EvolutionarySearch, OptRunner, PopulationEvaluator,
)
from repro.opt.space import AdjacencySpace
from repro.utils import env
from repro.utils.jaxcompat import make_auto_mesh


@pytest.fixture(scope="module")
def pipe8():
    space = AdjacencySpace(n_chiplets=8, max_degree=4)
    return AdjacencyPipeline(space, make_auto_mesh((1,), ("data",)))


@pytest.fixture(scope="module")
def genomes8(pipe8):
    rng = np.random.default_rng(0)
    return pipe8.space.repair(pipe8.space.sample(rng, 3))


# small scenario batches so the loop oracle stays fast
_MODEL_KW = {
    "iid": dict(p=0.15, n_scenarios=3, seed=1),
    "region": dict(radius=1.0, n_scenarios=3, seed=2),
    "single": dict(top_k=5),
    "double": dict(top_k=4),
    "chiplet": dict(),
}


# ---------------------------------------------------------------------------
# fused fault grid vs the all-numpy host oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", sorted(MODELS))
def test_fault_grid_matches_host_reference(pipe8, genomes8, model):
    """ISSUE 9 acceptance: the fused masked-batch eval matches the
    unbatched numpy reference to <= 1e-5 for EVERY fault model."""
    sc = make_scenarios(pipe8.space, model, **_MODEL_KW[model])
    grid = pipe8.evaluate_faults(genomes8, sc.link_fail, sc.node_fail)
    lat, thr, reach = degraded_reference_grid(pipe8.space, genomes8, sc)
    np.testing.assert_allclose(grid.latency, lat, rtol=1e-5)
    np.testing.assert_allclose(grid.throughput, thr, rtol=1e-5)
    np.testing.assert_allclose(grid.reachable_fraction, reach,
                               rtol=1e-5, atol=1e-6)


def test_pristine_scenario_reproduces_unfaulted_eval(pipe8, genomes8):
    """Scenario 0 (include_pristine=True) must equal the plain pipeline
    bit for bit — faults are a pure mask transform on the same program."""
    sc = single_link_faults(pipe8.space, top_k=3)
    assert sc.names[0] == "pristine"
    grid = pipe8.evaluate_faults(genomes8, sc.link_fail, sc.node_fail)
    plain = pipe8.evaluate(genomes8)
    np.testing.assert_array_equal(grid.latency[:, 0], plain.latency)
    np.testing.assert_array_equal(grid.throughput[:, 0], plain.throughput)
    np.testing.assert_array_equal(grid.reachable_fraction[:, 0],
                                  np.ones(len(genomes8), np.float32))


def test_faults_memory_accounts_for_the_grid_program(pipe8, genomes8):
    """The compiled grid program's own accounting: its arguments hold the
    bucketed population's bits and the scenario masks, its outputs the
    [P, F] grid's three columns plus the length sums; the pipeline
    evaluates as before afterwards."""
    sc = single_link_faults(pipe8.space, top_k=5)
    F, G = sc.n_scenarios, pipe8.space.genome_length
    mem = pipe8.faults_memory(len(genomes8), F)
    assert set(mem) == {"argument_bytes", "output_bytes", "temp_bytes",
                        "code_bytes"}
    bp = 8                                   # bucket of a population of 3
    assert mem["argument_bytes"] >= 4 * bp * G + F * G + F * pipe8.n
    assert mem["output_bytes"] >= 4 * (3 * bp * F + bp)
    assert mem["temp_bytes"] >= 0
    grid = pipe8.evaluate_faults(genomes8, sc.link_fail, sc.node_fail)
    assert grid.latency.shape == (len(genomes8), F)


def test_faulting_all_links_disconnects_everything(pipe8, genomes8):
    space = pipe8.space
    link_fail = np.ones((1, space.genome_length), bool)
    node_fail = np.zeros((1, space.n_chiplets), bool)
    grid = pipe8.evaluate_faults(genomes8, link_fail, node_fail)
    # self-traffic is zero in these patterns: nothing routes at all
    assert (grid.reachable_fraction[:, 0] <= 1e-6).all()
    assert (grid.throughput[:, 0] == 0.0).all()
    assert (grid.latency[:, 0] >= 1e9).all()


# ---------------------------------------------------------------------------
# enumeration samplers vs loop oracles
# ---------------------------------------------------------------------------

def test_single_link_enumeration_vs_loop_oracle(pipe8):
    space = pipe8.space
    G = space.genome_length
    sc = single_link_faults(space)          # exhaustive: F = G + pristine
    assert sc.n_scenarios == G + 1
    body = sc.link_fail[1:]
    assert (body.sum(axis=1) == 1).all()    # exactly one dead link each
    # every slot appears exactly once (loop-oracle coverage)
    assert sorted(np.nonzero(body)[1].tolist()) == list(range(G))
    assert not sc.node_fail.any()


def test_double_link_enumeration_vs_loop_oracle(pipe8):
    space = pipe8.space
    k = 4
    sc = double_link_faults(space, top_k=k)
    body = sc.link_fail[1:]
    assert len(body) == k * (k - 1) // 2
    assert (body.sum(axis=1) == 2).all()
    pairs = {tuple(np.nonzero(row)[0]) for row in body}
    assert len(pairs) == len(body)          # all unordered pairs distinct
    cand = {g for p in pairs for g in p}
    assert len(cand) == k


def test_chiplet_enumeration_and_weights(pipe8):
    space = pipe8.space
    sc = single_chiplet_faults(space)
    assert sc.n_scenarios == space.n_chiplets + 1
    assert (sc.node_fail[1:].sum(axis=1) == 1).all()
    assert sc.weights.sum() == pytest.approx(1.0)
    assert (sc.weights > 0).all()


def test_samplers_are_seeded(pipe8):
    a = make_scenarios(pipe8.space, "iid", p=0.1, n_scenarios=4, seed=3)
    b = make_scenarios(pipe8.space, "iid", p=0.1, n_scenarios=4, seed=3)
    c = make_scenarios(pipe8.space, "iid", p=0.1, n_scenarios=4, seed=4)
    np.testing.assert_array_equal(a.link_fail, b.link_fail)
    assert (a.link_fail != c.link_fail).any()
    with pytest.raises(ValueError):
        make_scenarios(pipe8.space, "no-such-model")


# ---------------------------------------------------------------------------
# robust objective reductions
# ---------------------------------------------------------------------------

def test_reduce_grid_and_robust_columns():
    lat = np.array([[10.0, 30.0], [20.0, 20.0]])
    thr = np.array([[5.0, 1.0], [4.0, 4.0]])
    reach = np.array([[1.0, 0.5], [1.0, 1.0]])
    w = np.array([0.5, 0.5])
    red = reduce_grid(lat, thr, reach, w)
    np.testing.assert_allclose(red["expected_latency"], [20.0, 20.0])
    np.testing.assert_allclose(red["worst_latency"], [30.0, 20.0])
    np.testing.assert_allclose(red["worst_throughput"], [1.0, 4.0])
    np.testing.assert_allclose(red["disconnect_prob"], [0.5, 0.0])
    np.testing.assert_allclose(red["min_reachable_fraction"], [0.5, 1.0])

    l, t, ok = robust_columns(red, RobustObjectives(mode="worst"))
    np.testing.assert_allclose(l, [30.0, 20.0])
    np.testing.assert_array_equal(ok, [False, True])
    l, t, ok = robust_columns(
        red, RobustObjectives(mode="expected", max_disconnect_prob=0.6))
    np.testing.assert_allclose(l, [20.0, 20.0])
    assert ok.all()
    with pytest.raises(ValueError):
        RobustObjectives(mode="median")


# ---------------------------------------------------------------------------
# quarantine + fallback ladder + watchdog + shutdown
# ---------------------------------------------------------------------------

def test_quarantine_nonfinite_penalizes_and_records():
    drain_quarantine()
    genomes = np.arange(8).reshape(4, 2)
    lat = np.array([1.0, np.nan, 3.0, np.inf])
    thr = np.array([1.0, 2.0, np.nan, 4.0])
    feasible = np.ones(4, bool)
    ql, qt, qf = quarantine_nonfinite(genomes, lat, thr, feasible,
                                      context="unit")
    assert np.isfinite(ql).all() and np.isfinite(qt).all()
    np.testing.assert_array_equal(qf, [True, False, False, False])
    assert ql[0] == 1.0 and qt[0] == 1.0          # good rows untouched
    assert ql[1] >= 1e29 and qt[1] == 0.0
    records = drain_quarantine()
    assert sorted(r["index"] for r in records) == [1, 2, 3]
    assert all(r["context"] == "unit" for r in records)
    assert drain_quarantine() == []


def test_fallback_ladder_walks_to_working_backend():
    reset_fallback_warnings()
    calls = []

    def attempt(bk):
        calls.append(bk)
        maybe_chaos_fail(bk)
        return bk

    with env.override(REPRO_CHAOS_BACKEND_FAIL="pallas_tiled,xla_blocked"):
        out = run_with_fallback("op", "pallas_tiled", attempt)
    assert out == "xla"
    assert calls == ["pallas_tiled", "xla_blocked", "xla"]


def test_fallback_ladder_strict_mode_raises():
    with env.override(REPRO_CHAOS_BACKEND_FAIL="xla",
                      REPRO_STRICT_BACKEND="1"):
        with pytest.raises(BackendChaosError):
            run_with_fallback("op", "xla",
                              lambda bk: maybe_chaos_fail(bk))


def test_fallback_ladder_exhausted_raises_first_error():
    with env.override(REPRO_CHAOS_BACKEND_FAIL="xla_blocked,xla"):
        with pytest.raises(BackendChaosError, match="xla_blocked"):
            run_with_fallback("op", "xla_blocked",
                              lambda bk: maybe_chaos_fail(bk))


def test_kernel_ops_fall_back_with_identical_results():
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    n = 10
    nh = rng.integers(0, n, (n, n)).astype(np.int32)
    nh[np.arange(n), np.arange(n)] = np.arange(n)
    t = rng.random((n, n)).astype(np.float32)
    want = ops.load_propagate(jnp.asarray(nh), jnp.asarray(t), max_hops=6)
    reset_fallback_warnings()
    with env.override(REPRO_CHAOS_BACKEND_FAIL="xla_blocked"):
        got = ops.load_propagate(jnp.asarray(nh), jnp.asarray(t),
                                 max_hops=6, backend="xla_blocked")
    np.testing.assert_allclose(np.asarray(want[0]), np.asarray(got[0]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(want[1]), np.asarray(got[1]),
                               rtol=1e-6)


def test_call_with_retry_bounded_backoff():
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("transient")
        return "ok"

    assert call_with_retry(flaky, retries=2, backoff=0.0) == "ok"
    assert len(attempts) == 3
    with pytest.raises(RuntimeError):
        call_with_retry(lambda: (_ for _ in ()).throw(RuntimeError("x")),
                        retries=1, backoff=0.0)


def test_call_with_retry_watchdog_timeout():
    def hang():
        time.sleep(10.0)

    t0 = time.perf_counter()
    with pytest.raises(WatchdogTimeout):
        call_with_retry(hang, retries=0, timeout_s=0.2, describe="hang")
    assert time.perf_counter() - t0 < 5.0


def test_watchdog_fires_from_worker_thread():
    # Regression: the watchdog used SIGALRM, which only works on the main
    # thread — the serve scheduler and any threaded caller got no deadline
    # at all. The monotonic-deadline watchdog must fire anywhere.
    box = {}

    def run():
        try:
            call_with_retry(time.sleep, 5.0, retries=0, timeout_s=0.2,
                            describe="sleepy")
        except BaseException as err:
            box["error"] = err

    t = threading.Thread(target=run)
    t0 = time.perf_counter()
    t.start()
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert isinstance(box.get("error"), WatchdogTimeout)
    assert time.perf_counter() - t0 < 5.0


def test_graceful_shutdown_flag_then_force():
    with graceful_shutdown(signals=("SIGUSR1",)) as flag:
        assert not flag.requested()
        os.kill(os.getpid(), signal.SIGUSR1)
        assert flag.requested()           # first signal: pollable flag
        with pytest.raises(KeyboardInterrupt):
            os.kill(os.getpid(), signal.SIGUSR1)


# ---------------------------------------------------------------------------
# fault-aware optimizer integration
# ---------------------------------------------------------------------------

def _make_fault_optimizer(seed=0, size=6, n=8, gens_space=None):
    space = AdjacencySpace(n_chiplets=n, max_degree=4)
    sc = single_link_faults(space, top_k=4)
    ev = PopulationEvaluator(
        space, budgets=Budgets(max_interposer_area=2500.0),
        faults=FaultSetup(scenarios=sc))
    return space, EvolutionarySearch(space, ev, seed=seed, pop_size=size)


def test_fault_evaluator_populates_robust_metrics():
    _, opt = _make_fault_optimizer(seed=1)
    res = OptRunner(opt).run(2)
    assert len(res.archive) >= 1
    for e in res.archive.entries:
        m = e.metrics
        # worst case can never beat the pristine design
        assert m["worst_latency"] >= m["pristine_latency"] - 1e-6
        assert m["worst_throughput"] <= m["pristine_throughput"] + 1e-6
        assert e.latency == pytest.approx(m["worst_latency"])
        assert 0.0 <= m["min_reachable_fraction"] <= 1.0
        assert m["reachable_fraction"] == pytest.approx(1.0)


def test_fault_resume_reproduces_uninterrupted_run(tmp_path):
    ckpt = str(tmp_path / "fopt.json")
    gens = 4
    _, full = _make_fault_optimizer(seed=2)
    r_full = OptRunner(full).run(gens)
    _, part = _make_fault_optimizer(seed=2)
    OptRunner(part, checkpoint_path=ckpt).run(2)
    _, fresh = _make_fault_optimizer(seed=2)
    r_res = OptRunner(fresh, checkpoint_path=ckpt).run(gens)
    a = [(e.latency, e.throughput, e.payload)
         for e in r_full.archive.front()]
    b = [(e.latency, e.throughput, e.payload)
         for e in r_res.archive.front()]
    assert a == b
    assert r_full.n_evals == r_res.n_evals


def test_fault_async_stepper_matches_sync_runner():
    """The path the benchmark's fault cell runs: ``AsyncStepper`` over a
    ``PopulationEvaluator`` with a ``FaultSetup`` (n=16, P=8, F=9) gives
    the synchronous ``OptRunner``'s archive front, evaluation count,
    robust columns of every generation and final optimizer state."""
    from repro.opt import AsyncStepper

    gens = 4
    runs = {}
    for mode in ("sync", "async"):
        space = AdjacencySpace(n_chiplets=16, max_degree=8)
        sc = single_link_faults(space, top_k=8)
        assert sc.n_scenarios == 9
        ev = PopulationEvaluator(space, faults=FaultSetup(scenarios=sc))
        columns = []
        finalize = ev._finalize_faults

        def keep(genomes, grid, _finalize=finalize, _columns=columns):
            out = _finalize(genomes, grid)
            _columns.append({k: v.tolist() for k, v in out.extra.items()})
            return out

        ev._finalize_faults = keep
        opt = EvolutionarySearch(space, ev, seed=4, pop_size=8)
        if mode == "async":
            AsyncStepper(opt, gens).run()
        else:
            OptRunner(opt).run(gens)
        runs[mode] = opt, columns
    (s_opt, s_cols), (a_opt, a_cols) = runs["sync"], runs["async"]
    assert s_opt.evaluator.n_evals == a_opt.evaluator.n_evals == gens * 8
    assert len(s_cols) == gens and s_cols == a_cols
    assert s_opt.state() == a_opt.state()
    front = lambda o: [(e.latency, e.throughput, e.payload, e.metrics)
                       for e in o.archive.front()]
    assert front(s_opt) and front(s_opt) == front(a_opt)


def test_faults_require_device_path():
    space = AdjacencySpace(n_chiplets=8, routing="updown_random")
    sc = single_link_faults(space, top_k=2)
    with pytest.raises(ValueError, match="fault"):
        PopulationEvaluator(space, faults=FaultSetup(scenarios=sc))


# ---------------------------------------------------------------------------
# checksummed snapshots + corrupt/truncated resume
# ---------------------------------------------------------------------------

def test_opt_resume_falls_back_on_corrupt_checkpoint(tmp_path):
    from repro.opt.runner import load_checkpoint, load_checkpoint_resilient
    ckpt = str(tmp_path / "opt.json")
    _, opt = _make_fault_optimizer(seed=3)
    OptRunner(opt, checkpoint_path=ckpt).run(2)
    good = load_checkpoint(ckpt)
    assert good["generation"] == 2

    # flip a byte inside the payload: sha256 must reject it
    blob = open(ckpt).read()
    with open(ckpt, "w") as f:
        f.write(blob.replace('"generation": 2', '"generation": 9'))
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(ckpt)
    state, source = load_checkpoint_resilient(ckpt)
    assert source == ckpt + ".prev"       # fell back to the rotation
    assert state["generation"] == 1

    # truncation (torn write) also falls back
    with open(ckpt, "w") as f:
        f.write(blob[:len(blob) // 2])
    state, source = load_checkpoint_resilient(ckpt)
    assert source == ckpt + ".prev" and state["generation"] == 1

    # both candidates corrupt -> fresh start, not a crash
    with open(ckpt + ".prev", "w") as f:
        f.write("{")
    assert load_checkpoint_resilient(ckpt) == (None, None)
    _, fresh = _make_fault_optimizer(seed=3)
    runner = OptRunner(fresh, checkpoint_path=ckpt)
    assert runner.optimizer.generation == 0


def test_pre_format2_flat_checkpoint_still_loads(tmp_path):
    from repro.opt.runner import load_checkpoint
    ckpt = str(tmp_path / "flat.json")
    with open(ckpt, "w") as f:
        json.dump({"algo": "ea", "generation": 5}, f)
    assert load_checkpoint(ckpt)["generation"] == 5


def test_sigkill_mid_write_leaves_resumable_checkpoint(tmp_path):
    """SIGKILL at an arbitrary instant of a checkpoint-write loop must
    leave either the new or the rotated snapshot verifiable."""
    ckpt = str(tmp_path / "kill.json")
    code = f"""
import sys
sys.path.insert(0, {json.dumps(os.path.join(os.path.dirname(__file__),
                                            "..", "src"))})
from repro.opt.runner import OptRunner, save_checkpoint
from repro.opt import Budgets, EvolutionarySearch, PopulationEvaluator
from repro.opt.space import AdjacencySpace
space = AdjacencySpace(n_chiplets=6, max_degree=3)
ev = PopulationEvaluator(space, budgets=Budgets(), device_path=False)
opt = EvolutionarySearch(space, ev, seed=0, pop_size=4)
opt.step()
print("READY", flush=True)
while True:
    save_checkpoint({json.dumps(ckpt)}, opt)
"""
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        deadline = time.perf_counter() + 30
        while not os.path.exists(ckpt):
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        time.sleep(0.05)                  # land mid-write with high odds
        proc.kill()
    finally:
        proc.wait(timeout=30)
    from repro.opt.runner import load_checkpoint_resilient
    state, source = load_checkpoint_resilient(ckpt)
    assert state is not None, "no verifiable snapshot survived SIGKILL"
    assert state["generation"] == 1


def test_array_checkpoint_shard_sha256_and_step_fallback(tmp_path):
    import jax.numpy as jnp
    from repro.ckpt import restore_checkpoint, save_checkpoint

    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, {"a": jnp.arange(6, dtype=jnp.float32)})
    save_checkpoint(d, 2, {"a": 2 * jnp.arange(6, dtype=jnp.float32)})
    manifest = json.load(open(os.path.join(d, "step_2", "manifest.json")))
    assert all(len(sh["sha256"]) == 64 for sh in manifest["shards"])

    shard = os.path.join(d, "step_2", "shard_0.npz")
    with open(shard, "r+b") as f:
        f.seek(12)
        f.write(b"\xff\xff\xff\xff")
    like = {"a": jnp.zeros(6, jnp.float32)}
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, like, step=2)      # explicit step: raises
    restored, step = restore_checkpoint(d, like)  # auto: falls back
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.arange(6, dtype=np.float32))


# ---------------------------------------------------------------------------
# reachable_fraction report column
# ---------------------------------------------------------------------------

def test_reachable_fraction_flags_partitioned_topology(pipe8):
    """ISSUE 9 satellite: a partitioned design must surface an explicit
    reachable fraction < 1 in the report arrays instead of poisoning the
    proxies with untyped inf."""
    space = pipe8.space
    n = space.n_chiplets
    bits = np.zeros((2, space.genome_length), np.int64)
    for g, (u, v) in enumerate(zip(space.pair_u, space.pair_v)):
        # two cliques {0..3} and {4..7}, no bridge: partitioned
        if (u < 4) == (v < 4):
            bits[0, g] = 1
        bits[1, g] = int(v == u + 1 or (u == 0 and v == n - 1))  # ring
    res = pipe8.evaluate(bits)
    reach = res.reports.reachable_fraction
    # 8 nodes in two halves: 2 * 4*3 / (8*7) ordered pairs reachable
    assert reach[0] == pytest.approx(24.0 / 56.0)
    assert reach[1] == pytest.approx(1.0)
    assert np.isfinite(res.reports.power).all()


def test_report_arrays_default_reachable_fraction():
    from repro.core.reports import ReportArrays
    z = np.zeros(3)
    r = ReportArrays(z, z, z, z)
    np.testing.assert_array_equal(r.reachable_fraction, np.ones(3))
