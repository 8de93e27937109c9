#!/usr/bin/env python3
"""Smoke run of the device-path design search on a TPU, through the
public API, in one process (nothing here starts a child).

    python chip_smoke.py             # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4   # four chips: population sharding only

One chip:
  (a) NSGA-II over an AdjacencySpace at n=64 (degree <= 8, random_uniform
      traffic), population 256, 3 generations, async driver: the fused
      load-propagation kernel.
  (b) the same at n=256, population 32: the tiled kernel and blocked
      routing.
  (c) one generation of the fused fault grid at n=64 (single-link model,
      top_k=32, so F=33 scenarios).
  (d) a SearchService answering three co-batched jobs.
  (e) correctness at n=64 and n=256, 16 genomes each: the genome pipeline
      against the host path (``evaluate_points``) to 1e-5 relative, and
      the compiled Pallas kernel against ``backend="xla"`` on the chip for
      the same routing tables; plus the min-plus and flow-accumulation
      kernels against their references.
The search runs on a mesh over ``jax.devices()[:1]``.

Four chips: one n=64 population of 256 evaluated on a 4-device and on a
1-device mesh; the metrics must agree to 1e-5 and each device must hold
P/4 rows of the outputs.

Each phase prints its shapes, compile seconds, wall seconds and the kernel
dispatch counters on a line of its own: log lines, not measurements. It
fails when a ``ops.fallback`` counter is non-zero, when load propagation
dispatched anything but compiled ``pallas`` at n <= REPRO_LOAD_PROP_FUSED_N
(``pallas_tiled`` above), or when Pallas would run in interpret mode. The
last line of stdout is one JSON object naming the device, printed only
when every phase passed. Without a TPU, or without the repository's
``src`` beside this file, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
RTOL = 1e-5


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_close(got, want, what: str) -> float:
    """Elementwise |got - want| <= RTOL * |want|; returns the worst
    relative error."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{what}: shape {got.shape} != "
                                   f"{want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite values")
    err = np.abs(got - want)
    rel = float((err / np.maximum(np.abs(want), 1e-30)).max(initial=0.0))
    check(bool((err <= RTOL * np.abs(want)).all()),
          f"{what}: max relative error {rel:.3e} > {RTOL:g}")
    return rel


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its monitoring
    events), so a phase can report compile time apart from run time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def dispatch_counts() -> dict:
    """{(backend, n, tile): count} of ``ops.load_propagate.dispatch``."""
    from repro.obs import metrics
    out = {}
    for c in metrics.REGISTRY.series("Counter",
                                     "ops.load_propagate.dispatch"):
        if c.value:
            lab = c.labels
            out[(lab["backend"], int(lab["n"]), str(lab["tile"]))] = c.value
    return out


def fmt_dispatch() -> str:
    return " ".join(f"{b}@n={n}/tile={t}:{v}"
                    for (b, n, t), v in sorted(dispatch_counts().items()))


def check_device_path() -> None:
    """No fallback, no interpret mode, and the kernel the chip should run
    at every node count."""
    from repro.kernels.ops import interpret_mode
    from repro.obs import metrics
    from repro.utils import env

    fallbacks = sum(c.value for c in metrics.REGISTRY.series(
        "Counter", "ops.fallback"))
    check(fallbacks == 0, f"ops.fallback counted {fallbacks}")
    check(not interpret_mode(), "Pallas kernels would run in interpret mode")
    fused_n = env.get_int("REPRO_LOAD_PROP_FUSED_N")
    for backend, n, _ in dispatch_counts():
        want = "pallas" if n <= fused_n else "pallas_tiled"
        check(backend == want, f"load_propagate dispatched {backend!r} at "
                               f"n={n}; the chip path is {want!r}")


def run_search(engine, space, pop: int, generations: int, clock,
               faults=None):
    """NSGA-II through the async driver; returns (optimizer, per-generation
    wall seconds, compile seconds)."""
    from repro.opt import AsyncStepper, PopulationEvaluator
    from repro.opt.runner import make_optimizer

    evaluator = PopulationEvaluator(space, engine=engine, faults=faults)
    opt = make_optimizer("nsga2", space, evaluator, seed=0, pop_size=pop)
    stepper = AsyncStepper(opt, generations)
    c0 = clock.seconds
    walls = []
    while True:
        t0 = time.perf_counter()
        if not stepper.step():
            break
        walls.append(time.perf_counter() - t0)
    stepper.drain()
    check(opt.generation == generations,
          f"search stopped at generation {opt.generation}/{generations}")
    front = opt.archive.front()
    check(len(front) > 0, "empty Pareto front")
    for e in front:
        check(e.latency == e.latency and e.throughput == e.throughput
              and e.latency < 1e29, f"bad front point {e.latency}, "
                                    f"{e.throughput}")
    return opt, walls, clock.seconds - c0


def phase_search(engine, clock, n: int, pop: int, tag: str) -> None:
    from repro.opt import AdjacencySpace

    space = AdjacencySpace(n_chiplets=n, max_degree=8,
                           traffic_pattern="random_uniform")
    opt, walls, comp = run_search(engine, space, pop, 3, clock)
    log(f"[{tag}] adjacency n={n} G={space.genome_length} P={pop} "
        f"nsga2 gens=3 async: compile_s={comp:.3f} "
        f"gen_wall_s={[round(w, 4) for w in walls]} "
        f"evals={opt.evaluator.n_evals} front={len(opt.archive)} | "
        f"dispatch {fmt_dispatch()}")


def phase_faults(engine, clock) -> None:
    from repro.faults.model import make_scenarios
    from repro.faults.objectives import FaultSetup, RobustObjectives
    from repro.opt import AdjacencySpace

    space = AdjacencySpace(n_chiplets=64, max_degree=8)
    scenarios = make_scenarios(space, "single", top_k=32)
    check(scenarios.n_scenarios == 33,
          f"F={scenarios.n_scenarios}, expected 33")
    faults = FaultSetup(scenarios=scenarios,
                        objectives=RobustObjectives(mode="worst"))
    opt, walls, comp = run_search(engine, space, 64, 1, clock, faults)
    log(f"[c] fault grid n=64 P=64 F={scenarios.n_scenarios} "
        f"(P*F={64 * scenarios.n_scenarios} rows) gens=1: "
        f"compile_s={comp:.3f} gen_wall_s={[round(w, 4) for w in walls]} "
        f"front={len(opt.archive)} | dispatch {fmt_dispatch()}")


def phase_service(engine, clock) -> None:
    import math

    from repro.serve import JobSpec, SearchService

    space = {"kind": "adjacency", "n_chiplets": 64, "max_degree": 8}
    specs = [JobSpec(job_id="nsga2", algo="nsga2", generations=2,
                     pop_size=128, seed=0, tenant="a", space=space),
             JobSpec(job_id="sa", algo="sa", generations=2, pop_size=64,
                     seed=1, tenant="b", space=space),
             JobSpec(job_id="random", algo="random", generations=2,
                     pop_size=64, seed=2, tenant="b", space=space)]
    c0 = clock.seconds
    t0 = time.perf_counter()
    with SearchService(engine=engine) as svc:
        for spec in specs:
            svc.submit(spec)
        jobs = svc.wait_all(timeout_s=600.0)
        stats = svc.stats()
    wall = time.perf_counter() - t0
    for job in jobs:
        check(job.status == "done",
              f"job {job.spec.job_id}: {job.status} ({job.reason})")
        rows = job.result_rows or []
        check(len(rows) > 0, f"job {job.spec.job_id}: empty front")
        check(all(math.isfinite(r["latency"]) and
                  math.isfinite(r["throughput"]) for r in rows),
              f"job {job.spec.job_id}: non-finite front")
    log(f"[d] service: 3 co-batched jobs (n=64, P=128+64+64) answered, "
        f"generations {[j.generation for j in jobs]}: wall_s={wall:.3f} "
        f"compile_s={clock.seconds - c0:.3f} "
        f"evals={stats['evals_total']} | dispatch {fmt_dispatch()}")


def phase_correctness(engine, clock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import (flow_accumulate, flow_accumulate_ref,
                               minplus_matmul, minplus_ref)
    from repro.kernels.ops import load_propagate
    from repro.opt import AdjacencySpace
    from repro.routing.device import hops_next_hop_batch
    from repro.traffic import make_traffic

    c0 = clock.seconds
    rng = np.random.default_rng(0)
    kernel_runs = []
    for n in (64, 256):
        space = AdjacencySpace(n_chiplets=n, max_degree=8)
        genomes = space.sample(rng, 16)
        dev = engine.evaluate_genomes(space, genomes)
        host = engine.evaluate_points(space.decode(genomes), n_pad=n,
                                      round_hops=True)
        r_lat = check_close(dev.latency, host.latency,
                            f"n={n} latency vs host path")
        r_thr = check_close(dev.throughput, host.throughput,
                            f"n={n} throughput vs host path")

        bits = np.asarray(genomes, np.int64) % 2
        adj = np.zeros((len(bits), n, n), bool)
        adj[:, space.pair_u, space.pair_v] = bits.astype(bool)
        adj[:, space.pair_v, space.pair_u] = bits.astype(bool)
        next_hop = hops_next_hop_batch(jnp.asarray(adj))
        traffic = make_traffic(space.traffic_pattern, n, seed=space.seed)
        load0 = jnp.broadcast_to(jnp.asarray(traffic.T, jnp.float32)[None],
                                 (len(bits), n, n))
        w_k, f_k = jax.block_until_ready(
            load_propagate(next_hop, load0, max_hops=n - 1))
        kernel_runs.append((n, r_lat, r_thr, next_hop, load0, w_k, f_k))
    # the counters now hold every dispatch the search path made; the XLA
    # reference below is requested explicitly and is not part of it
    check_device_path()

    parts = []
    for n, r_lat, r_thr, next_hop, load0, w_k, f_k in kernel_runs:
        w_x, f_x = load_propagate(next_hop, load0, max_hops=n - 1,
                                  backend="xla")
        r_w = check_close(w_k, w_x, f"n={n} W pallas vs xla")
        r_f = check_close(f_k, f_x, f"n={n} flow pallas vs xla")
        parts.append(f"n={n}: lat {r_lat:.2e} thr {r_thr:.2e} "
                     f"W {r_w:.2e} flow {r_f:.2e}")

    a = jnp.asarray(rng.uniform(0.0, 50.0, (2, 256, 256)), jnp.float32)
    b = jnp.asarray(rng.uniform(0.0, 50.0, (2, 256, 256)), jnp.float32)
    r_mp = check_close(minplus_matmul(a, b), minplus_ref(a, b),
                       "minplus_matmul vs reference")
    n, pairs = 64, 4096
    flow = jnp.asarray(rng.uniform(0, 5, (n, n)), jnp.float32)
    cur = jnp.asarray(rng.integers(0, n, pairs), jnp.int32)
    nxt = jnp.asarray(rng.integers(0, n, pairs), jnp.int32)
    amt = jnp.asarray(rng.uniform(0, 2, pairs), jnp.float32)
    r_fa = check_close(flow_accumulate(flow, cur, nxt, amt),
                       flow_accumulate_ref(flow, cur, nxt, amt),
                       "flow_accumulate vs reference")
    log(f"[e] max relative error ({'; '.join(parts)}; minplus {r_mp:.2e}; "
        f"flow_accum {r_fa:.2e}) compile_s={clock.seconds - c0:.3f}")


def phase_sharding(devices, clock) -> None:
    import numpy as np

    from repro.dse.engine import DseEngine
    from repro.opt import AdjacencySpace
    from repro.utils.jaxcompat import make_auto_mesh

    space = AdjacencySpace(n_chiplets=64, max_degree=8)
    genomes = space.sample(np.random.default_rng(0), 256)
    eng4 = DseEngine(mesh=make_auto_mesh((4,), ("data",),
                                         devices=devices[:4]))
    eng1 = DseEngine(mesh=make_auto_mesh((1,), ("data",),
                                         devices=devices[:1]))
    c0 = clock.seconds
    t0 = time.perf_counter()
    pending = eng4.evaluate_genomes_async(space, genomes)
    for arr in pending.arrays:
        shards = arr.addressable_shards
        check(len({s.device for s in shards}) == 4,
              f"output spans {len(shards)} shards, expected 4 devices")
        rows = [s.data.shape[0] for s in shards]
        check(rows == [arr.shape[0] // 4] * 4,
              f"rows per device {rows}, expected {arr.shape[0] // 4} each")
    res4 = pending.result()
    wall4 = time.perf_counter() - t0
    t0 = time.perf_counter()
    res1 = eng1.evaluate_genomes(space, genomes)
    wall1 = time.perf_counter() - t0
    r_lat = check_close(res4.latency, res1.latency, "latency 4 vs 1 device")
    r_thr = check_close(res4.throughput, res1.throughput,
                        "throughput 4 vs 1 device")
    check_device_path()
    log(f"[4chips] adjacency n=64 P=256: rows/device={rows} "
        f"max relative error lat {r_lat:.2e} thr {r_thr:.2e}; "
        f"wall_s 4dev={wall4:.3f} 1dev={wall1:.3f} (compile included) "
        f"compile_s={clock.seconds - c0:.3f} | dispatch {fmt_dispatch()}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the population-sharding check over "
                        "four chips")
    args = p.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL: no repository source at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: FAIL: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"[smoke] {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache}")

    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_sharding(devices, clock)
        else:
            from repro.dse.engine import DseEngine
            from repro.utils.jaxcompat import make_auto_mesh

            engine = DseEngine(mesh=make_auto_mesh(
                (1,), ("data",), devices=devices[:1]))
            phase_search(engine, clock, 64, 256, "a")
            phase_search(engine, clock, 256, 32, "b")
            phase_faults(engine, clock)
            phase_service(engine, clock)
            phase_correctness(engine, clock)   # ends with the path check
    except Exception as err:                    # noqa: BLE001 - report, fail
        traceback.print_exc()
        print(f"chip_smoke: FAIL: {type(err).__name__}: {err}",
              file=sys.stderr)
        return 1
    log(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s "
        f"(compile {clock.seconds:.1f}s)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
