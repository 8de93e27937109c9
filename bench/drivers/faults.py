"""Driver of the fault cells: one fault-aware design search as ``python -m
repro.opt --faults ... --async`` runs it, through the asynchronous driver
(``opt.runner.AsyncStepper`` -> ``PopulationEvaluator(faults=FaultSetup)``
-> ``DseEngine.evaluate_genomes_faults_async`` -> the fused [P, F]
population x fault-scenario grid of ``AdjacencyPipeline``) on a mesh over
the cell's chips.

The scenarios come from the configuration's ``faults`` block through the
program's own fault model (``faults.model.make_scenarios``); the robust
objectives (worst case or expectation, and the disconnection constraint)
from the same block. Set-up, the window and its end are as in
``drivers/search.py``: repair's degree-cap scan is warmed at every bucket
it can meet, the warm-up generations compile the grid program, the window
holds whole generations, and ``completed_evals`` counts the designs whose
robust objectives were returned to the optimizer in it. After the window
the run records the share of the window's (design, scenario) rows and of
its designs that a scenario degrades (the rest read their pristine
answer), and the grid program's bytes per chip from its compiled
executable.

Afterwards a sample of the window's designs is compared with the plain
reference under the same scenarios (``compare_faults.py``): one whole
generation of the window and ``check_designs`` designs of the others, both
drawn from the seed, and the window's design with the highest worst-case
latency.
"""
from __future__ import annotations

import time

import numpy as np

import compare
import compare_faults
import harness
from warmup import warm_repair


class WindowRecorder:
    """``on_generation`` hook: keeps each generation's genomes (bit-packed),
    the robust columns the program computed and its report columns, for
    the comparison after the window."""

    def __init__(self):
        self.gens: dict[int, dict] = {}

    def __call__(self, opt, meta, ev) -> None:
        gen = {"bits": np.packbits(ev.genomes.astype(bool), axis=1)}
        for col in compare_faults.COLUMNS:
            gen[col] = np.asarray(ev.extra[col], np.float64)
        for col in compare.REPORT_COLUMNS:
            gen[col] = np.asarray(getattr(ev.reports, col))
        self.gens[meta["generation"]] = gen


def fault_setup(space, cfg: dict):
    """The configuration's scenarios and robust objectives, built by the
    program's fault model."""
    from repro.faults.model import make_scenarios
    from repro.faults.objectives import FaultSetup, RobustObjectives

    scenarios = make_scenarios(space, cfg["model"], top_k=int(cfg["top_k"]),
                               include_pristine=bool(cfg["include_pristine"]))
    if scenarios.n_scenarios != int(cfg["scenarios"]):
        raise ValueError(f"the fault model gave {scenarios.n_scenarios} "
                         f"scenarios, the configuration states "
                         f"{cfg['scenarios']}")
    return FaultSetup(scenarios=scenarios, objectives=RobustObjectives(
        mode=cfg["mode"],
        max_disconnect_prob=float(cfg["max_disconnect_prob"])))


def run(ctx: harness.Run) -> None:
    from repro.dse.engine import DseEngine
    from repro.opt import AsyncStepper, PopulationEvaluator
    from repro.opt.runner import make_optimizer, make_space
    from repro.utils.jaxcompat import make_auto_mesh

    tr = ctx.traffic
    space_kw = dict(ctx.config["space"])
    space = make_space(space_kw.pop("kind"), **space_kw)
    setup = fault_setup(space, ctx.config["faults"])
    mesh = make_auto_mesh((len(ctx.devices),), ("data",),
                          devices=list(ctx.devices))
    evaluator = PopulationEvaluator(space, engine=DseEngine(mesh=mesh),
                                    faults=setup)
    pop = int(tr["population"])
    opt = make_optimizer(tr["algo"], space, evaluator, seed=ctx.seed,
                         pop_size=pop)
    recorder = WindowRecorder()
    stepper = AsyncStepper(opt, generations=1 << 40,
                           on_generation=recorder)

    t = time.perf_counter()
    warm_repair(space, pop)
    t_repair = time.perf_counter() - t
    for _ in range(int(tr["warmup_generations"])):
        stepper.step()
    ctx.rec["compile_s"] = ctx.clock.seconds
    ctx.log(f"[faults] set-up: repair warm-up {t_repair:.3f} s, "
            f"{tr['warmup_generations']} warm-up generations "
            f"{time.perf_counter() - t - t_repair:.3f} s")

    gen0 = opt.generation
    t0 = ctx.window_open()
    while True:
        stepper.step()
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    ctx.window_close()
    gen1 = opt.generation
    stepper.drain()
    ctx.rec["memory_peak_bytes"] = ctx.memory_peak()
    ctx.reduce_trace()
    memory = program_memory(space, mesh, pop, setup.scenarios.n_scenarios)
    if memory is not None:
        ctx.rec["grid_program_bytes"] = memory
        ctx.log("[faults] grid program per chip (bytes): " + ", ".join(
            f"{k} {v}" for k, v in memory.items()))

    sc = setup.scenarios
    window = [recorder.gens[g] for g in range(gen0 + 1, gen1 + 1)]
    ctx.rec["generations"] = gen1 - gen0
    ctx.rec["completed_evals"] = sum(len(g["bits"]) for g in window)
    ctx.rec["attempted"] = ctx.rec["completed_evals"]
    ctx.rec["failed"] = 0
    ctx.rec["n_chiplets"] = space.n_chiplets
    ctx.rec["window_bits"] = [g["bits"] for g in window]
    ctx.rec["genome_length"] = space.genome_length
    ctx.rec["fault_link_fail"] = np.asarray(sc.link_fail, bool)
    ctx.rec["fault_node_fail"] = np.asarray(sc.node_fail, bool)
    degraded = np.concatenate([compare_faults.degraded_rows(
        np.unpackbits(g["bits"], axis=1, count=space.genome_length),
        sc.link_fail, sc.node_fail) for g in window])
    ctx.rec["degraded_row_share"] = float(degraded.mean())
    ctx.rec["degraded_design_share"] = float(degraded.any(axis=1).mean())
    ctx.log(f"[faults] n={space.n_chiplets} P={pop} F={sc.n_scenarios} "
            f"generations={gen1 - gen0} evals={ctx.rec['completed_evals']} "
            f"window_s={ctx.rec['window_s']:.4f} "
            f"setup_s={ctx.rec['setup_s']:.4f} "
            f"compile_s={ctx.rec['compile_s']:.4f}")

    t = time.perf_counter()
    designs = _sample(window, space.genome_length, ctx.seed,
                      int(tr["check_designs"]))
    numbers = compare_faults.compare_designs(designs, ctx.config)
    ctx.rec["compare_s"] = time.perf_counter() - t
    ctx.rec["compared_degraded_designs"] = numbers["degraded_designs"]
    ctx.log(f"[faults] compared {numbers['designs']} designs under "
            f"{sc.n_scenarios} scenarios in {ctx.rec['compare_s']:.3f} s; "
            f"{numbers['degraded_designs']} of them hold a failed link; "
            f"window rows degraded {ctx.rec['degraded_row_share']!r}, "
            f"designs degraded {ctx.rec['degraded_design_share']!r}")
    compare_faults.record(ctx, numbers)
    harness.record_path_checks(ctx)
    ctx.rec["correct"] = harness.checks_pass(ctx.rec["checks"])


def program_memory(space, mesh, pop: int, n_scenarios: int):
    """The grid program's own accounting of its bytes per chip (arguments,
    outputs, temporaries, code), read from the executable compiled for the
    cell's chips; None where the program does not offer it."""
    from repro.dse.genomes import make_pipeline
    measure = getattr(make_pipeline(space, mesh), "faults_memory", None)
    return None if measure is None else measure(pop, n_scenarios)


def _sample(window, genome_length: int, seed: int, k: int) -> list[dict]:
    """One whole generation of the window and k designs of the others,
    both drawn from the seed, and the window's design with the highest
    worst-case latency. The whole generation is there so that a fault in
    one answer of every evaluated batch is always compared."""
    worst = np.concatenate([g["worst_latency"] for g in window])
    offsets = np.cumsum([0] + [len(g["bits"]) for g in window])
    whole = int(np.random.default_rng([seed, 0x6E4]).integers(len(window)))
    always = list(dict.fromkeys([int(np.argmax(worst)),
                                 *range(offsets[whole], offsets[whole + 1])]))
    rows = compare.sample_rows(len(worst), k + len(always), seed, always)
    out = []
    for r in rows:
        gi = int(np.searchsorted(offsets, r, side="right") - 1)
        g, i = window[gi], r - offsets[gi]
        d = {"bits": np.unpackbits(g["bits"][i], count=genome_length)}
        for col in compare_faults.COLUMNS + compare.REPORT_COLUMNS:
            d[col] = g[col][i]
        out.append(d)
    return out
