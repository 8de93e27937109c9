"""Driver of the search cells: one design search as ``python -m repro.opt``
runs it, through the asynchronous driver (``opt.runner.AsyncStepper`` ->
``PopulationEvaluator`` -> ``DseEngine.evaluate_genomes_async`` ->
``AdjacencyPipeline``) on a mesh over the cell's chips.

Set-up builds the space and engine, warms repair's degree-cap scan at every
candidate bucket it can meet (``warmup.py``) and runs the warm-up generations (which
compile the evaluation program). The window starts at the next generation
and ends when the first generation completes after ``--seconds``, so it
holds whole generations; ``completed_evals`` counts the designs whose
results were returned to the optimizer in it. Afterwards a sample of the
window's designs is compared with the plain reference: one whole
generation of the window and ``check_designs`` designs of the others, both
drawn from the seed, and the window's highest-latency design.
"""
from __future__ import annotations

import time

import numpy as np

import compare
import harness
from warmup import warm_repair


class WindowRecorder:
    """``on_generation`` hook: keeps each generation's genomes (bit-packed)
    and the program's results, for the comparison after the window."""

    def __init__(self):
        self.gens: dict[int, dict] = {}

    def __call__(self, opt, meta, ev) -> None:
        self.gens[meta["generation"]] = {
            "bits": np.packbits(ev.genomes.astype(bool), axis=1),
            "latency": np.asarray(ev.latency),
            "throughput": np.asarray(ev.throughput),
            "reports": ev.reports}


def run(ctx: harness.Run) -> None:
    from repro.dse.engine import DseEngine
    from repro.opt import AsyncStepper, PopulationEvaluator
    from repro.opt.runner import make_optimizer, make_space
    from repro.utils.jaxcompat import make_auto_mesh

    tr = ctx.traffic
    space_kw = dict(ctx.config["space"])
    space = make_space(space_kw.pop("kind"), **space_kw)
    mesh = make_auto_mesh((len(ctx.devices),), ("data",),
                          devices=list(ctx.devices))
    engine = DseEngine(mesh=mesh)
    evaluator = PopulationEvaluator(space, engine=engine)
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
    ctx.log(f"[search] set-up: repair warm-up {t_repair:.3f} s, "
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

    window = [recorder.gens[g] for g in range(gen0 + 1, gen1 + 1)]
    ctx.rec["generations"] = gen1 - gen0
    ctx.rec["completed_evals"] = sum(len(g["latency"]) for g in window)
    ctx.rec["attempted"] = ctx.rec["completed_evals"]
    ctx.rec["failed"] = 0
    ctx.rec["n_chiplets"] = space.n_chiplets
    ctx.rec["window_bits"] = [g["bits"] for g in window]
    ctx.rec["genome_length"] = space.genome_length
    ctx.log(f"[search] n={space.n_chiplets} P={pop} "
            f"generations={gen1 - gen0} evals={ctx.rec['completed_evals']} "
            f"window_s={ctx.rec['window_s']:.4f} "
            f"setup_s={ctx.rec['setup_s']:.4f} "
            f"compile_s={ctx.rec['compile_s']:.4f}")

    t = time.perf_counter()
    designs = _sample(window, space.genome_length, ctx.seed,
                      int(tr["check_designs"]))
    numbers = compare.compare_designs(designs, ctx.config)
    ctx.log(f"[search] compared {numbers['designs']} designs in "
            f"{time.perf_counter() - t:.3f} s")
    compare.record(ctx, numbers)
    harness.record_path_checks(ctx)
    ctx.rec["correct"] = harness.checks_pass(ctx.rec["checks"])


def _sample(window, genome_length: int, seed: int, k: int) -> list[dict]:
    """One whole generation of the window and k designs of the others,
    both drawn from the seed, and the window's highest-latency design. The
    whole generation is there so that a fault in one answer of every
    evaluated batch is always compared."""
    lat = np.concatenate([g["latency"] for g in window])
    offsets = np.cumsum([0] + [len(g["latency"]) for g in window])
    whole = int(np.random.default_rng([seed, 0x6E4]).integers(len(window)))
    always = list(dict.fromkeys([int(np.argmax(lat)),
                                 *range(offsets[whole], offsets[whole + 1])]))
    rows = compare.sample_rows(len(lat), k + len(always), seed, always)
    out = []
    for r in rows:
        gi = int(np.searchsorted(offsets, r, side="right") - 1)
        g, i = window[gi], r - offsets[gi]
        d = {"bits": np.unpackbits(g["bits"][i], count=genome_length),
             "latency": g["latency"][i], "throughput": g["throughput"][i]}
        for col in compare.REPORT_COLUMNS:
            d[col] = getattr(g["reports"], col)[i]
        out.append(d)
    return out
