#!/usr/bin/env python3
"""Record the small TPU trace that ``test_xplane.py`` reads (run on the
chip; writes ``tests/data/small.xplane.pb`` and ``small.json``).

Inside one ``bench.window`` annotation it evaluates a population of 8
designs at n=64 (one call of the fused load-propagation kernel), sleeps,
and evaluates it again: the trace has two kernel calls and one idle gap at
least as long as the sleep.
"""
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402
import xplane  # noqa: E402

SLEEP_S = 0.2


def main() -> int:
    harness.configure_jax()
    devices = harness.require_chips(1)
    import jax
    import numpy as np
    from repro.dse.engine import DseEngine
    from repro.opt import AdjacencySpace
    from repro.utils.jaxcompat import make_auto_mesh

    space = AdjacencySpace(n_chiplets=64, max_degree=8)
    engine = DseEngine(mesh=make_auto_mesh((1,), ("data",),
                                           devices=devices))
    genomes = space.sample(np.random.default_rng(0), 8)
    engine.evaluate_genomes(space, genomes)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(harness.WINDOW_ANNOTATION):
        engine.evaluate_genomes(space, genomes)
        time.sleep(SLEEP_S)
        engine.evaluate_genomes(space, genomes)
    jax.profiler.stop_trace()
    data = HERE / "data"
    data.mkdir(exist_ok=True)
    shutil.copy(xplane.find_xplane(tmp), data / "small.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    (data / "small.json").write_text(json.dumps(
        {"kernel_calls": 2, "kernel": "load_prop_pallas",
         "sleep_s": SLEEP_S, "device_kind": devices[0].device_kind},
        indent=2) + "\n")
    print(os.path.getsize(data / "small.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
