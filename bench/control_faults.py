#!/usr/bin/env python3
"""Readings of the fault comparison's two ends, on the chip at a fault
cell's own size.

    python bench/control_faults.py --workload <cell> --seconds <s> --seeds <n> ...

As ``control.py`` does for the search cells, for each seed, in this one
process, it runs the cell as ``run.py`` does (a short window at the cell's
own load) and reports, for the designs the comparison drew, the compared
numbers of the program (the lower reading) and of the control: the plain
fault reference (``reference_faults.py``) computed one precision below
what the configuration states (proxies in bfloat16, reports in float32),
put in the program's place (the upper reading). The control stands in the
program's place in the run itself: the driver records the control's
numbers beside their limits and decides ``correct`` from them, which has
to come out false; the command exits 1 where it comes out true on any
seed. A limit lies between the largest program reading and the smallest
control reading. The benchmark's own runs do not run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import compare_faults  # noqa: E402
import harness  # noqa: E402

NUMBERS = compare_faults.NUMBERS


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    try:
        devices = harness.require_chips(cell["cell"]["chips"])
    except harness.NoChip as err:
        print(f"control_faults: {err}", file=sys.stderr)
        return 3
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    program_compare = compare_faults.compare_designs
    rows = []
    for seed in args.seeds:
        seen = {}

        def both(designs, config, *a, **kw):
            designs = list(designs)
            seen["program"] = program_compare(designs, config, *a, **kw)
            seen["control"] = program_compare(
                compare_faults.control_designs(designs, config), config)
            return seen["control"]

        compare_faults.compare_designs = both
        run = harness.Run(cell, seed, args.seconds, False, devices,
                          time.perf_counter())
        try:
            driver.run(run)
        finally:
            run.close()
            compare_faults.compare_designs = program_compare
        program = {**run.rec["checks"],
                   **{k: {"value": float(seen["program"][k]),
                          "limit": cell["limits"][k]} for k in NUMBERS}}
        row = {"seed": seed, "correct": harness.checks_pass(program),
               "control.correct": run.rec["correct"],
               **{f"program.{k}": seen["program"][k] for k in NUMBERS},
               **{f"control.{k}": seen["control"][k] for k in NUMBERS},
               "designs": seen["program"]["designs"],
               "compare_s": run.rec["compare_s"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {f"lower.{k}": max(r[f"program.{k}"] for r in rows)
               for k in NUMBERS}
    summary.update({f"upper.{k}": min(r[f"control.{k}"] for r in rows)
                    for k in NUMBERS})
    summary["control_correct_on_any_seed"] = any(
        r["control.correct"] for r in rows)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      **summary}), flush=True)
    return 1 if summary["control_correct_on_any_seed"] else 0


if __name__ == "__main__":
    sys.exit(main())
