#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json``. The run warms up every shape the cell's
traffic uses (set-up), measures for ``--seconds``, then checks what the
timed path produced against the plain reference (``reference.py``). With
``--trace 0`` the last line of stdout carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace and the program's spans. Every compared number is printed beside its
limit as the last lines of stderr and under ``checks``, the last key of
the result line.

Without a TPU, with fewer chips than the cell asks for, or without the
program's ``src/`` beside this directory, it exits non-zero and prints no
result. It never starts a child process.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import harness  # noqa: E402
import spans  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def result_line(run: harness.Run, cell: dict, devices) -> dict:
    """The JSON result: ``checks`` comes last."""
    rec = run.rec
    entries = cell["per_layer"] if run.trace else cell["end_to_end"]
    out = {"correct": bool(rec["correct"]),
           "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]),
           "metrics": harness.read_metrics(entries, rec),
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": rec["memory_peak_bytes"]}}
    if run.trace:
        tr = rec["trace"]
        lo, hi = tr["window_ns"]
        busy = [sum(e - s for s, e in tr["busy"].get(d.id, []))
                for d in devices]
        print("device idle share (%) per chip: " + ", ".join(
            f"{d.id}: {100.0 * (1.0 - b / (hi - lo))!r}"
            for d, b in zip(devices, busy)), file=sys.stderr, flush=True)
        out["device"]["busy_s"] = sum(busy) / len(busy) / 1e9
        out["device"]["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = spans.breakdown(rec)
    out["checks"] = rec["checks"]
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.configure_jax()
        devices = harness.require_chips(cell["cell"]["chips"])
    except (harness.NoChip, FileNotFoundError, KeyError) as err:
        print(f"bench: {type(err).__name__}: {err}", file=sys.stderr)
        return 3
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_PROCESS)
    try:
        driver = harness.load_module(
            harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
        driver.run(run)
        out = result_line(run, cell, devices)
    except Exception as err:                   # noqa: BLE001 - fail loudly
        traceback.print_exc()
        print(f"bench: run failed: {type(err).__name__}: {err}",
              file=sys.stderr)
        return 1
    finally:
        run.close()
    for name, c in out["checks"].items():
        ok = (not math.isnan(c["value"])) and c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
