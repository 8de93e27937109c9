"""Shared machinery of the benchmark: finding a cell's files by name,
holding the chip, compile accounting, the traced window, and the result
line. Everything that belongs to one configuration, traffic mix or metric
lives in its own file (``configs/``, ``traffic/``, ``drivers/``,
``metrics/``) and is found by the name ``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WINDOW_ANNOTATION = "bench.window"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path):
    """Import a driver or metric file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, its configuration and its traffic mix, by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads(
        (BENCH / "limits" / f"{workload}.json").read_text())
    def wanted(m):
        return "workloads" not in m or workload in m["workloads"]
    return {"spec": spec, "cell": cell, "config": config, "traffic": traffic,
            "limits": limits,
            "end_to_end": [m for m in spec["end_to_end"] if wanted(m)],
            "per_layer": [m for m in spec["per_layer"] if wanted(m)]}


def configure_jax(root: Path = ROOT) -> str:
    """Put the program on the path and JAX's persistent compilation cache
    at the checkout's fixed ``.jax_cache`` (small programs included)."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"no program source at {src}")
    sys.path.insert(0, str(src))
    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no size-bounded eviction: its bookkeeping files are what failed to
    # write on the chip's machine, and the cache holds a few MiB
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def require_chips(n: int):
    """The first ``n`` TPU devices, or ``NoChip``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    return devices[:n]


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number
    of traces, from JAX's monitoring events. A trace inside the measured
    window means a program was built there."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[0]:
                self.traces += 1


def program_compiles() -> tuple[int, float]:
    """(sum of ``dse.genomes.COMPILE_COUNTS``, sum of ``jit.compile``)."""
    from repro.dse.genomes import COMPILE_COUNTS
    from repro.obs import metrics
    jit = sum(c.value for c in metrics.REGISTRY.series("Counter",
                                                       "jit.compile"))
    return sum(COMPILE_COUNTS.values()), jit


def device_path_faults() -> list[str]:
    """What keeps the run off the chip path: an ``ops.fallback``, Pallas in
    interpret mode, or a load-propagation dispatch that is not the compiled
    kernel for its node count."""
    from repro.kernels.ops import interpret_mode
    from repro.obs import metrics
    from repro.utils import env
    out = []
    fallbacks = sum(c.value for c in metrics.REGISTRY.series(
        "Counter", "ops.fallback"))
    if fallbacks:
        out.append(f"ops.fallback counted {fallbacks}")
    if interpret_mode():
        out.append("Pallas kernels would run in interpret mode")
    fused_n = env.get_int("REPRO_LOAD_PROP_FUSED_N")
    for c in metrics.REGISTRY.series("Counter",
                                     "ops.load_propagate.dispatch"):
        if not c.value:
            continue
        n = int(c.labels["n"])
        want = "pallas" if n <= fused_n else "pallas_tiled"
        if c.labels["backend"] != want:
            out.append(f"load_propagate dispatched {c.labels['backend']!r} "
                       f"at n={n}; the chip path is {want!r}")
    return out


class Run:
    """One run of one cell: its inputs, clocks and the records the driver
    fills in. The driver calls ``window_open`` after warm-up and
    ``window_close`` when its window ends."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 devices, t_process: float, check_chip: bool = True):
        self.cell = cell["cell"]
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.limits = cell["limits"]
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.check_chip = check_chip
        self.t_process = t_process
        self.clock = CompileClock()
        self.rec: dict = {"checks": {},
                          "device_ids": [d.id for d in devices],
                          "device_kind": devices[0].device_kind}
        self.tmp = tempfile.mkdtemp(prefix="bench-")
        self._annotation = None

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    # -- the measured window -------------------------------------------
    def window_open(self) -> float:
        """End of set-up; starts tracing in a traced run. Returns the
        window's start on ``time.perf_counter``."""
        rec = self.rec
        rec["setup_s"] = time.perf_counter() - self.t_process
        rec["compile_s_setup"] = self.clock.seconds
        self._traces0 = self.clock.traces
        self._programs0 = program_compiles()
        if self.trace:
            import jax
            from repro.obs.trace import enable_tracing, span
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(os.path.join(self.tmp, "profile"),
                                     profiler_options=opts)
            enable_tracing()
            # a marker span ties the tracer's clock to time.monotonic_ns
            self._mono_mark = time.monotonic_ns()
            with span("bench.mark"):
                pass
            self._annotation = jax.profiler.TraceAnnotation(
                WINDOW_ANNOTATION)
            self._mono_ann = time.monotonic_ns()
            self._annotation.__enter__()
        self._mono0 = time.monotonic_ns()
        t0 = time.perf_counter()
        self._t0 = t0
        return t0

    def window_close(self) -> float:
        """End of the window (whole units of work); stops tracing and
        reads the device memory peak. Returns the window's length."""
        t1 = time.perf_counter()
        mono1 = time.monotonic_ns()
        rec = self.rec
        rec["window_s"] = t1 - self._t0
        rec["compiles_in_window"] = self.clock.traces - self._traces0
        now = program_compiles()
        rec["program_compiles_in_window"] = (
            now[0] - self._programs0[0] + now[1] - self._programs0[1])
        if self.trace:
            import jax
            from repro.obs.trace import TRACER, disable_tracing
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            disable_tracing()
            spans = TRACER.to_dicts()
            mark = next(sp for sp in spans if sp["name"] == "bench.mark")
            origin = self._mono_mark - mark["ts_us"] * 1e3
            # every span as (name, start, end) in time.monotonic_ns
            rec["spans"] = [(sp["name"], origin + sp["ts_us"] * 1e3,
                             origin + (sp["ts_us"] + sp["dur_us"]) * 1e3,
                             sp["thread"], sp["depth"]) for sp in spans]
            rec["mono_window_ns"] = (self._mono0, mono1)
        return rec["window_s"]

    def reduce_trace(self) -> None:
        """Read the profiler's trace into ``rec['trace']`` (traced runs)."""
        if not self.trace:
            return
        import xplane
        path = xplane.find_xplane(os.path.join(self.tmp, "profile"))
        tr = xplane.reduce(path, WINDOW_ANNOTATION)
        # time.monotonic_ns + offset = the trace's clock
        tr["mono_offset_ns"] = tr["window_ns"][0] - self._mono_ann
        self.rec["trace"] = tr
        self.rec["trace_bytes"] = os.path.getsize(path)

    def memory_peak(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0

    def check(self, name: str, value: float, limit: float) -> None:
        """Record one compared number with its limit (pass: value <=
        limit; NaN fails)."""
        self.rec["checks"][name] = {"value": value, "limit": limit}

    def close(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)


def read_metrics(entries: list[dict], rec: dict) -> dict:
    """Each metric's reader (``metrics/<name>.py``) over the run's
    records; a reader that finds nothing returns None and the metric is
    left out."""
    out = {}
    for m in entries:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def record_path_checks(run: Run) -> None:
    """Exact checks of the chip path: nothing traced or compiled inside
    the window, and (on the chip) no fallback, no interpret mode, the
    compiled kernel for every node count."""
    run.check("window_compiles", float(run.rec["compiles_in_window"]
                                       + run.rec["program_compiles_in_window"]),
              0.0)
    if run.check_chip:
        faults = device_path_faults()
        for f in faults:
            run.log(f"[path] {f}")
        run.check("chip_path_faults", float(len(faults)), 0.0)


def checks_pass(checks: dict) -> bool:
    return all(isinstance(c["value"], (int, float))
               and not math.isnan(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())

