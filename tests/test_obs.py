"""Observability layer tests (ISSUE 7).

Covers: span nesting/depth/thread attribution, the ring-buffer bound,
Chrome-trace export schema, the JSONL schema validator, histogram
percentile math against a numpy reference, the metrics registry counters
(structure cache + the generalized COMPILE_COUNTS probe across a
10-generation optimizer run), disabled-mode cheapness (shared no-op span,
no net allocation growth), the structured logging root's
print-compatibility, telemetry derivation, and checkpoint version-stamp
warnings (warn, never crash).
"""
import json
import logging
import threading
import tracemalloc

import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs.log import configure, get_logger
from repro.obs.trace import TRACER, Tracer, _NULL_SPAN, span


# ---------------------------------------------------------------------------
# spans: nesting, depth, thread attribution, ring buffer
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_order():
    tr = Tracer(enabled=True)
    with tr.span("outer", phase=1):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    events = tr.to_dicts()
    # export order is start-time order: outer opens before its children
    assert [e["name"] for e in events] == ["outer", "inner", "inner"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert all(e["depth"] == 1 for e in by_name["inner"])
    outer = by_name["outer"][0]
    assert outer["depth"] == 0
    assert outer["attrs"] == {"phase": 1}
    # the outer span brackets both inner spans
    for e in by_name["inner"]:
        assert outer["ts_us"] <= e["ts_us"]
        assert (e["ts_us"] + e["dur_us"]
                <= outer["ts_us"] + outer["dur_us"] + 1e-6)


def test_span_set_attaches_attrs_after_entry():
    tr = Tracer(enabled=True)
    with tr.span("work", a=1) as sp:
        sp.set(result=42)
    (e,) = tr.to_dicts()
    assert e["attrs"] == {"a": 1, "result": 42}


def test_span_thread_attribution_and_independent_depth():
    tr = Tracer(enabled=True)

    def worker():
        with tr.span("thread_work"):
            pass

    with tr.span("main_outer"):
        t = threading.Thread(target=worker, name="obs-worker")
        t.start()
        t.join()
    events = {e["name"]: e for e in tr.to_dicts()}
    assert events["thread_work"]["thread"] == "obs-worker"
    assert events["main_outer"]["thread"] == "MainThread"
    # depth is tracked per thread: the worker's span is a root on its
    # thread even though the main thread was inside a span
    assert events["thread_work"]["depth"] == 0
    assert events["thread_work"]["tid"] != events["main_outer"]["tid"]


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = Tracer(maxlen=4, enabled=True)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    events = tr.to_dicts()
    assert len(events) == 4
    assert [e["name"] for e in events] == ["s6", "s7", "s8", "s9"]
    assert tr.n_dropped == 6


def test_enable_clears_and_rebases_origin():
    tr = Tracer(enabled=True)
    with tr.span("old"):
        pass
    tr.enable(clear=True)
    with tr.span("new"):
        pass
    events = tr.to_dicts()
    assert [e["name"] for e in events] == ["new"]
    assert events[0]["ts_us"] >= 0


# ---------------------------------------------------------------------------
# disabled mode: shared no-op, no net allocations
# ---------------------------------------------------------------------------

def test_disabled_span_returns_shared_singleton():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a"), tr.span("b", k=1)
    assert s1 is s2 is _NULL_SPAN
    assert not TRACER.enabled
    assert span("module_level") is _NULL_SPAN
    # the null span supports the full protocol
    with s1 as sp:
        sp.set(anything=1)


def test_disabled_span_has_no_net_allocation_growth():
    tr = Tracer(enabled=False)

    def burst(n):
        for _ in range(n):
            with tr.span("hot", a=1, b=2):
                pass

    burst(100)  # warm up caches/bytecode
    tracemalloc.start()
    before, _ = tracemalloc.get_traced_memory()
    burst(5000)
    after, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # transient kwargs dicts are freed immediately; nothing accumulates
    assert after - before < 16 * 1024, (before, after)
    assert tr.to_dicts() == []


# ---------------------------------------------------------------------------
# export formats + schema validation
# ---------------------------------------------------------------------------

def _traced_tracer():
    tr = Tracer(enabled=True)
    with tr.span("outer", n=16):
        with tr.span("inner", obj=object()):
            pass
    t = threading.Thread(
        target=lambda: tr.span("threaded").__enter__().__exit__(),
        name="exporter")
    t.start()
    t.join()
    return tr


def test_jsonl_export_roundtrips_and_validates(tmp_path):
    tr = _traced_tracer()
    path = tmp_path / "run.trace.jsonl"
    n = tr.export_jsonl(str(path))
    events = obs_report.load_trace(str(path))
    assert len(events) == n == 3
    assert obs_report.validate_trace(events) == []


def test_chrome_export_schema(tmp_path):
    tr = _traced_tracer()
    path = tmp_path / "run.chrome.json"
    tr.export_chrome(str(path))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert any(e["name"] == "process_name" for e in meta)
    thread_names = {e["args"]["name"] for e in meta
                    if e["name"] == "thread_name"}
    assert {"MainThread", "exporter"} <= thread_names
    assert len(spans) == 3
    for e in spans:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["tid"], int) and isinstance(e["pid"], int)
    # non-JSON attr values are stringified, not dropped
    inner = next(e for e in spans if e["name"] == "inner")
    assert isinstance(inner["args"]["obj"], str)
    assert doc["otherData"]["dropped_events"] == 0


def test_validate_trace_rejects_bad_events():
    assert obs_report.validate_trace([]) == ["trace contains no spans"]
    good = {"name": "x", "ts_us": 0.0, "dur_us": 1.0, "tid": 1,
            "thread": "MainThread", "depth": 0}
    assert obs_report.validate_trace([good]) == []
    missing = {k: v for k, v in good.items() if k != "dur_us"}
    assert any("dur_us" in e for e in obs_report.validate_trace([missing]))
    wrong_type = dict(good, tid="not-an-int")
    assert any("tid" in e for e in obs_report.validate_trace([wrong_type]))
    negative = dict(good, ts_us=-5.0)
    assert any("ts_us" in e for e in obs_report.validate_trace([negative]))
    bad_attrs = dict(good, attrs=[1, 2])
    assert any("attrs" in e for e in obs_report.validate_trace([bad_attrs]))


# ---------------------------------------------------------------------------
# metrics: counters, gauges, histogram percentiles vs numpy
# ---------------------------------------------------------------------------

def test_registry_series_identity_by_name_and_labels():
    reg = obs_metrics.Registry()
    a = reg.counter("hits", backend="xla")
    b = reg.counter("hits", backend="xla")
    c = reg.counter("hits", backend="pallas")
    assert a is b and a is not c
    a.inc()
    a.inc(2)
    assert a.value == 3 and c.value == 0
    g = reg.gauge("rate")
    g.set(0.5)
    snap = reg.snapshot()
    assert {"name": "hits", "labels": {"backend": "xla"}, "value": 3} \
        in snap["counters"]
    assert snap["gauges"] == [{"name": "rate", "labels": {}, "value": 0.5}]


def test_registry_reset_zeroes_in_place():
    # instrumentation sites cache metric objects at module level, so reset
    # must zero them in place, not discard them
    reg = obs_metrics.Registry()
    c = reg.counter("hits")
    h = reg.histogram("lat")
    c.inc(5)
    h.observe(1.0)
    reg.reset()
    assert c.value == 0 and h.count == 0 and h.sum == 0.0
    assert reg.counter("hits") is c    # same object, still registered
    c.inc()
    assert reg.snapshot()["counters"][0]["value"] == 1


def test_histogram_exact_stats_and_percentiles_vs_numpy():
    rng = np.random.default_rng(7)
    values = np.concatenate([
        rng.lognormal(mean=-4.0, sigma=1.5, size=4000),
        rng.uniform(1e-6, 5.0, size=1000),
    ])
    reg = obs_metrics.Registry()
    h = reg.histogram("lat_s")
    for v in values:
        h.observe(float(v))
    assert h.count == len(values)
    assert h.sum == pytest.approx(values.sum(), rel=1e-9)
    assert h.min == values.min() and h.max == values.max()
    assert h.mean == pytest.approx(values.mean(), rel=1e-9)
    # bucket ladder grows by 1.25x, so a percentile estimate (the bucket's
    # upper edge) is within one bucket width of the exact value
    for q in (50, 90, 99):
        exact = float(np.percentile(values, q, method="inverted_cdf"))
        est = h.percentile(q)
        assert exact / 1.001 <= est <= exact * 1.2501, (q, exact, est)
    assert h.min <= h.percentile(0.001) <= h.percentile(99.999) <= h.max


def test_histogram_edge_cases():
    h = obs_metrics.Histogram("x", {})
    assert h.percentile(50) is None and h.mean is None
    d = h.to_dict()
    assert d["count"] == 0 and d["min"] is None and d["p99"] is None
    h.observe(0.0)       # below the lowest bound
    h.observe(1e9)       # overflow bucket
    assert h.count == 2 and h.percentile(100) == 1e9
    # low percentile lands in the first bucket: its upper edge (1e-7),
    # bounded by the observed extrema
    assert h.min <= h.percentile(1) <= obs_metrics._DEFAULT_BUCKETS[0]


# ---------------------------------------------------------------------------
# instrumentation correctness across a real optimizer run
# ---------------------------------------------------------------------------

def _counter_sum(name, label_filter=None):
    total = 0
    for c in obs_metrics.REGISTRY.series("Counter", name):
        if label_filter is None or label_filter(c.labels):
            total += c.value
    return total


def test_cache_and_compile_counters_across_ten_generations():
    import jax
    from repro.dse.genomes import COMPILE_COUNTS, reset_compile_counts
    from repro.opt import (AdjacencySpace, EvolutionarySearch, OptRunner,
                           PopulationEvaluator)

    jax.clear_caches()
    reset_compile_counts()
    is_adj = lambda labels: labels.get("fn") == "genomes.adjacency"
    compiles0 = _counter_sum("jit.compile", is_adj)
    space = AdjacencySpace(n_chiplets=11, max_degree=4)
    ev = PopulationEvaluator(space)
    opt = EvolutionarySearch(space, ev, seed=0, pop_size=10)
    OptRunner(opt).run(10)
    adjacency = {k: v for k, v in COMPILE_COUNTS.items()
                 if k[0] == "adjacency"}
    # the registry mirror of the COMPILE_COUNTS probe sees the same single
    # compile for the whole run (one program per bucketed shape)
    assert sum(adjacency.values()) == 1
    assert _counter_sum("jit.compile", is_adj) - compiles0 == 1


def test_structure_cache_counters_track_instance_stats():
    from repro.core.structure_cache import StructureCache, StructureEntry
    from repro.core.structure_cache import GLOBAL_STRUCTURE_CACHE  # noqa: F401

    hits0 = _counter_sum("structure_cache.hit")
    misses0 = _counter_sum("structure_cache.miss")
    evicts0 = _counter_sum("structure_cache.evict")
    cache = StructureCache(maxsize=2)
    assert cache.get("a") is None                       # miss
    cache.put("a", StructureEntry(arrays=None))
    assert cache.get("a") is not None                   # hit
    cache.put("b", StructureEntry(arrays=None))
    cache.put("c", StructureEntry(arrays=None))         # evicts "a"
    assert cache.get("a") is None                       # miss
    assert _counter_sum("structure_cache.hit") - hits0 == cache.hits == 1
    assert (_counter_sum("structure_cache.miss") - misses0
            == cache.misses == 2)
    assert _counter_sum("structure_cache.evict") - evicts0 == 1


def test_kernel_dispatch_counters():
    import jax.numpy as jnp
    from repro.kernels import ops

    next_hop = jnp.tile(jnp.arange(8, dtype=jnp.int32)[:, None], (1, 8))
    load0 = jnp.zeros((8, 8), jnp.float32)
    before = _counter_sum("ops.load_propagate.dispatch")
    ops.load_propagate(next_hop, load0)
    after = _counter_sum("ops.load_propagate.dispatch")
    assert after - before == 1
    rows = [c for c in obs_metrics.REGISTRY.series(
        "Counter", "ops.load_propagate.dispatch") if c.value]
    assert all({"backend", "tile", "promoted", "n"} <= set(r.labels)
               for r in rows)


# ---------------------------------------------------------------------------
# structured logging root
# ---------------------------------------------------------------------------

@pytest.fixture
def info_logging():
    configure(level="info", force=True)
    yield
    configure(level="info", force=True)


def test_log_info_is_print_compatible(capsys, info_logging):
    log = get_logger("testmod")
    log.info("[opt] gen 3/10 evals=48")
    assert capsys.readouterr().out == "[opt] gen 3/10 evals=48\n"


def test_log_structured_fields_render_as_kv(capsys, info_logging):
    log = get_logger("testmod")
    log.info("[opt] gen done", gen=3, evals=48)
    assert capsys.readouterr().out == "[opt] gen done gen=3 evals=48\n"


def test_log_levels_gate_output(capsys, info_logging):
    log = get_logger("testmod")
    log.debug("hidden at info")
    assert capsys.readouterr().out == ""
    configure(level="debug", force=True)
    log.debug("visible at debug")
    assert capsys.readouterr().out == "visible at debug\n"
    configure(level="quiet", force=True)
    log.info("hidden at quiet")
    log.warning("warnings pass quiet")
    assert capsys.readouterr().out == "warnings pass quiet\n"
    assert log.log("info", "string levels resolve") is None


def test_log_single_root(info_logging):
    root = logging.getLogger("repro")
    assert len(root.handlers) == 1
    assert get_logger("a")._logger.parent is root
    assert configure() is root  # idempotent

def test_log_rejects_unknown_level():
    with pytest.raises(ValueError):
        configure(level="loud", force=True)
    configure(level="info", force=True)


# ---------------------------------------------------------------------------
# report: telemetry derivation + summarize on synthetic data
# ---------------------------------------------------------------------------

def _synthetic_snapshot():
    return {
        "counters": [
            {"name": "opt.async.host_s", "labels": {}, "value": 3.0},
            {"name": "opt.async.wait_s", "labels": {}, "value": 1.0},
            {"name": "opt.evals_completed", "labels": {}, "value": 240},
            {"name": "structure_cache.hit", "labels": {}, "value": 30},
            {"name": "structure_cache.miss", "labels": {}, "value": 10},
            {"name": "jit.compile",
             "labels": {"fn": "genomes.adjacency", "shape": "8/16"},
             "value": 1},
            {"name": "ops.apsp.dispatch",
             "labels": {"backend": "pallas", "tile": 128,
                        "promoted": False, "n": 256}, "value": 4},
            {"name": "faults.rows", "labels": {}, "value": 330},
            {"name": "faults.disconnected_rows", "labels": {}, "value": 12},
            {"name": "faults.infeasible", "labels": {}, "value": 7},
            {"name": "kernels.load_propagate.hops",
             "labels": {"max_hops": 63}, "value": 1650},
            {"name": "kernels.load_propagate.designs",
             "labels": {"max_hops": 63}, "value": 330},
        ],
        "gauges": [],
        "histograms": [
            {"name": "opt.generation_s", "labels": {}, "count": 10,
             "sum": 1.0, "min": 0.05, "max": 0.3, "mean": 0.1,
             "p50": 0.1, "p90": 0.2, "p99": 0.3},
        ],
    }


def test_telemetry_derivation():
    t = obs_report.telemetry(_synthetic_snapshot())
    assert t["async_overlap_pct"] == 75.0
    assert t["structure_cache"] == {"hits": 30, "misses": 10,
                                    "hit_rate": 0.75}
    assert t["jit_compiles"]["total"] == 1
    assert "fn=genomes.adjacency,shape=8/16" in t["jit_compiles"]["by_shape"]
    disp = t["kernel_dispatch"]["apsp"]
    assert disp["backend=pallas,n=256,promoted=False,tile=128"] == 4
    assert t["generations"]["p99_s"] == 0.3
    # completed evaluations over the generations' summed time
    assert t["evals_per_s"] == 240.0
    assert t["faults"] == {"rows": 330, "disconnected_rows": 12,
                           "infeasible": 7}
    assert t["load_propagate_hops"] == [
        {"max_hops": 63, "designs": 330, "hops": 1650, "mean_hops": 5.0}]


def test_telemetry_degrades_on_empty_snapshot():
    t = obs_report.telemetry({"counters": [], "gauges": [],
                              "histograms": []})
    assert t["async_overlap_pct"] is None
    assert t["evals_per_s"] is None
    assert t["structure_cache"]["hit_rate"] is None
    assert t["jit_compiles"]["total"] == 0
    assert t["kernel_dispatch"] == {}
    assert t["faults"] is None
    assert t["load_propagate_hops"] is None


def test_summarize_and_format_report():
    events = [
        {"name": "opt.generation", "ts_us": 0.0, "dur_us": 1000.0,
         "tid": 1, "thread": "MainThread", "depth": 0},
        {"name": "opt.generation", "ts_us": 1500.0, "dur_us": 500.0,
         "tid": 1, "thread": "MainThread", "depth": 0},
    ]
    summary = obs_report.summarize(events, _synthetic_snapshot())
    assert summary["trace"]["n_spans"] == 2
    assert summary["trace"]["duration_s"] == 0.002
    gen = summary["spans"]["opt.generation"]
    assert gen["count"] == 2 and gen["total_s"] == 0.0015
    text = obs_report.format_report(summary)
    assert "async overlap:" in text and "75.0%" in text
    assert "evals/s:              240 completed" in text
    assert "fault grid:           330 scenario rows, 12 disconnected" in text
    assert ("load-prop hops:       5 a design of at most 63 (330 designs)"
            in text)
    assert "opt.generation" in text


def test_dump_run_writes_all_artifacts(tmp_path):
    tr = _traced_tracer()
    reg = obs_metrics.Registry()
    reg.counter("structure_cache.hit").inc(5)
    prefix = str(tmp_path / "run")
    summary = obs_report.dump_run(prefix, tracer=tr, registry=reg)
    for suffix in (".trace.jsonl", ".chrome.json", ".metrics.json",
                   ".report.json"):
        assert (tmp_path / ("run" + suffix)).exists(), suffix
    with open(prefix + ".report.json") as f:
        on_disk = json.load(f)
    assert on_disk["trace"]["n_spans"] == summary["trace"]["n_spans"] == 3
    errors = obs_report.validate_trace(
        obs_report.load_trace(prefix + ".trace.jsonl"))
    assert errors == []


# ---------------------------------------------------------------------------
# checkpoint version stamps: warn, never crash
# ---------------------------------------------------------------------------

def test_version_stamp_roundtrip_and_mismatch():
    from repro.utils.version import check_version_stamp, version_stamp

    stamp = version_stamp(config_hash="abc")
    assert check_version_stamp(stamp, config_hash="abc") == []
    assert check_version_stamp(None) \
        == ["checkpoint predates version stamping (no versions recorded)"]
    tampered = dict(stamp, jax="0.0.1")
    problems = check_version_stamp(tampered, config_hash="abc")
    assert len(problems) == 1 and "jax=0.0.1" in problems[0]
    problems = check_version_stamp(stamp, config_hash="other")
    assert any("config_hash" in p for p in problems)


def test_opt_resume_warns_on_version_mismatch(tmp_path, capsys,
                                              info_logging):
    from repro.opt import (AdjacencySpace, PopulationEvaluator, RandomSearch,
                           OptRunner)

    ckpt = str(tmp_path / "opt_ckpt.json")
    space = AdjacencySpace(n_chiplets=6, max_degree=3)

    def build():
        return RandomSearch(space, PopulationEvaluator(space), seed=0,
                            batch_size=4)

    OptRunner(build(), checkpoint_path=ckpt).run(1)
    with open(ckpt) as f:
        envelope = json.load(f)
    state = envelope["state"]            # format-2 checksummed envelope
    assert "versions" in state and "repro" in state["versions"]
    state["versions"]["jax"] = "0.0.1"
    from repro.faults.harness import json_digest
    envelope["sha256"] = json_digest(state)   # keep the envelope valid
    with open(ckpt, "w") as f:
        json.dump(envelope, f)
    capsys.readouterr()
    runner = OptRunner(build(), checkpoint_path=ckpt)   # resumes + warns
    out = capsys.readouterr().out
    assert "resume warning" in out and "jax=0.0.1" in out
    assert runner.optimizer.generation == 1             # resume still worked


def test_ckpt_manifest_versions_warn_on_mismatch(tmp_path, capsys,
                                                 info_logging):
    from repro.ckpt.checkpoint import restore_checkpoint, save_checkpoint

    tree = {"w": np.arange(6, dtype=np.float32)}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, tree, config_hash="h1")
    manifest_path = tmp_path / "ckpt" / "step_1" / "manifest.json"
    with open(manifest_path) as f:
        manifest = json.load(f)
    assert manifest["versions"]["config_hash"] == "h1"
    manifest["versions"]["repro"] = "99.0.0"
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    capsys.readouterr()
    restored, step = restore_checkpoint(d, tree, config_hash="h1")
    out = capsys.readouterr().out
    assert "restore warning" in out and "repro=99.0.0" in out
    assert step == 1
    np.testing.assert_array_equal(restored["w"], tree["w"])
