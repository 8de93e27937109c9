"""Level 1: jaxpr/HLO contract auditor.

A :class:`Contract` names one compiled program (at representative bucket
shapes) and the structural properties its trace must satisfy. The checks
run on jaxprs — no compilation or execution needed except the optional
HLO buffer bound — so the whole registry audits in seconds:

* ``forbidden_primitives`` — primitive names that must not appear
  anywhere in the trace (recursively through pjit/scan/while/cond
  sub-jaxprs). Scatter in load propagation, host callbacks, etc.
* ``forbid_f64`` — no equation may *produce* a float64 value. Checked on
  a trace taken under ``jax.enable_x64(True)`` so latent leaks
  (code relying on x64-off canonicalization) are caught, not masked.
* ``max_transient_elements`` — no equation output exceeds this element
  count: the bound that proves a blocked path streams slabs instead of
  materializing the dense intermediate.
* ``forbidden_shapes`` — symbolic shape patterns (e.g. ``("P","n","n")``
  with a ``dims`` mapping chosen so the axes are distinguishable) that
  must not appear as any equation output.
* ``gather_index_min_bits`` — every gather's index operand is at least
  this wide: the int16-resident tables must be widened to int32 before
  indexing (int16 gathers silently wrap past 32k nodes).
* ``out_dtypes`` — exact dtypes of the program outputs.
* ``ladder``/``ladder_expected`` — recompile-hazard check: hash the
  jaxpr at every raw size of a bucket ladder and require exactly the
  expected number of distinct programs (generalizing the
  ``COMPILE_COUNTS`` trace-time probe to a static proof).
* ``hlo``/``max_hlo_buffer_bytes`` — parse the *optimized* HLO
  (``utils.hlo_cost``) and bound the largest single buffer any
  instruction produces.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable

from .findings import Finding

REGISTRY_PATH = "src/repro/analysis/registry.py"

CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "callback",
                       "debug_callback")
SCATTER_PRIMITIVES = ("scatter", "scatter-add", "scatter-mul",
                      "scatter-min", "scatter-max")


@dataclasses.dataclass
class Contract:
    """One audited program: how to trace it and what its trace must obey.

    ``trace``/``trace_x64``/``ladder``/``hlo`` are thunks so building the
    registry stays import-cheap; nothing traces until the audit runs.
    """

    name: str
    trace: Callable[[], Any]                      # -> ClosedJaxpr
    description: str = ""
    forbidden_primitives: tuple[str, ...] = ()
    trace_x64: Callable[[], Any] | None = None    # -> ClosedJaxpr (x64 on)
    forbid_f64: bool = False
    max_transient_elements: int | None = None
    forbidden_shapes: tuple[tuple, ...] = ()      # symbolic dim patterns
    dims: dict | None = None                      # symbol -> concrete size
    gather_index_min_bits: int | None = None
    out_dtypes: tuple | None = None
    ladder: Callable[[], list[str]] | None = None  # -> jaxpr key per size
    ladder_expected: int | None = None
    hlo: Callable[[], str] | None = None          # -> optimized HLO text
    max_hlo_buffer_bytes: int | None = None
    bench: dict | None = None                     # benchmark variant export


def _sub_jaxprs(params: dict):
    """Sub-jaxprs referenced from an equation's params (pjit jaxpr=...,
    scan/while/cond branches, custom_* call jaxprs...)."""
    from jax.extend import core as jex_core

    jaxpr_types = (jex_core.Jaxpr, jex_core.ClosedJaxpr)
    for v in params.values():
        if isinstance(v, jaxpr_types):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                if isinstance(item, jaxpr_types):
                    yield item


def iter_eqns(jaxpr):
    """All equations in a (Closed)Jaxpr, recursively through sub-jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)   # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def jaxpr_key(closed) -> str:
    """Canonical hash of a trace. Two calls dispatch to the same compiled
    program iff their jaxprs print identically (same structure, shapes,
    dtypes; jaxpr var names are assigned deterministically per trace)."""
    return hashlib.sha1(str(closed).encode()).hexdigest()


def _aval(var):
    return getattr(var, "aval", None)


def _resolve_shape(pattern: tuple, dims: dict | None) -> tuple:
    return tuple(dims[d] if isinstance(d, str) else d
                 for d in pattern) if dims else tuple(pattern)


def audit_contract(c: Contract) -> list[Finding]:
    """Run every declared check of one contract; findings carry the
    contract name and anchor at the registry (the audit is a property of
    the traced program, not of one source line)."""
    findings: list[Finding] = []

    def add(rule: str, message: str) -> None:
        findings.append(Finding(rule=rule, path=REGISTRY_PATH, line=0,
                                message=message, contract=c.name))

    try:
        closed = c.trace()
    except Exception as e:   # a registry entry that fails to trace IS a finding
        add("audit-trace-error", f"tracing failed: {e!r}")
        return findings

    forbidden = set(c.forbidden_primitives)
    seen_forbidden: dict[str, int] = {}
    max_elems = 0
    max_elems_eqn = ""
    shape_hits: dict[tuple, str] = {}
    resolved = [(_resolve_shape(p, c.dims), p) for p in c.forbidden_shapes]

    for eqn in iter_eqns(closed):
        prim = eqn.primitive.name
        if prim in forbidden:
            seen_forbidden[prim] = seen_forbidden.get(prim, 0) + 1
        if c.gather_index_min_bits and prim == "gather":
            idx_aval = _aval(eqn.invars[1])
            if idx_aval is not None and idx_aval.dtype.kind in "iu" \
                    and idx_aval.dtype.itemsize * 8 < c.gather_index_min_bits:
                add("audit-gather-index",
                    f"gather indexed by {idx_aval.dtype.name} "
                    f"(< {c.gather_index_min_bits}-bit); widen table "
                    "indices before the gather")
        for out in eqn.outvars:
            aval = _aval(out)
            if aval is None or not hasattr(aval, "shape"):
                continue
            size = 1
            for d in aval.shape:
                size *= int(d)
            if size > max_elems:
                max_elems, max_elems_eqn = size, prim
            shape = tuple(int(d) for d in aval.shape)
            for concrete, symbolic in resolved:
                if shape == concrete and concrete not in shape_hits:
                    shape_hits[concrete] = prim

    for prim, count in sorted(seen_forbidden.items()):
        add("audit-forbidden-primitive",
            f"forbidden primitive {prim!r} appears {count}x in the trace")
    if c.max_transient_elements is not None \
            and max_elems > c.max_transient_elements:
        add("audit-transient-bound",
            f"largest transient is {max_elems} elements (a {max_elems_eqn} "
            f"output) > bound {c.max_transient_elements}")
    for concrete, prim in shape_hits.items():
        sym = next(s for r, s in resolved if r == concrete)
        add("audit-forbidden-shape",
            f"transient of forbidden shape {sym} (= {concrete}, a {prim} "
            "output) materialized")

    if c.out_dtypes is not None:
        outs = tuple(_aval(v).dtype for v in closed.jaxpr.outvars)
        expected = tuple(c.out_dtypes)
        import numpy as np
        if tuple(np.dtype(d) for d in outs) \
                != tuple(np.dtype(d) for d in expected):
            add("audit-out-dtype",
                f"output dtypes {tuple(d.name for d in outs)} != expected "
                f"{tuple(np.dtype(d).name for d in expected)}")

    if c.forbid_f64:
        x64_trace = c.trace_x64 or c.trace
        try:
            import jax
            with jax.enable_x64(True):
                closed64 = x64_trace()
        except Exception as e:
            add("audit-trace-error", f"x64 tracing failed: {e!r}")
        else:
            f64_prims: dict[str, int] = {}
            for eqn in iter_eqns(closed64):
                for out in eqn.outvars:
                    aval = _aval(out)
                    if aval is not None and getattr(aval, "dtype", None) \
                            is not None and aval.dtype.name == "float64":
                        name = eqn.primitive.name
                        f64_prims[name] = f64_prims.get(name, 0) + 1
            for prim, count in sorted(f64_prims.items()):
                add("audit-f64",
                    f"{prim} produces float64 {count}x under x64 — the "
                    "device path relies on canonicalization; cast "
                    "explicitly to float32")

    if c.ladder is not None:
        try:
            keys = c.ladder()
        except Exception as e:
            add("audit-trace-error", f"ladder tracing failed: {e!r}")
        else:
            distinct = len(set(keys))
            if c.ladder_expected is not None \
                    and distinct != c.ladder_expected:
                add("audit-recompile",
                    f"bucket ladder yields {distinct} distinct compiled "
                    f"programs over {len(keys)} sizes; expected "
                    f"{c.ladder_expected} — bucketing is fragmented or "
                    "over-merged")

    if c.hlo is not None and c.max_hlo_buffer_bytes is not None:
        from ..utils.hlo_cost import _shape_bytes, parse_computations
        try:
            hlo_text = c.hlo()
        except Exception as e:
            add("audit-trace-error", f"HLO lowering failed: {e!r}")
        else:
            worst, worst_op = 0, ""
            for comp in parse_computations(hlo_text).values():
                for inst in comp.instrs:
                    b = _shape_bytes(inst.shape)
                    if b > worst:
                        worst, worst_op = b, inst.op
            if worst > c.max_hlo_buffer_bytes:
                add("audit-hlo-buffer",
                    f"largest HLO buffer is {worst} bytes (a {worst_op}) "
                    f"> bound {c.max_hlo_buffer_bytes}")

    return findings


def audit_all(contracts: list[Contract]) -> list[Finding]:
    findings: list[Finding] = []
    for c in contracts:
        findings += audit_contract(c)
    return findings
