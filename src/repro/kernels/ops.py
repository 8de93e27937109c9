"""Jit'd public wrappers around the Pallas kernels: shape padding, dtype
handling, 2D/batched dispatch. On a TPU the kernels are compiled for the
chip; elsewhere they run in interpret mode (the kernel body runs through
the Pallas interpreter). ``REPRO_PALLAS_INTERPRET`` forces either mode.
On a TPU a kernel that fails to compile or dispatch raises: the backend
fallback ladder (``faults.harness.run_with_fallback``) is off there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs import metrics as _metrics
from ..utils import env as _env
from .minplus import minplus_pallas
from .flow_accum import flow_accum_pallas
from .ref import BIG, minplus_ref, flow_accumulate_ref


def _note_dispatch(op: str, backend: str, tile: int | None,
                   promoted: bool, n: int) -> None:
    """Telemetry (repro.obs): which kernel variant this dispatch selected
    and why. Counted once per *Python-level* call — for direct callers that
    is every call; for jitted callers (``edge_flows``, the genome
    pipelines) once per trace, i.e. the decision baked into each compiled
    program."""
    _metrics.counter(f"ops.{op}.dispatch", backend=backend,
                     tile=tile if tile is not None else "-",
                     promoted=promoted, n=n).inc()


def interpret_mode() -> bool:
    """Interpret Pallas kernels off the TPU and compile them on it, unless
    ``REPRO_PALLAS_INTERPRET`` asks for one mode explicitly."""
    forced = _env.get_str("REPRO_PALLAS_INTERPRET")
    if forced:
        return forced != "0"
    return jax.default_backend() != "tpu"


# The tiled load-propagation kernel lays destinations along lanes, so its
# compiled tile is a whole number of 128-lane vregs (``pick_tile`` sizes
# the XLA one-hot, which has no such constraint).
LANE_TILE = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _set_block(dst: jax.Array, src: jax.Array) -> jax.Array:
    """Corner-anchored pad-write dst[:s0, :s1, ...] = src as ONE
    dynamic_update_slice. The ``.at[slices].set`` spelling lowers to a
    scatter, which the audited device contracts forbid (scatter is the
    slow path on TPU; see repro.analysis.registry)."""
    return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                        (0,) * dst.ndim)


def _pick_block(dim: int, pref: int, mult: int) -> int:
    """Largest multiple of ``mult`` <= pref that keeps padding small."""
    if dim >= pref:
        return pref
    return max(_round_up(dim, mult), mult)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def minplus_matmul(a: jax.Array, b: jax.Array, bm: int | None = None,
                   bn: int | None = None, bk: int | None = None) -> jax.Array:
    """(min,+) product for 2D [M,K]x[K,N] or batched [B,M,K]x[B,K,N] inputs.

    Pads every dimension to the block grid with +BIG (never wins a min) and
    crops the result back.
    """
    squeeze = a.ndim == 2
    if squeeze:
        a, b = a[None], b[None]
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    B, M, K = a.shape
    _, _, N = b.shape
    bm = bm or _pick_block(M, 128, 8)
    bn = bn or _pick_block(N, 128, 128)
    bk = bk or _pick_block(K, 128, 8)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    ap = _set_block(jnp.full((B, Mp, Kp), BIG, jnp.float32), a)
    bp_ = _set_block(jnp.full((B, Kp, Np), BIG, jnp.float32), b)
    out = minplus_pallas(ap, bp_, bm=bm, bn=bn, bk=bk, interpret=interpret_mode())
    out = out[:, :M, :N]
    return out[0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("bp",))
def flow_accumulate(flow: jax.Array, cur: jax.Array, nxt: jax.Array,
                    amount: jax.Array, bp: int | None = None) -> jax.Array:
    """Scatter-as-matmul flow accumulation for [n,n] or batched [B,n,n] flow.

    Pads the pair axis with amount == 0 entries (index 0 targets contribute
    nothing) and the node axis to the lane multiple with zero flow.
    """
    squeeze = flow.ndim == 2
    if squeeze:
        flow, cur, nxt, amount = flow[None], cur[None], nxt[None], amount[None]
    B, n, _ = flow.shape
    P = cur.shape[1]
    bp = bp or _pick_block(P, 512, 8)
    Pp = _round_up(P, bp)
    n_lane = _round_up(n, 128)

    fl = _set_block(jnp.zeros((B, n_lane, n_lane), jnp.float32), flow)
    cu = _set_block(jnp.zeros((B, Pp), jnp.int32), cur)
    nx = _set_block(jnp.zeros((B, Pp), jnp.int32), nxt)
    am = _set_block(jnp.zeros((B, Pp), jnp.float32), amount)
    out = flow_accum_pallas(fl, cu, nx, am, bp=bp, interpret=interpret_mode())
    out = out[:, :n, :n].astype(flow.dtype)
    return out[0] if squeeze else out


def load_prop_tile(backend: str, n: int, batch: int) -> int | None:
    """The destination tile ``load_propagate`` runs ``backend`` with
    (None for the untiled backends)."""
    from .load_prop import pick_tile

    pinned = _env.get_opt_int("REPRO_LOAD_PROP_TILE")
    if backend == "xla_blocked":
        return pinned or pick_tile(n, batch)
    if backend in ("pallas_tiled", "pallas_tiled_interpret"):
        return pinned or LANE_TILE
    return None


def load_propagate(next_hop: jax.Array, load0: jax.Array,
                   max_hops: int | None = None, adaptive: bool = True,
                   backend: str | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """(W, flow) of ``load_propagate_counted``, without the hop counts."""
    w, flow, _ = load_propagate_counted(next_hop, load0, max_hops, adaptive,
                                        backend)
    return w, flow


def load_propagate_counted(next_hop: jax.Array, load0: jax.Array,
                           max_hops: int | None = None,
                           adaptive: bool = True,
                           backend: str | None = None
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Accumulated per-destination load + directed edge flows behind one
    backend-aware entry (the shared primitive of ``edge_flows``,
    ``edge_flows_load`` and the fused genome pipeline's proxies).

    next_hop: [n, n] or [B, n, n] routing table (src-major: next_hop[u, d]
    is u's next hop toward d; unreachable pairs self-loop). load0: matching
    dest-major initial load (load0[d, u] = traffic residing at u destined
    for d; the diagonal is masked off defensively). Returns

        W[d, u]    = Σ_j L_j[d, u]  (per-hop loads summed — every unit of
                     traffic counted once per hop departure from u),
        flow[u, v] = Σ_d [next_hop[u, d] = v] · W[d, u]  (directed edge
                     flows; traffic-weighted latency is Σ W · step_cost of
                     the chosen hop, see ``dse.genomes._eval_proxies``),
                     and
        hops[b]    = the hops the loop ran for design b, int32 (a scalar
                     for an [n, n] table).

    ``backend`` is one of ``load_prop.LOAD_PROP_BACKENDS``; ``None``
    auto-selects via ``load_prop.default_backend()`` — the fused Pallas
    kernel on TPU, the pure-XLA loop on CPU/GPU. Above
    ``REPRO_LOAD_PROP_FUSED_N`` (default 160) nodes the fused/dense
    backends are promoted to their destination-tiled twins
    (``pallas -> pallas_tiled``, ``xla -> xla_blocked``) so neither the
    whole-matrix VMEM pane nor the [B, n, n, n] one-hot ever materializes;
    ``REPRO_LOAD_PROP_TILE`` pins the tile size (else ``LANE_TILE`` for
    the tiled kernel and ``load_prop.pick_tile`` for ``xla_blocked``;
    compiled, a pinned Pallas tile must be a multiple of 128).
    ``adaptive`` swaps the fixed ``max_hops`` loop (default n − 1) for one
    that stops once the load has drained, at the routed diameter: per
    design in the fused kernel, per design and destination tile in the
    tiled one (a design's hops are its largest tile's), per batch in
    ``xla`` and per destination slab in ``xla_blocked`` (hops: the batch's
    or the largest slab's trip count). Drained hops propagate exact zeros,
    so W and flow are bit-identical to the fixed loop; a design with a
    self-looped unreachable pair never drains and runs all ``max_hops``.
    The env-driven default is resolved outside this function's own jit
    boundary, so direct callers pick up a flipped
    ``REPRO_LOAD_PROP_BACKEND`` on their next call — but *jitted* callers
    (``edge_flows``, the genome pipelines) resolve it at their trace time
    and keep the backend baked into their compiled programs; set the
    variable before first use.
    """
    from .load_prop import default_backend
    from ..faults.harness import maybe_chaos_fail, run_with_fallback

    if backend is None:
        backend = default_backend()
    n = next_hop.shape[-1]
    batch = next_hop.shape[0] if next_hop.ndim == 3 else 1
    fused_n = _env.get_int("REPRO_LOAD_PROP_FUSED_N")
    promote = {"xla": "xla_blocked", "pallas": "pallas_tiled",
               "pallas_interpret": "pallas_tiled_interpret"}
    promoted = n > fused_n and backend in promote
    if promoted:
        backend = promote[backend]

    # Off the TPU a failed dispatch falls back down the ladder
    # (pallas_tiled -> xla_blocked -> xla); on a TPU, or under
    # REPRO_STRICT_BACKEND=1, it raises. The chaos hook injects failures
    # for CI to prove the ladder keeps results green.
    def attempt(bk):
        tile = load_prop_tile(bk, n, batch)
        maybe_chaos_fail(bk)
        _note_dispatch("load_propagate", bk, tile, promoted, n)
        return _load_propagate(next_hop, load0, max_hops, adaptive, bk,
                               tile)

    return run_with_fallback("load_propagate", backend, attempt)


@functools.partial(jax.jit, static_argnames=("max_hops", "adaptive",
                                             "backend", "tile"))
def _load_propagate(next_hop: jax.Array, load0: jax.Array,
                    max_hops: int | None, adaptive: bool, backend: str,
                    tile: int | None = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    from .load_prop import (load_prop_pallas, load_prop_pallas_tiled,
                            load_prop_xla, load_prop_xla_blocked)

    squeeze = next_hop.ndim == 2
    if squeeze:
        next_hop, load0 = next_hop[None], load0[None]
    B, n, _ = next_hop.shape
    # trace-time probe: one increment per compiled program shape
    _metrics.counter("jit.compile", fn="kernels.load_propagate",
                     backend=backend, n=n, batch=B,
                     tile=tile if tile is not None else "-").inc()
    if max_hops is None:
        max_hops = max(n - 1, 1)
    if backend == "xla":
        w, flow, hops = load_prop_xla(next_hop, load0.astype(jnp.float32),
                                      max_hops, adaptive)
    elif backend == "xla_blocked":
        w, flow, hops = load_prop_xla_blocked(next_hop,
                                              load0.astype(jnp.float32),
                                              max_hops, adaptive, tile)
    else:
        n_lane = _round_up(n, 128)
        nh_p = jnp.tile(jnp.arange(n_lane, dtype=jnp.int32)[:, None],
                        (B, 1, n_lane))
        nh_p = _set_block(nh_p, next_hop.astype(jnp.int32))
        l0_p = _set_block(jnp.zeros((B, n_lane, n_lane), jnp.float32),
                          load0)
        if backend in ("pallas_tiled", "pallas_tiled_interpret"):
            w, flow, hops = load_prop_pallas_tiled(
                nh_p, l0_p, max_hops, tile, adaptive,
                interpret=backend == "pallas_tiled_interpret")
        else:
            w, flow, hops = load_prop_pallas(
                nh_p, l0_p, max_hops, adaptive,
                interpret=backend == "pallas_interpret")
        w, flow = w[:, :n, :n], flow[:, :n, :n]
    if squeeze:
        return w[0], flow[0], hops[0]
    return w, flow, hops


def apsp(d: jax.Array, n_iters: int | None = None,
         backend: str | None = None) -> jax.Array:
    """All-pairs path costs via min-plus squaring behind one backend-aware
    entry. d: [n, n] or [B, n, n] step costs (+inf/BIG = no edge; diagonal
    forced to 0).

    ``backend`` is one of ``apsp.APSP_BACKENDS``; ``None`` auto-selects via
    ``apsp.default_backend()`` — the fused Pallas kernel compiled for
    hardware on TPU, a pure-XLA doubling on CPU/GPU (where the Pallas
    interpreter would run the kernel body in Python). Above
    ``REPRO_APSP_FUSED_N`` (default 160) nodes the fused/dense backends are
    promoted to their blocked twins (``pallas -> pallas_tiled``,
    ``xla -> xla_blocked``) that stream [tile, n] slabs per squaring;
    ``REPRO_APSP_TILE`` pins the tile size. The fused Pallas path falls
    back to iterated minplus_matmul beyond the VMEM budget. The env-driven
    default is resolved *outside* the jit boundary, so flipping
    ``REPRO_APSP_BACKEND`` mid-process takes effect on the next call
    instead of being frozen into the jit cache."""
    from .apsp import default_backend
    from .load_prop import pick_tile
    from ..faults.harness import maybe_chaos_fail, run_with_fallback

    if backend is None:
        backend = default_backend()
    n = d.shape[-1]
    batch = d.shape[0] if d.ndim == 3 else 1
    fused_n = _env.get_int("REPRO_APSP_FUSED_N")
    promote = {"xla": "xla_blocked", "pallas": "pallas_tiled",
               "pallas_interpret": "pallas_tiled_interpret"}
    promoted = n > fused_n and backend in promote
    if promoted:
        backend = promote[backend]

    def attempt(bk):
        tile = None
        if bk in ("xla_blocked", "pallas_tiled", "pallas_tiled_interpret"):
            tile = _env.get_opt_int("REPRO_APSP_TILE") or pick_tile(n, batch)
        maybe_chaos_fail(bk)
        _note_dispatch("apsp", bk, tile, promoted, n)
        return _apsp(d, n_iters, bk, tile)

    return run_with_fallback("apsp", backend, attempt)


@functools.partial(jax.jit, static_argnames=("n_iters", "backend", "tile"))
def _apsp(d: jax.Array, n_iters: int | None, backend: str,
          tile: int | None = None) -> jax.Array:
    import math
    from .apsp import (MAX_FUSED_N, apsp_pallas, apsp_pallas_tiled,
                       apsp_xla, apsp_xla_blocked)

    squeeze = d.ndim == 2
    if squeeze:
        d = d[None]
    B, n, _ = d.shape
    # trace-time probe: one increment per compiled program shape
    _metrics.counter("jit.compile", fn="kernels.apsp", backend=backend,
                     n=n, batch=B,
                     tile=tile if tile is not None else "-").inc()
    if n_iters is None:
        n_iters = max(1, math.ceil(math.log2(max(n - 1, 2))) + 1)
    d = jnp.minimum(jnp.where(jnp.isfinite(d), d, BIG), BIG)
    eye = jnp.where(jnp.eye(n, dtype=bool), jnp.float32(0.0),
                    jnp.float32(BIG))
    d = jnp.minimum(d.astype(jnp.float32), eye[None])
    n_lane = _round_up(n, 128)
    if backend == "xla":
        out = apsp_xla(d, n_iters)
    elif backend == "xla_blocked":
        out = apsp_xla_blocked(d, n_iters, tile)
    elif backend in ("pallas_tiled", "pallas_tiled_interpret"):
        dp = _set_block(jnp.full((B, n_lane, n_lane), BIG, jnp.float32),
                        d)
        eye_p = jnp.where(jnp.eye(n_lane, dtype=bool), jnp.float32(0.0),
                          jnp.float32(BIG))
        dp = jnp.minimum(dp, eye_p[None])
        out = apsp_pallas_tiled(
            dp, n_iters, tile,
            interpret=backend == "pallas_tiled_interpret")[:, :n, :n]
    elif n_lane <= MAX_FUSED_N:
        dp = _set_block(jnp.full((B, n_lane, n_lane), BIG, jnp.float32),
                        d)
        eye_p = jnp.where(jnp.eye(n_lane, dtype=bool), jnp.float32(0.0),
                          jnp.float32(BIG))
        dp = jnp.minimum(dp, eye_p[None])
        out = apsp_pallas(dp, n_iters,
                          interpret=backend == "pallas_interpret")[:, :n, :n]
    else:
        def body(_, m):
            return jnp.minimum(minplus_matmul(m, m), BIG)
        out = jax.lax.fori_loop(0, n_iters, body, d)
    out = jnp.where(out >= BIG * 0.5, jnp.inf, out)
    return out[0] if squeeze else out


__all__ = ["minplus_matmul", "flow_accumulate", "apsp", "load_propagate",
           "load_propagate_counted", "load_prop_tile",
           "minplus_ref", "flow_accumulate_ref", "BIG"]
