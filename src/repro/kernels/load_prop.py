"""Pallas TPU kernel: fused per-destination load propagation (ISSUE 5).

The scatter-free load-propagation loop is the proxy engine's hot loop: the
state L[d, u] (traffic residing at u, destined for d) is propagated one hop
per step through the static routing table, the per-hop loads are summed into
W = Σ_j L_j, and both proxies fall out of W — edge flows via one contraction
with the next-hop one-hot, traffic-weighted latency via the per-hop step
costs. Three call sites used to carry near-identical copies of this loop
(``core/throughput.edge_flows``, ``edge_flows_load``,
``dse/genomes._eval_proxies``); they all dispatch through
``kernels.ops.load_propagate`` now.

Done as XLA ops each hop materializes the [n, n, n] one-hot in HBM and runs
a batch of small gemvs per step. For the DSE regime (n ≤ a few hundred) the
whole per-design state is a handful of [n, n] tiles, so the entire
propagation fuses into ONE pallas_call per design: next-hop table and load
live in VMEM/registers, the one-hot comparisons are regenerated from iota
on the fly (never materialized), and the final flow contraction happens in
the same kernel — zero intermediate HBM traffic.

Routing is shortest-path in hops, so a design's load has drained after D
hops, D its routed diameter, and every later hop would propagate exact
zeros. With ``adaptive`` every backend stops there: the kernels per grid
step (per design; per design and destination tile when tiled) once the
load pane is empty, the XLA loops once the batch's (or slab's) load is.
``max_hops`` stays the safety bound: a table whose unreachable pairs
self-loop never drains and runs all of it. The result is bit-identical
to the fixed ``max_hops`` loop either way, and every backend also returns
the hops it ran for each design (the batch or slab maximum for XLA).

Backend selection mirrors ``kernels.apsp``: compiled Pallas on TPU, the
pure-XLA loop on CPU/GPU (where the Pallas interpreter would run the kernel
body in Python). ``REPRO_LOAD_PROP_BACKEND`` overrides (``pallas`` |
``pallas_interpret`` | ``xla`` | ``pallas_tiled`` |
``pallas_tiled_interpret`` | ``xla_blocked``); the legacy
``REPRO_PALLAS_INTERPRET=0`` still forces compiled Pallas everywhere.
On a TPU the compiled kernel is the only path: a failure to compile or
dispatch raises (see ``faults.harness.strict_backend``).

Large-n tier (ISSUE 6): the fused kernel keeps the whole [n, n] state pane
in VMEM and the XLA loop materializes the [B, n, n, n] one-hot, so both
blow up past n ≈ 128–256. The ``*_tiled`` / ``xla_blocked`` variants
exploit that the propagation is *independent per destination*: they
stream destination slabs of the next-hop table and load matrix (a 2-D
grid batch × destination-tile of src-major ``[n, tile]`` lane slabs for
Pallas, a ``lax.scan`` over ``[tile, n]`` slabs for XLA), accumulating
the shared flow matrix across tiles. Per-tile working set is
O(tile · n) state + O(B · tile · n²) transient one-hot for XLA —
bounded by the tile size regardless of n.
``kernels.ops.load_propagate`` auto-switches to the tiled variant above
``REPRO_LOAD_PROP_FUSED_N`` (default 160) nodes; ``REPRO_LOAD_PROP_TILE``
overrides the auto-chosen tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import env as _env

# The one-hot contractions of the XLA loops run on a TPU's MXU, whose
# default f32 precision rounds the loads to bf16; HIGHEST keeps them f32
# (no effect on the CPU).
_EXACT = jax.lax.Precision.HIGHEST

LOAD_PROP_BACKENDS = ("pallas", "pallas_interpret", "xla",
                      "pallas_tiled", "pallas_tiled_interpret", "xla_blocked")


def default_backend() -> str:
    """Pick the load-propagation backend for the current runtime.

    Priority: ``REPRO_LOAD_PROP_BACKEND`` env var, then compiled Pallas on
    TPU (or anywhere when ``REPRO_PALLAS_INTERPRET=0``), else the XLA
    fallback.
    """
    env = _env.get_str("REPRO_LOAD_PROP_BACKEND")
    if env:
        if env not in LOAD_PROP_BACKENDS:
            raise ValueError(f"REPRO_LOAD_PROP_BACKEND={env!r}; "
                             f"options: {LOAD_PROP_BACKENDS}")
        return env
    if jax.default_backend() == "tpu":
        return "pallas"
    if _env.get_str("REPRO_PALLAS_INTERPRET") == "0":
        return "pallas"
    return "xla"


def hop_loop(step, carry, max_hops: int, adaptive: bool, active):
    """The one fixed-length/adaptive hop-iteration scaffold every
    propagation loop in the package uses, the Pallas kernels' included.

    ``step``: carry -> carry (one hop). ``active``: carry -> bool scalar;
    with ``adaptive`` the loop stops as soon as it goes False (``max_hops``
    stays the safety bound), otherwise it runs exactly ``max_hops`` steps
    (same result when extra steps are no-ops — e.g. converged loads
    propagate zeros). Returns (hops run as an int32 scalar, final carry)."""
    if adaptive:
        def cond(state):
            i, c = state
            return (i < max_hops) & active(c)

        def body(state):
            i, c = state
            return i + 1, step(c)

        return jax.lax.while_loop(cond, body, (jnp.int32(0), carry))

    # a fori_loop (not a bare scan): Mosaic lowers only the indexed form
    return (jnp.int32(max_hops),
            jax.lax.fori_loop(0, max_hops, lambda _, c: step(c), carry))


def load_prop_xla(next_hop: jax.Array, load0: jax.Array, max_hops: int,
                  adaptive: bool
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pure-XLA batched load propagation: the CPU/GPU fallback behind
    ``ops.load_propagate``.

    next_hop: [B, n, n] int (src-major: next_hop[u, d]); load0: [B, n, n]
    f32 dest-major (load0[d, u], diagonal zero). Returns (W, flow, hops):
    the accumulated dest-major load W[d, u] = Σ_j L_j[d, u], the directed
    edge flows flow[u, v] = Σ_d [next_hop[u, d] = v] · W[d, u], and the
    loop's trip count for every design [B] (one loop runs the batch).

    The one-hot oh[d, u, v] = [next_hop[u, d] = v] is built ONCE (the table
    is static across hops); each hop is one batched contraction, with
    delivered load (v = d) masked off after every step.
    """
    B, n, _ = next_hop.shape
    ids = jnp.arange(n, dtype=next_hop.dtype)
    offdiag = ~jnp.eye(n, dtype=bool)
    nhT = next_hop.swapaxes(-1, -2)                             # [B, d, u]
    oh = (nhT[:, :, :, None] == ids).astype(jnp.float32)        # [B, d, u, v]
    load0 = jnp.where(offdiag, load0, 0.0)

    def step(state):
        load, total = state
        total = total + load
        load = jnp.where(offdiag,
                         jnp.einsum("bduv,bdu->bdv", oh, load,
                                    precision=_EXACT), 0.0)
        return load, total

    def still_active(state):
        return jnp.any(state[0] > 0)

    hops, (_, total) = hop_loop(step, (load0, jnp.zeros_like(load0)),
                                max_hops, adaptive, still_active)
    flow = jnp.einsum("bduv,bdu->buv", oh, total, precision=_EXACT)
    return total, flow, jnp.full((B,), hops, jnp.int32)


def _kernel_hops(ld_ref, propagate, offdiag, max_hops: int, adaptive: bool):
    """Both kernels' hop loop over the load pane ``ld_ref`` (the grid
    step's whole state): each hop adds the standing load into W^T and
    propagates it. Adaptive, it stops once the pane is empty (loads are
    non-negative, so its max is 0). Returns (hops run, W^T)."""
    def step(state):
        total, _ = state
        total = total + ld_ref[...]
        new = jnp.where(offdiag, propagate(), 0.0)
        ld_ref[...] = new
        return total, jnp.max(new) > 0

    init = (jnp.zeros(ld_ref.shape, jnp.float32), jnp.max(ld_ref[...]) > 0)
    hops, (total, _) = hop_loop(step, init, max_hops, adaptive,
                                lambda state: state[1])
    return hops, total


def _load_prop_kernel(max_hops: int, adaptive: bool, nh_ref, l0_ref, w_ref,
                      f_ref, h_ref, ld_ref):
    """One design per grid step: the whole propagation plus the flow
    contraction, with every one-hot regenerated from iota comparisons
    inside VMEM (the [n, n, n] tensor never exists). ``h_ref`` receives
    the hops the step ran, across its lanes.

    State is src-major — ld_ref[u, d] is the load at u destined for d —
    so the per-source loop reads row u of the next-hop table and of the
    load from their refs on the sublane axis (``pl.ds``). Mosaic refuses a
    slice of a loaded value at a traced lane index, which the dest-major
    layout needed. Outputs are src-major too: W^T[u, d] and flow^T[v, u]."""
    n = l0_ref.shape[-1]
    viota = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    liota = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    offdiag = viota != liota
    ld_ref[...] = jnp.where(offdiag, l0_ref[...], 0.0)

    def propagate():
        # new[v, d] = Σ_u [nh[u, d] = v] · load[u, d]: the scatter over v
        # as a broadcast-compare-add sweep over source rows.
        def body(u, acc):
            idx = nh_ref[pl.ds(u, 1), :]                        # [1, d]
            lu = ld_ref[pl.ds(u, 1), :]                         # [1, d]
            return acc + jnp.where(viota == idx, lu, 0.0)

        return jax.lax.fori_loop(0, n, body,
                                 jnp.zeros((n, n), jnp.float32))

    hops, w = _kernel_hops(ld_ref, propagate, offdiag, max_hops, adaptive)
    w_ref[...] = w
    h_ref[...] = jnp.full(h_ref.shape, hops, jnp.int32)

    # flow^T[v, u] = Σ_d [nh[u, d] = v] · W^T[u, d]: column u of flow^T is
    # a lane reduction, placed by a lane-iota select.
    def f_body(u, acc):
        idx = nh_ref[pl.ds(u, 1), :]                            # [1, d]
        wu = w_ref[pl.ds(u, 1), :]                              # [1, d]
        col = jnp.sum(jnp.where(viota == idx, wu, 0.0), axis=1,
                      keepdims=True)                            # [v, 1]
        return jnp.where(liota == u, col, acc)

    f_ref[...] = jax.lax.fori_loop(0, n, f_body,
                                   jnp.zeros((n, n), jnp.float32))


# Each grid step writes its hop count across one row of 128 lanes: a
# (1, 128) block spans the output's last two dims whole, as Mosaic needs.
_HOPS_LANES = 128


@functools.partial(jax.jit,
                   static_argnames=("max_hops", "adaptive", "interpret"))
def load_prop_pallas(next_hop: jax.Array, load0: jax.Array, max_hops: int,
                     adaptive: bool = True, *, interpret: bool = True
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched fused load propagation. next_hop: [B, n, n] int32 src-major
    (padding rows/cols must be self-loops); load0: [B, n, n] f32 dest-major
    with zero padding. Returns (W dest-major, directed flow, hops [B])."""
    B, n, _ = next_hop.shape
    kernel = functools.partial(_load_prop_kernel, max_hops, adaptive)
    block = pl.BlockSpec((None, n, n), lambda b: (b, 0, 0))
    w_t, f_t, hops = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[block, block],
        out_specs=[block, block,
                   pl.BlockSpec((None, 1, _HOPS_LANES),
                                lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, n, n), jnp.float32),
                   jax.ShapeDtypeStruct((B, n, n), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, _HOPS_LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(next_hop.astype(jnp.int32),
      load0.astype(jnp.float32).swapaxes(-1, -2))
    return w_t.swapaxes(-1, -2), f_t.swapaxes(-1, -2), hops[:, 0, 0]


# --------------------------------------------------------------------------
# Large-n tier: destination-tiled variants (ISSUE 6)
# --------------------------------------------------------------------------

def pick_tile(n: int, batch: int, budget_elems: int = 1 << 25) -> int:
    """Auto tile size for the XLA-blocked variants (and the tiled APSP
    kernel's row slabs): the largest power of two ≤ 128 whose transient
    working set (batch · tile · n² elements for the XLA one-hot) stays
    under ``budget_elems`` (default 2^25 ≈ 128 MB f32). Floor of 8 keeps
    the sublane dimension tiling-friendly. Powers of two always divide the
    128-lane padding the Pallas paths apply, so the grid never needs a
    ragged last tile there."""
    tile = 128
    while tile > 8 and batch * tile * n * n > budget_elems:
        tile //= 2
    return tile


def load_prop_xla_blocked(next_hop: jax.Array, load0: jax.Array,
                          max_hops: int, adaptive: bool, tile: int
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Destination-blocked XLA load propagation: bit-compatible with
    ``load_prop_xla`` but scans over ``tile``-row destination slabs so the
    transient one-hot is [B, tile, n, n] instead of [B, n, n, n].

    Each slab runs its own hop loop (adaptive slabs stop at the slab's own
    routed eccentricity — strictly earlier than the batch diameter); the
    flow matrix is the scan carry, accumulated across slabs, and every
    design's hops are the largest slab's trip count. Tile sizes
    that don't divide n are handled by zero-padding the destination axis:
    padded rows carry zero load and contribute nothing.
    """
    B, n, _ = next_hop.shape
    tile = max(1, min(tile, n))
    nt = -(-n // tile)
    n_pad = nt * tile
    ids = jnp.arange(n, dtype=jnp.int32)
    nhT = next_hop.swapaxes(-1, -2).astype(jnp.int32)           # [B, d, u]
    pad = ((0, 0), (0, n_pad - n), (0, 0))
    nh_t = jnp.pad(nhT, pad).reshape(B, nt, tile, n)
    l0_t = jnp.pad(load0.astype(jnp.float32), pad).reshape(B, nt, tile, n)
    d_t = jnp.arange(n_pad, dtype=jnp.int32).reshape(nt, tile)

    def slab(flow, xs):
        nh, l0, dids = xs                   # [B, T, n], [B, T, n], [T]
        oh = (nh[:, :, :, None] == ids).astype(jnp.float32)  # [B, T, u, v]
        offdiag = (dids[None, :, None] != ids)               # [1, T, v]
        load0s = jnp.where(offdiag, l0, 0.0)

        def step(state):
            load, total = state
            total = total + load
            load = jnp.where(offdiag,
                             jnp.einsum("btuv,btu->btv", oh, load,
                                        precision=_EXACT), 0.0)
            return load, total

        def still_active(state):
            return jnp.any(state[0] > 0)

        hops, (_, total) = hop_loop(step, (load0s, jnp.zeros_like(load0s)),
                                    max_hops, adaptive, still_active)
        return flow + jnp.einsum("btuv,btu->buv", oh, total,
                                 precision=_EXACT), (total, hops)

    flow0 = jnp.zeros((B, n, n), jnp.float32)
    flow, (w_t, hops) = jax.lax.scan(
        slab, flow0, (nh_t.swapaxes(0, 1), l0_t.swapaxes(0, 1), d_t))
    w = w_t.swapaxes(0, 1).reshape(B, n_pad, n)[:, :n]
    return w, flow, jnp.full((B,), jnp.max(hops), jnp.int32)


def _load_prop_tiled_kernel(max_hops: int, adaptive: bool, nh_ref, l0_ref,
                            w_ref, f_ref, h_ref, ld_ref):
    """One (design, destination-tile) pair per grid step, in the fused
    kernel's src-major layout: the destination tile is a [n, tile] lane
    slab of the next-hop table and load, and the shared flow^T pane [n, n]
    is revisited across the inner (tile) grid axis and accumulated in
    place. The tile's hop loop stops at its own destinations' largest
    eccentricity; ``h_ref`` receives its hops. Compiled, ``tile`` must be
    a multiple of the 128-lane width."""
    t = pl.program_id(1)
    n, tile = l0_ref.shape
    viota = jax.lax.broadcasted_iota(jnp.int32, (n, tile), 0)
    dglob = jax.lax.broadcasted_iota(jnp.int32, (n, tile), 1) + t * tile
    offdiag = viota != dglob
    ld_ref[...] = jnp.where(offdiag, l0_ref[...], 0.0)

    def propagate():
        def body(u, acc):
            idx = nh_ref[pl.ds(u, 1), :]                        # [1, tile]
            lu = ld_ref[pl.ds(u, 1), :]                         # [1, tile]
            return acc + jnp.where(viota == idx, lu, 0.0)

        return jax.lax.fori_loop(0, n, body,
                                 jnp.zeros((n, tile), jnp.float32))

    hops, w = _kernel_hops(ld_ref, propagate, offdiag, max_hops, adaptive)
    w_ref[...] = w
    h_ref[...] = jnp.full(h_ref.shape, hops, jnp.int32)

    @pl.when(t == 0)
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)

    # this tile's contribution: flow^T[v, u] += Σ_{d∈tile} [nh[u,d]=v]·W^T
    uiota = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def f_body(u, acc):
        idx = nh_ref[pl.ds(u, 1), :]                            # [1, tile]
        wu = w_ref[pl.ds(u, 1), :]                              # [1, tile]
        col = jnp.sum(jnp.where(viota == idx, wu, 0.0), axis=1,
                      keepdims=True)                            # [v, 1]
        return jnp.where(uiota == u, col, acc)

    f_ref[...] = f_ref[...] + jax.lax.fori_loop(
        0, n, f_body, jnp.zeros((n, n), jnp.float32))


@functools.partial(jax.jit, static_argnames=("max_hops", "tile",
                                             "adaptive", "interpret"))
def load_prop_pallas_tiled(next_hop: jax.Array, load0: jax.Array,
                           max_hops: int, tile: int, adaptive: bool = True,
                           *, interpret: bool = True
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Destination-tiled fused load propagation: grid (batch × dest-tile)
    streaming [n, tile] src-major slabs through VMEM. Same contract as
    ``load_prop_pallas`` (self-loop padding rows, zero-padded load); the
    destination axis must additionally be a multiple of ``tile``, which
    ``ops.load_propagate`` guarantees (128-lane tiles of the 128-lane
    padding). A design's hops are the largest of its tiles'."""
    B, n, _ = next_hop.shape
    if n % tile:
        raise ValueError(f"tile {tile} must divide padded n {n}")
    nt = n // tile
    kernel = functools.partial(_load_prop_tiled_kernel, max_hops, adaptive)
    slab = pl.BlockSpec((None, n, tile), lambda b, t: (b, 0, t))
    w_t, f_t, hops = pl.pallas_call(
        kernel,
        grid=(B, nt),
        in_specs=[slab, slab],
        out_specs=[slab, pl.BlockSpec((None, n, n), lambda b, t: (b, 0, 0)),
                   pl.BlockSpec((None, None, 1, _HOPS_LANES),
                                lambda b, t: (b, t, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, n, n), jnp.float32),
                   jax.ShapeDtypeStruct((B, n, n), jnp.float32),
                   jax.ShapeDtypeStruct((B, nt, 1, _HOPS_LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
        interpret=interpret,
    )(next_hop.astype(jnp.int32),
      load0.astype(jnp.float32).swapaxes(-1, -2))
    return (w_t.swapaxes(-1, -2), f_t.swapaxes(-1, -2),
            jnp.max(hops[:, :, 0, 0], axis=1))
