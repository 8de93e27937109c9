"""Pallas TPU kernel: fused all-pairs-shortest-path (min-plus squaring).

``path_cost_minplus`` performs ceil(log2(n)) (min,+) squarings; done as
separate kernel launches each squaring round-trips the n x n matrix through
HBM (2 * n^2 * 4B per iteration). For the DSE regime the matrices are small
(n <= 256 chiplets => <= 256 KiB), so the entire matrix fits VMEM and the
whole APSP fuses into ONE pallas_call: the grid's iteration axis revisits
the same block while a VMEM scratch carries the evolving distance matrix —
zero intermediate HBM traffic.

The inner product is the same VPU broadcast-add-min loop as minplus.py:
row k is read from the VMEM ref on the sublane axis, column k is a
lane-masked reduction of the loaded matrix.
ops.apsp falls back to iterated minplus_matmul for matrices beyond the VMEM
budget.

Backend selection (``ops.apsp``) is dispatched through ``default_backend``:
on TPU the kernel compiles for hardware; on CPU/GPU the Pallas interpreter
would execute the kernel body in Python per grid step, so the default there
is a pure-XLA min-plus doubling instead. ``REPRO_APSP_BACKEND`` overrides
(``pallas`` | ``pallas_interpret`` | ``xla`` | ``pallas_tiled`` |
``pallas_tiled_interpret`` | ``xla_blocked``); the legacy
``REPRO_PALLAS_INTERPRET=0`` still forces compiled Pallas everywhere.
On a TPU the compiled kernel is the only path: a failure to compile or
dispatch raises (see ``faults.harness.strict_backend``).

Large-n tier (ISSUE 6): the fused kernel carries the whole [n, n] matrix in
VMEM scratch and ``apsp_xla`` materializes [B, n, n, n] per squaring, both
of which fall over for hundreds of chiplets. The ``*_tiled`` / ``xla_blocked``
variants block each min-plus squaring over [tile, n] row slabs (and k-tiles),
so the working set is O(tile · n) per grid step for Pallas and
O(B · tile² · n) transient for XLA. Each squaring then round-trips HBM —
the right trade once the matrix no longer fits VMEM.
``ops.apsp`` auto-switches above ``REPRO_APSP_FUSED_N`` (default 160) nodes;
``REPRO_APSP_TILE`` overrides the auto-chosen tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import env as _env
from .minplus import lane_column
from .ref import BIG

# [n, n] f32 scratch must fit comfortably in ~16 MiB VMEM with headroom.
MAX_FUSED_N = 1024

APSP_BACKENDS = ("pallas", "pallas_interpret", "xla",
                 "pallas_tiled", "pallas_tiled_interpret", "xla_blocked")


def default_backend() -> str:
    """Pick the APSP execution backend for the current runtime.

    Priority: ``REPRO_APSP_BACKEND`` env var, then compiled Pallas on TPU
    (or anywhere when ``REPRO_PALLAS_INTERPRET=0``), else the XLA fallback.
    """
    env = _env.get_str("REPRO_APSP_BACKEND")
    if env:
        if env not in APSP_BACKENDS:
            raise ValueError(f"REPRO_APSP_BACKEND={env!r}; "
                             f"options: {APSP_BACKENDS}")
        return env
    if jax.default_backend() == "tpu":
        return "pallas"
    if _env.get_str("REPRO_PALLAS_INTERPRET") == "0":
        return "pallas"
    return "xla"


def _apsp_kernel(d_ref, o_ref, acc_ref):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _load():
        acc_ref[...] = d_ref[...]

    d = acc_ref[...]
    n = d.shape[0]

    def body(k, acc):
        return jnp.minimum(acc, lane_column(d, k) + acc_ref[pl.ds(k, 1), :])

    acc_ref[...] = jax.lax.fori_loop(0, n, body, d)

    @pl.when(it == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("n_iters",))
def apsp_xla(d: jax.Array, n_iters: int) -> jax.Array:
    """Pure-XLA batched min-plus squaring (same semantics as the fused
    kernel, no lane padding): the CPU/GPU fallback behind ``ops.apsp``.

    d: [B, n, n] step costs with BIG = no edge and a zeroed diagonal.
    """
    def body(_, m):
        return jnp.minimum(m, jnp.min(m[:, :, :, None] + m[:, None, :, :],
                                      axis=2))

    return jax.lax.fori_loop(0, n_iters, body, d)


@functools.partial(jax.jit, static_argnames=("n_iters", "interpret"))
def apsp_pallas(d: jax.Array, n_iters: int, *, interpret: bool = True
                ) -> jax.Array:
    """Batched fused APSP. d: [B, n, n] step-cost matrix (BIG = no edge,
    diagonal 0). Returns the min-plus n-th power (all-pairs path costs)."""
    B, n, _ = d.shape
    return pl.pallas_call(
        _apsp_kernel,
        grid=(B, n_iters),
        in_specs=[pl.BlockSpec((None, n, n), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((None, n, n), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(d)


# --------------------------------------------------------------------------
# Large-n tier: blocked min-plus squaring (ISSUE 6)
# --------------------------------------------------------------------------
#
# With a zeroed diagonal, minplus(m, m)[i, j] <= m[i, j] + m[j, j] = m[i, j]
# automatically (the k = j term), so the blocked squarings below skip the
# explicit minimum-with-input the dense paths carry — same fixed point,
# same per-iteration values.

@functools.partial(jax.jit, static_argnames=("n_iters", "tile"))
def apsp_xla_blocked(d: jax.Array, n_iters: int, tile: int) -> jax.Array:
    """Pure-XLA blocked min-plus squaring: bit-compatible with ``apsp_xla``
    but each squaring scans [tile, n] row slabs with an inner k-tile scan,
    so the transient is [B, tile, tile, n] instead of [B, n, n, n]. Tiles
    that don't divide n get a BIG-padded ragged edge (cropped on return).
    """
    B, n, _ = d.shape
    tile = max(1, min(tile, n))
    nt = -(-n // tile)
    n_pad = nt * tile
    m = d
    if n_pad != n:
        m = jnp.full((B, n_pad, n_pad), BIG, d.dtype).at[:, :n, :n].set(d)

    def square(m):
        def row_slab(_, i):
            a = jax.lax.dynamic_slice_in_dim(m, i * tile, tile, 1)  # [B,T,n]

            def k_slab(acc, k):
                ak = jax.lax.dynamic_slice_in_dim(a, k * tile, tile, 2)
                bk = jax.lax.dynamic_slice_in_dim(m, k * tile, tile, 1)
                cand = jnp.min(ak[:, :, :, None] + bk[:, None, :, :], axis=2)
                return jnp.minimum(acc, cand), None

            acc, _ = jax.lax.scan(k_slab, jnp.full_like(a, BIG),
                                  jnp.arange(nt))
            return None, acc

        _, rows = jax.lax.scan(row_slab, None, jnp.arange(nt))
        return rows.swapaxes(0, 1).reshape(B, n_pad, n_pad)

    m = jax.lax.fori_loop(0, n_iters, lambda _, x: square(x), m)
    return m[:, :n, :n]


def _apsp_square_kernel(tile: int, a_ref, b_ref, o_ref, acc_ref):
    """One (design, row-tile, k-tile) triple per grid step of a single
    min-plus squaring: [tile, n] row/k slabs in VMEM, accumulator revisited
    across the k axis."""
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, BIG, acc_ref.dtype)

    a = a_ref[...]                                    # [T, n] row slab

    def body(j, acc):
        k = kt * tile + j
        return jnp.minimum(acc, lane_column(a, k) + b_ref[pl.ds(j, 1), :])

    acc_ref[...] = jax.lax.fori_loop(0, tile, body, acc_ref[...])

    @pl.when(kt == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("n_iters", "tile", "interpret"))
def apsp_pallas_tiled(d: jax.Array, n_iters: int, tile: int, *,
                      interpret: bool = True) -> jax.Array:
    """Blocked fused APSP: each squaring is one pallas_call on a
    (batch × row-tile × k-tile) grid streaming [tile, n] slabs through
    VMEM. ``tile`` must divide n (``ops.apsp`` guarantees this by picking
    power-of-two tiles that divide the 128-lane padding)."""
    B, n, _ = d.shape
    if n % tile:
        raise ValueError(f"tile {tile} must divide padded n {n}")
    nt = n // tile
    kernel = functools.partial(_apsp_square_kernel, tile)

    def square(m):
        return pl.pallas_call(
            kernel,
            grid=(B, nt, nt),
            in_specs=[pl.BlockSpec((None, tile, n),
                                   lambda b, i, k: (b, i, 0)),
                      pl.BlockSpec((None, tile, n),
                                   lambda b, i, k: (b, k, 0))],
            out_specs=pl.BlockSpec((None, tile, n),
                                   lambda b, i, k: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B, n, n), jnp.float32),
            scratch_shapes=[pltpu.VMEM((tile, n), jnp.float32)],
            interpret=interpret,
        )(m, m)

    return jax.lax.fori_loop(0, n_iters, lambda _, m: square(m), d)
