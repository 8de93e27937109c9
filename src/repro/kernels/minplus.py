"""Pallas TPU kernel: tiled (min, +) matrix product.

The latency proxy's all-pairs-shortest-path step is a min-plus matmul
(DESIGN.md §2): ``out[i,j] = min_k a[i,k] + b[k,j]``. The MXU cannot evaluate
a (min, +) semiring, so this is a VPU kernel: each [bm, bn] output tile is
accumulated in a VMEM scratch buffer while k-blocks stream through VMEM, with
an inner fori_loop over the k-block (one [bm, bn] broadcast-add-min per k:
row k of b read from its ref, column k of a as a lane-masked reduction) to
keep the live working set at O(bm*bn + bm*bk + bk*bn) — never the
O(bm*bk*bn) cube a naive broadcast would materialize.

Grid: (batch, m/bm, n/bn, k/bk), k innermost so the scratch accumulator is
revisited consecutively (TPU grids iterate sequentially over the last axis).

VMEM budget at the default bm=bn=bk=128, f32:
  a tile 64 KiB + b tile 64 KiB + scratch 64 KiB + out tile 64 KiB = 256 KiB.
MXU alignment is irrelevant (VPU kernel) but tiles stay multiples of (8, 128)
for lane/sublane layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import BIG


def lane_column(x: jax.Array, k) -> jax.Array:
    """Column k of a loaded [r, c] value as an [r, 1] vector: a lane-masked
    sum (exact — one nonzero summand). Mosaic refuses ``x[:, k]`` at a
    traced lane index; rows come straight from refs via ``pl.ds``."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lanes == k, x, 0.0), axis=1, keepdims=True)


def _minplus_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, BIG, acc_ref.dtype)

    a = a_ref[...].astype(acc_ref.dtype)        # [bm, bk]
    bk = a.shape[1]

    def body(kk, acc):
        row = b_ref[pl.ds(kk, 1), :].astype(acc.dtype)          # [1, bn]
        return jnp.minimum(acc, lane_column(a, kk) + row)

    acc_ref[...] = jax.lax.fori_loop(0, bk, body, acc_ref[...])

    @pl.when(k == pl.num_programs(3) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: bool = True) -> jax.Array:
    """Batched (min,+) product via pallas_call. a: [B, M, K], b: [B, K, N].

    Shapes must be pre-padded to multiples of the block sizes (ops.py does
    this, padding with +BIG so padding never wins the min).
    """
    B, M, K = a.shape
    _, _, N = b.shape
    grid = (B, M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bm, bk), lambda b_, i, j, k: (b_, i, k)),
            pl.BlockSpec((None, bk, bn), lambda b_, i, j, k: (b_, k, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn),
                               lambda b_, i, j, k: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
