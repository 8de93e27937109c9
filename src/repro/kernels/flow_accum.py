"""Pallas TPU kernel: flow accumulation as one-hot matmul (scatter-as-matmul).

The throughput proxy's hot loop adds each route's traffic onto the directed
edge (cur, nxt) it traverses this hop. The natural GPU implementation is an
atomic scatter-add; TPUs have no fast scatter atomics, so we rebuild the
update as an MXU matmul over one-hot masks generated *inside* the kernel from
iota comparisons (DESIGN.md §2 — nothing is materialized in HBM):

    mask_cur[u, p] = [cur[p] == u]                   [n, bp]
    mask_amt[v, p] = amount[p] * [nxt[p] == v]       [n, bp]
    out += mask_cur @ mask_amtᵀ                      [n, n]  (MXU)

Grid: (batch, P/bp) with the pair axis innermost; the [n, n] output block is
revisited across pair-blocks and accumulated in place (initialized from the
incoming flow at p == 0).

VMEM at bp=512, n=128, f32: masks 2 x 256 KiB + out 64 KiB + indices ~4 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flow_kernel(cur_ref, nxt_ref, amt_ref, fin_ref, o_ref):
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = fin_ref[...]

    cur = cur_ref[...]                                # [1, bp] int32
    nxt = nxt_ref[...]                                # [1, bp] int32
    amt = amt_ref[...].astype(jnp.float32)            # [1, bp]
    n = o_ref.shape[-1]
    bp = cur.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, bp), 0)
    mask_cur = (iota == cur).astype(jnp.float32)      # [u, p]
    mask_amt = jnp.where(iota == nxt, amt, 0.0)       # [v, p]
    contrib = jax.lax.dot_general(
        mask_cur, mask_amt,
        dimension_numbers=(((1,), (1,)), ((), ())),   # contract over pairs
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    o_ref[...] = o_ref[...] + contrib.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def flow_accum_pallas(flow: jax.Array, cur: jax.Array, nxt: jax.Array,
                      amount: jax.Array, *, bp: int = 512,
                      interpret: bool = True) -> jax.Array:
    """Batched flow accumulation. flow: [B, n, n]; cur/nxt/amount: [B, P]
    with P a multiple of bp (ops.py pads with amount == 0). The pair
    vectors travel as [B, 1, P] so each block is a lane-major [1, bp] row
    (a multiple of 128 lanes, or the whole padded pair axis)."""
    B, n, _ = flow.shape
    P = cur.shape[1]
    grid = (B, P // bp)
    pairs = pl.BlockSpec((None, 1, bp), lambda b_, p: (b_, 0, p))
    pane = pl.BlockSpec((None, n, n), lambda b_, p: (b_, 0, 0))
    return pl.pallas_call(
        _flow_kernel,
        grid=grid,
        in_specs=[pairs, pairs, pairs, pane],
        out_specs=pane,
        out_shape=jax.ShapeDtypeStruct((B, n, n), flow.dtype),
        interpret=interpret,
    )(cur[:, None, :], nxt[:, None, :], amount[:, None, :], flow)
