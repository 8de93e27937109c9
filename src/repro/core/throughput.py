"""Throughput proxy (paper §2.1.3), TPU-native.

Two edge properties are needed: the bandwidth B({u,v}) (computed at graph
construction from bump geometry) and the flow F({u,v}) — the sum of all
traffic routed over the edge. The proxy is then

    T = min_{e in E} B(e) / F(e) * total_traffic.

Computing F is the hot loop: the reference walks every route and increments
per-edge counters. The natural GPU port would use atomic scatter-adds; TPUs
have no fast scatter atomics, so we step all n^2 routes *simultaneously*,
hop by hop, and accumulate each hop's contributions with a
**scatter-as-matmul**: with one-hot row masks M_cur [P, n] and M_nxt [P, n]
for the current/next vertex of each pair p carrying traffic a_p, the flow
update is

    F += M_curᵀ @ (a[:, None] * M_nxt)        (an MXU matmul)

The Pallas kernel in ``kernels/flow_accum.py`` builds the masks on the fly
from iota comparisons inside VMEM (nothing materialized in HBM); the jnp
fallback here uses segment-sum scatter, which XLA handles fine on CPU.

The number of hop steps is at most a static bound passed in (defaults to
n-1, the worst case; topology generators provide tight bounds); with
``adaptive`` the loop stops at the routed diameter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _dest_major_load0(next_hop: jax.Array, traffic: jax.Array) -> jax.Array:
    """Initial dest-major load L0[d, u] from a (possibly router-padded)
    traffic matrix: traffic[s, d] starts residing at s, destined for d."""
    n = next_hop.shape[0]
    n_c = traffic.shape[0]
    t = traffic.astype(jnp.float32)
    if n_c != n:
        # router padding: jnp.pad stays a `pad` under vmap, where an
        # .at[].set / dynamic_update_slice spelling batches to a scatter
        t = jnp.pad(t, ((0, n - n_c), (0, n - n_c)))
    return t.T


@functools.partial(jax.jit, static_argnames=("max_hops", "use_kernel",
                                              "adaptive"))
def edge_flows(next_hop: jax.Array, traffic: jax.Array,
               max_hops: int | None = None,
               use_kernel: bool = False,
               adaptive: bool = False) -> jax.Array:
    """Directed edge flows [n, n]: flow[u, v] = total traffic traversing the
    directed channel u->v under the routing table.

    traffic is [n_chiplets, n_chiplets]; routers never source traffic.

    The default path dispatches through the shared load-propagation
    primitive ``kernels.ops.load_propagate`` (fused Pallas kernel on TPU,
    scatter-free XLA loop elsewhere — see ``edge_flows_load`` for the
    formulation). ``use_kernel=True`` instead runs the per-route pair walk
    with the scatter-as-matmul ``flow_accumulate`` Pallas kernel — the
    alternative TPU story for very large n, kept as an independent
    implementation (and test oracle).

    ``adaptive=True`` replaces the fixed-length loop with one that stops
    once every route has reached its destination (``max_hops`` stays the
    safety bound), on either path: the primitive passes it to every
    backend, the fused kernels included. Same flows; the trip count
    becomes the actual routed diameter instead of the static bound. Under
    vmap an XLA loop runs until the *batch* maximum diameter, the kernel
    until each design's own. Unreachable pairs (next_hop self-loops)
    never deliver, so both variants accumulate them on the diagonal for
    exactly ``max_hops`` hops (zero-bandwidth self-edges then drive the
    proxy to 0).
    """
    n = next_hop.shape[0]
    if max_hops is None:
        max_hops = n - 1
    if not use_kernel:
        from ..kernels.ops import load_propagate
        _, flow = load_propagate(next_hop, _dest_major_load0(next_hop,
                                                             traffic),
                                 max_hops=max_hops, adaptive=adaptive)
        return flow

    from ..kernels.load_prop import hop_loop
    from ..kernels.ops import flow_accumulate

    n_c = traffic.shape[0]
    t = jax.lax.dynamic_update_slice(
        jnp.zeros((n, n), dtype=jnp.float32),
        traffic.astype(jnp.float32), (0, 0))
    amount = t.ravel()                                   # [n*n]
    dest = jnp.tile(jnp.arange(n, dtype=next_hop.dtype), (n,))   # [n*n]
    cur0 = jnp.repeat(jnp.arange(n, dtype=next_hop.dtype), n)    # [n*n]

    def step(state):
        cur, flow = state
        nxt = next_hop[cur, dest]
        active = (cur != dest) & (amount > 0)
        contrib = jnp.where(active, amount, 0.0)
        flow = flow_accumulate(flow, cur, nxt, contrib)
        return jnp.where(active, nxt, cur), flow

    def still_active(state):
        return jnp.any((state[0] != dest) & (amount > 0))

    flow0 = jnp.zeros((n, n), dtype=jnp.float32)
    _, (_, flow) = hop_loop(step, (cur0, flow0), max_hops, adaptive,
                            still_active)
    return flow


@functools.partial(jax.jit, static_argnames=("max_hops", "adaptive"))
def edge_flows_load(next_hop: jax.Array, traffic: jax.Array,
                    max_hops: int | None = None,
                    adaptive: bool = True) -> jax.Array:
    """``edge_flows`` as per-destination load propagation — scatter-free,
    for backends where XLA scatter-add is a scalar loop (CPU).

    State is the load matrix L[d, u] = traffic currently residing at u and
    destined for d. The routing table is static across hops, so its one-hot
    tensor OH[d, u, v] = [next_hop[u, d] = v] is built once; each hop is
    one contraction propagating the load, the summed per-hop loads
    W = Σ_j L_j are accumulated as a cheap [n, n] add, and the edge flows
    come from ONE final contraction

        flow[u, v] = Σ_d OH[d, u, v] · W[d, u]

    (every unit of load at u toward d crosses edge (u, next_hop[u, d])
    exactly once per hop). Delivered traffic (v == d) leaves the system;
    unreachable pairs (next_hop[u, d] = u) accumulate on the diagonal
    exactly like the walk in ``edge_flows``. This is now a thin alias for
    the shared primitive ``kernels.ops.load_propagate`` (one implementation
    of the fixed-length and adaptive variants, Pallas-fused on TPU); the
    fused genome pipeline (``dse.genomes._eval_proxies``) calls the same
    primitive and additionally extracts the traffic-weighted latency from
    the W tensor.
    """
    from ..kernels.ops import load_propagate

    _, flow = load_propagate(next_hop, _dest_major_load0(next_hop, traffic),
                             max_hops=max_hops, adaptive=adaptive)
    return flow


@jax.jit
def undirected_flows(flow: jax.Array) -> jax.Array:
    """Paper models links as undirected: F({u,v}) sums both directions."""
    return flow + flow.T


@functools.partial(jax.jit, static_argnames=("max_hops", "use_kernel",
                                              "directed", "adaptive"))
def throughput_proxy(next_hop: jax.Array, adj_bw: jax.Array,
                     traffic: jax.Array, max_hops: int | None = None,
                     use_kernel: bool = False,
                     directed: bool = False,
                     adaptive: bool = False) -> jax.Array:
    """Paper §2.1.3:

        T = min_{u,v} B({u,v}) / F({u,v}) * sum(traffic)

    Edges with zero flow do not constrain the minimum. Returns a float32
    scalar in units of total offered traffic (traffic generators normalize to
    1.0, so T is directly "sustainable fraction of offered load").

    ``directed=False`` is the paper's formula: F sums both directions of the
    undirected link against its total bandwidth B (wires shared between
    directions). ``directed=True`` evaluates each direction against B
    separately — the right structural model when comparing against a
    simulator (or hardware like TPU ICI) with full-duplex channels.
    """
    flow_dir = edge_flows(next_hop, traffic, max_hops, use_kernel, adaptive)
    f = flow_dir if directed else undirected_flows(flow_dir)
    bw = adj_bw.astype(jnp.float32)
    ratio = jnp.where(f > 0, bw / jnp.maximum(f, 1e-30), jnp.inf)
    min_ratio = jnp.min(ratio)
    total = jnp.sum(traffic).astype(jnp.float32)
    return (min_ratio * total).astype(jnp.float32)


@jax.jit
def reachable_fraction(next_hop: jax.Array, traffic: jax.Array) -> jax.Array:
    """Traffic-weighted fraction of reachable source/destination pairs.

    Unreachable pairs self-loop in the routing table
    (``next_hop[u, d] = u``, see ``routing.device``); their flow piles up
    on the diagonal and silently drives the throughput proxy to 0 while
    the latency proxy under-counts them entirely. This surfaces the
    condition as an explicit [0, 1] metric: 1.0 iff every pair with
    traffic can route. next_hop: [n, n] or [B, n, n]; traffic
    [n_c, n_c] (router-padded internally). Returns a scalar or [B]."""
    squeeze = next_hop.ndim == 2
    if squeeze:
        next_hop = next_hop[None]
    n = next_hop.shape[-1]
    t = _dest_major_load0(next_hop[0], traffic).T        # [n, n] src-major
    ids = jnp.arange(n, dtype=next_hop.dtype)
    reach = (next_hop != ids[None, :, None]) | (ids[:, None] ==
                                                ids[None, :])[None]
    total = jnp.maximum(jnp.sum(t), 1e-30)
    frac = (jnp.sum(t[None] * reach, axis=(1, 2)) / total
            ).astype(jnp.float32)
    return frac[0] if squeeze else frac


@functools.partial(jax.jit, static_argnames=("max_hops",))
def bottleneck_edges(next_hop: jax.Array, adj_bw: jax.Array,
                     traffic: jax.Array, max_hops: int | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Diagnostics for DSE: per-edge saturation ratio F/B (higher = closer to
    the bottleneck) and the argmin edge index (u*n+v)."""
    flow_dir = edge_flows(next_hop, traffic, max_hops)
    f_und = undirected_flows(flow_dir)
    bw = adj_bw.astype(jnp.float32)
    ratio = jnp.where(f_und > 0, bw / jnp.maximum(f_und, 1e-30), jnp.inf)
    return ratio, jnp.argmin(ratio.ravel())
