"""Device-resident genome→metrics pipelines (ISSUE 4 tentpole).

The optimizer's steady-state loop used to round-trip every genome through
per-design Python: decode → DesignPoint → host graph build → numpy routing
tables, with structure-cache misses on essentially every free-form genome.
These pipelines remove the host from the loop:

* ``AdjacencyPipeline`` — one fused, jit-compiled program from a bit-genome
  batch to (latency, throughput) arrays for ``opt.space.AdjacencySpace``.
  The genome decode (bits → adjacency), chiplet geometry (grid placement,
  greedy nearest-PHY assignment, link lengths/latencies/bandwidths), batched
  routing-table construction (``routing.device``), and the two proxies all
  run on the device. Everything data-independent — chiplet side lengths,
  PHY offsets, bump-limited bandwidths per (radix, degree) — is precomputed
  on the host in float64 as small lookup tables indexed by the design's
  radix, so the device path reproduces the host build's numbers (proxy
  metrics agree within 1e-5; the greedy PHY scan and routing tie-breaks are
  exact, asserted in tests/test_device_path.py).

* ``ParametricPipeline`` — ``opt.space.ParametricSpace`` genomes index a
  *finite* set of structures, so the decode is a gather: structures are
  built lazily through the shared structure cache (host, exact), stacked
  once, and each generation is one indexed gather plus the same jitted
  proxy evaluation the sweep engine uses. Any registered topology/routing
  (including the RNG-streamed ``updown_random``) is supported because the
  tables come from the host builder.

Both pipelines shard the population axis across every device of the engine
mesh via ``shard_map`` (ISSUE 5): the fused program runs per shard with all
lookup tables replicated and zero cross-device communication, so the same
code spans 1 CPU device or a full accelerator mesh. The proxies' hot loop
dispatches through the shared ``kernels.ops.load_propagate`` primitive
(fused Pallas kernel on TPU, XLA loop elsewhere;
``REPRO_LOAD_PROP_BACKEND`` overrides), whose hop loop stops at each
design's routed diameter in the kernel and at each shard's in the XLA
loop; ``AdjacencyPipeline``'s finishers count the hops it ran
(``kernels.load_propagate.hops`` over ``kernels.load_propagate.designs``).
``evaluate_async`` dispatches without blocking — the async optimizer
driver (``opt.runner.AsyncStepper``) overlaps archive/checkpoint work with
the in-flight call.

Both pipelines are jit-cache-stable: the population axis is padded to
power-of-two buckets (×device-count multiples), ``ParametricPipeline``
node counts pad to shared power-of-two buckets (``node_bucket`` — spaces
over heterogeneous chiplet counts reuse one compiled program), and every
static argument is derived from the space, so generation after generation
reuses one compiled program per (bucketed P, n) shape. ``COMPILE_COUNTS``
records a trace-time probe per shape key; tests assert exactly one
compilation across a whole run.

Reports (area/power/cost for the constraint masks) stay on the host in
float64 — they are O(P) scalar gathers from per-radix/per-structure tables,
exact against ``core.reports``.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import defaultdict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.latency import num_doubling_steps
from ..core.reports import ReportArrays
from ..faults.objectives import REACH_EPS
from ..kernels.ops import load_propagate_counted
from ..kernels.ref import BIG
from ..obs import metrics as _metrics
from ..obs.trace import span as _span
from ..routing.device import hops_next_hop_batch
from ..utils.jaxcompat import shard_map

# Trace-time compile probe: key -> number of jit traces. One generation after
# another must reuse the same compiled program, so each key stays at 1 for a
# whole run (asserted in tests/test_device_path.py). The same events also
# land in the repro.obs metrics registry (the ``jit.compile`` counter
# series), where the run report and BENCH telemetry read them.
COMPILE_COUNTS: dict[tuple, int] = defaultdict(int)


def _note_compile(key: tuple) -> None:
    COMPILE_COUNTS[key] += 1
    _metrics.counter("jit.compile", fn=f"genomes.{key[0]}",
                     shape="/".join(str(k) for k in key[1:])).inc()


def reset_compile_counts() -> None:
    COMPILE_COUNTS.clear()


# Serializes the module-level jit-factory caches below. ``lru_cache``
# guards its own dict, but NOT the factory body: two server jobs encoding
# designs at once could both miss and trace/compile the same program twice
# (wasted minutes at large n, double-counted COMPILE_COUNTS). The lock
# makes a concurrent miss build exactly one compiled program (asserted in
# tests/test_serve.py's concurrent-access stress test).
_FACTORY_LOCK = threading.RLock()


def _locked_factory(fn):
    """Wrap an ``lru_cache``'d jit factory so concurrent first calls
    serialize on ``_FACTORY_LOCK`` (every later hit pays one uncontended
    lock acquire — nanoseconds against a jit dispatch)."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with _FACTORY_LOCK:
            return fn(*args, **kwargs)
    wrapper.cache_clear = fn.cache_clear   # keep the lru_cache test hooks
    wrapper.cache_info = fn.cache_info
    return wrapper


def bucket_population(size: int, multiple: int = 1) -> int:
    """Pad the population axis to a power-of-two bucket (>= 8) rounded up to
    a device-count multiple, so repeated generations hit one compiled
    program regardless of small population-size jitter."""
    b = 1 << max(3, int(size - 1).bit_length())
    if multiple > 1:
        b = ((b + multiple - 1) // multiple) * multiple
    return b


NODE_TILE = 16


def node_bucket(n: int) -> int:
    """Pad node counts to ``NODE_TILE``-multiple buckets (floor 8):
    pipelines over heterogeneous-``n`` spaces then share one compiled
    program per bucket instead of compiling per exact node count (padding
    rows are self-looped routers with zero traffic — exact no-ops for every
    proxy). Tile multiples instead of powers of two keep the padding
    overhead bounded at ~(1 + 16/n)² of the real quadratic work — the old
    power-of-two buckets padded n = 576 to 1024 (3.2× the work/memory) —
    while staying aligned with the tiled kernels' slab sizes."""
    if n <= 8:
        return 8
    return ((n + NODE_TILE - 1) // NODE_TILE) * NODE_TILE


class PendingGenomeEval:
    """Handle for an in-flight (dispatched, not yet materialized) genome
    evaluation: the device computes while the host keeps working (archive
    updates, checkpoint writes — see ``opt.runner.AsyncStepper``).
    ``result()`` blocks on the device, builds the host-side reports, and is
    idempotent. ``arrays`` holds the dispatched device outputs, sharded
    over the population axis (bucket-padded rows included)."""

    def __init__(self, finisher, arrays: tuple = ()):
        self._finisher = finisher
        self.arrays = arrays
        self._result: GenomeEvalResult | None = None
        self._block_s = 0.0

    @property
    def block_s(self) -> float:
        """Seconds ``result()`` spent waiting for the device outputs (the
        ``genomes.block`` span), without the host reports."""
        return self._block_s

    def block(self) -> None:
        """Wait until the device has produced ``arrays``; the finishers
        call this where they first need the outputs."""
        t0 = time.perf_counter()
        with _span("genomes.block"):
            jax.block_until_ready(self.arrays)
        self._block_s += time.perf_counter() - t0

    def result(self) -> GenomeEvalResult:
        if self._finisher is not None:
            self._result = self._finisher()
            self._finisher = None
        return self._result


@dataclass
class GenomeEvalResult:
    """Metrics for one genome population (see DseEngine.evaluate_genomes)."""
    latency: np.ndarray       # [P] f32
    throughput: np.ndarray    # [P] f32
    reports: ReportArrays     # [P] f64 host-exact constraint columns


@dataclass
class FaultGridResult:
    """Degraded metrics over a [P, F] population x fault-scenario grid
    (ISSUE 9): one fused device call evaluates every genome under every
    fault scenario; ``faults.objectives`` reduces the grid into robust
    Pareto objectives."""
    latency: np.ndarray              # [P, F] f32 (BIG when nothing routes)
    throughput: np.ndarray           # [P, F] f32 (0 when nothing routes)
    reachable_fraction: np.ndarray   # [P, F] f32 delivered traffic share
    reports: ReportArrays            # [P] pristine constraint columns


# ---------------------------------------------------------------------------
# AdjacencySpace: fused bits -> metrics
# ---------------------------------------------------------------------------

def _eval_proxies(next_hop, step_cost, node_weight, adj_bw, traffic,
                  max_hops: int):
    """Both proxies from ONE load-propagation pass through the shared
    primitive ``kernels.ops.load_propagate`` (Pallas-fused on TPU, XLA loop
    elsewhere): the accumulated per-destination load W[d, u] gives
    the edge flows via the primitive's final contraction, and — because a
    unit of traffic pays step_cost(u, nh[u, d]) each time it leaves u — the
    traffic-weighted total path cost is

        Σ_{u,d} W[d, u] · step_cost[u, nh[u, d]] + Σ_d (Σ_s T[s, d]) · nw[d]

    which replaces the whole path-doubling pass. Exact for connected
    (repaired) designs, where every routed pair terminates; ``max_hops`` is
    the safety bound (n-1), and the adaptive loop stops at each design's
    routed diameter in the kernels (the XLA loop: the shard's largest).
    Matches the reference proxies to f32 summation order (asserted against
    the host path in tests). Returns (latency, throughput, hops run) [P].
    """
    Pn, n, _ = next_hop.shape
    t32 = traffic.astype(jnp.float32)
    t_total = jnp.sum(t32)
    dest_weight = jnp.sum(jnp.sum(t32, axis=0) * node_weight)
    load0 = jnp.broadcast_to(t32.T[None], (Pn, n, n))
    total, flow, hops = load_propagate_counted(
        next_hop, load0, max_hops=max_hops, adaptive=True)
    f = flow + flow.swapaxes(-1, -2)
    ratio = jnp.where(f > 0, adj_bw / jnp.maximum(f, 1e-30), jnp.inf)
    thr = (jnp.min(ratio, axis=(1, 2)) * t_total).astype(jnp.float32)
    # tables arrive int16 (routing/device.py); widen at the gather site
    sc_next = jnp.take_along_axis(step_cost, next_hop.astype(jnp.int32),
                                  axis=2)                        # [P, u, d]
    lat = ((jnp.sum(total * sc_next.swapaxes(-1, -2), axis=(1, 2))
            + dest_weight) / t_total).astype(jnp.float32)
    return lat, thr, hops


def _adjacency_structure(bits, pair_u, pair_v, pair_id, chain_slot,
                         chain_eslot, inv_j, inv_c, col, row, side_t,
                         phyx_t, phyy_t, cphyx_t, cphyy_t, bw_t, consts,
                         *, n: int, k_phys: int, euclid: bool):
    """Genome decode + geometry: repaired bit genomes [P, G] -> structure
    arrays ``(adj, step_cost, adj_bw, length)`` — the bits->adjacency
    decode, the greedy nearest-PHY chain scan, and the link geometry
    (lengths, latencies, bump-limited bandwidths). Shared verbatim by the
    pristine eval (``_adjacency_eval``) and the fault grid
    (``_adjacency_eval_faults``): faults degrade the *routing structure*
    (masked adjacency / step costs) but never the manufactured geometry,
    so the pristine structure is computed once per genome either way.

    pair_u/pair_v: [G] pair endpoints; pair_id: [n, n] static map from a
    vertex pair to its genome slot (G on the diagonal), which turns every
    [P, n, n] materialization into a gather — no XLA scatters anywhere.

    The greedy PHY scan's used-set is per-chiplet, so the host's sequential
    pass decomposes into n *independent* chains — chiplet c walks its n-1
    incident slots in the greedy order restricted to c. Only SET bits
    occupy a PHY, so each chain has at most k_phys real steps: the scan
    runs over k_phys *compacted* steps (per-design set-slots-first
    reordering of the static schedule) instead of all n-1. chain_slot/
    chain_eslot: [n-1, n] static schedules (step j, chiplet c) -> genome
    slot / (slot, endpoint) index into the precomputed distance tensor;
    inv_j/inv_c: [2G] static (chain step, chiplet) coordinates of each
    (slot, endpoint). side_t/phyx_t/phyy_t/bw_t: per-radix lookup tables
    (host f64 → f32). consts: [spacing, link_const, link_per_mm, phy_lat2,
    internal].
    """
    Pn, G = bits.shape
    spacing, link_const, link_per_mm, phy_lat2, internal = consts
    bitsb = bits.astype(bool)
    bits_pad = jnp.concatenate(
        [bitsb, jnp.zeros((Pn, 1), bool)], axis=1)  # column G = padding

    # --- decode: bits -> adjacency, degrees, radix-indexed geometry ---
    adj = bits_pad[:, pair_id]                                  # [P, n, n]
    deg = adj.sum(axis=2, dtype=jnp.int32)                      # [P, n]
    radix = jnp.clip(jnp.max(deg, axis=1), 1, k_phys)           # [P]
    side = side_t[radix]                                        # [P]
    pitch = side + spacing
    offx = phyx_t[radix]                                        # [P, K]
    offy = phyy_t[radix]
    coffx = cphyx_t[radix]          # centered: phy - side/2 (greedy ties)
    coffy = cphyy_t[radix]
    phy_valid = jnp.arange(k_phys)[None, :] < radix[:, None]    # [P, K]

    # --- greedy nearest-PHY assignment (the host's sequential scan as n
    # independent per-chiplet chains, one chain step per scan step) ---
    # The candidate distance |pos_a + phy - (pos_b + side/2)| is evaluated
    # in the factored form |Δcol·pitch + (phy.x - side/2)| + |Δrow·pitch +
    # (phy.y - side/2)| (centered offsets precomputed in f64). Like the
    # host's scan (factory.PHY_TIE_TOL), the pick goes to the lowest PHY
    # index within a relative tolerance of the minimum: geometrically tied
    # candidates (noise ~1e-6 in f32) resolve identically on both paths,
    # while genuinely distinct candidates differ by ≥ fractions of the
    # chiplet side (~1e-2 relative).
    tie_tol = 1e-4
    phy_ids = jnp.arange(k_phys, dtype=jnp.int32)
    # Candidate distances depend on (slot, endpoint, phy) but not on the
    # evolving used-state: precompute them for all 2G endpoint slots at
    # once (index layout: slot + endpoint*G), leaving the scan body with a
    # single gather plus the masked argmax.
    dcol2 = jnp.concatenate([col[pair_u] - col[pair_v],
                             col[pair_v] - col[pair_u]])        # [2G]
    drow2 = jnp.concatenate([row[pair_u] - row[pair_v],
                             row[pair_v] - row[pair_u]])

    def cand_dist(es):
        """Candidate distances [P, n, K] for one compact step's endpoint
        slots — computed on demand from the factored grid offsets (the
        full [P, 2G, K] tensor is never materialized; the compacted scan
        touches at most k_phys·n of its 2G rows)."""
        dc = dcol2[es]                                          # [P, n]
        dr = drow2[es]
        return (jnp.abs(dc[:, :, None] * pitch[:, None, None] +
                        coffx[:, None, :]) +
                jnp.abs(dr[:, :, None] * pitch[:, None, None] +
                        coffy[:, None, :]))

    # Chain compaction: only set bits occupy a PHY, so at most k_phys of a
    # chiplet's n-1 chain steps do anything. Route every (design, chiplet)
    # chain's t-th SET slot to compact step t (relative greedy order
    # preserved — unset slots never touch the used-set) and scan just
    # k_phys steps. The (t-th set slot -> chain step) map is one one-hot
    # contraction over the rank tensor; steps beyond a chiplet's degree are
    # gated off, and picks of unset slots are arbitrary — masked out of
    # every consumer below (lat/bw/length gate on the genome bit).
    cs_bits = bits_pad[:, chain_slot]                       # [P, n-1, n]
    csb = cs_bits.astype(jnp.int32)
    rank = jnp.cumsum(csb, axis=1) - csb     # set slots before step j
    tio = jnp.arange(k_phys, dtype=jnp.int32)
    # Position of the t-th set slot in chiplet c's chain, WITHOUT the
    # [P, k, n-1, n] one-hot: with rank_inc[j] = set slots through step j,
    # the t-th set slot sits at position Σ_j [rank_inc[j] <= t] (every step
    # strictly before it satisfies the bound, it and everything after do
    # not). One [P, n-1, n] reduction per compact step via lax.map. Steps
    # past a chiplet's degree clamp to the last chain slot — their picks
    # are garbage in the dense form too and every consumer gates on
    # ``valid``/the genome bit.
    rank_inc = rank + csb
    pos = jax.lax.map(
        lambda t: jnp.sum((rank_inc <= t).astype(jnp.int32), axis=1), tio)
    pos = jnp.minimum(jnp.moveaxis(pos, 0, 1),
                      chain_eslot.shape[0] - 1)             # [P, k, n]
    eslots = chain_eslot.astype(jnp.int32)[
        pos, jnp.arange(n)[None, None, :]]                  # [P, k, n]
    valid = tio[None, :, None] < deg[:, None, :]            # [P, k, n]

    def step(used, xs):
        es, ok = xs                     # [P, n]: chiplet c's compact step
        d = cand_dist(es)                                       # [P, n, K]
        free = phy_valid[:, None, :] & ~used
        d = jnp.where(free, d, BIG)
        dm = jnp.min(d, axis=2)
        near = d <= (dm + tie_tol * jnp.maximum(dm, 1.0))[:, :, None]
        pick = jnp.argmax(free & near, axis=2).astype(jnp.int32)  # [P, n]
        used = used | ((phy_ids[None, None, :] == pick[:, :, None]) &
                       ok[:, :, None])
        return used, pick

    used0 = jnp.zeros((Pn, n, k_phys), bool)
    _, picks = jax.lax.scan(step, used0, (jnp.moveaxis(eslots, 1, 0),
                                          jnp.moveaxis(valid, 1, 0)))
    # [k, P, n] -> per (pair, endpoint) picks [P, 2G]: a set slot's compact
    # step is its rank at its static (chain step, chiplet) coordinates.
    picks_c = jnp.moveaxis(picks, 0, 1)                     # [P, k, n]
    t_ge = jnp.minimum(rank[:, inv_j, inv_c], k_phys - 1)   # [P, 2G]
    picks_ge = jnp.take_along_axis(picks_c[:, :, inv_c],
                                   t_ge[:, None, :], axis=1)[:, 0, :]
    pick_u = picks_ge[:, :G]
    pick_v = picks_ge[:, G:]

    # --- link geometry -> latencies, bandwidths (pair order) ---
    posx_u = col[pair_u][None, :] * pitch[:, None]              # [P, G]
    posy_u = row[pair_u][None, :] * pitch[:, None]
    posx_v = col[pair_v][None, :] * pitch[:, None]
    posy_v = row[pair_v][None, :] * pitch[:, None]
    ax = posx_u + jnp.take_along_axis(offx, pick_u, axis=1)
    ay = posy_u + jnp.take_along_axis(offy, pick_u, axis=1)
    bx = posx_v + jnp.take_along_axis(offx, pick_v, axis=1)
    by = posy_v + jnp.take_along_axis(offy, pick_v, axis=1)
    if euclid:
        length = jnp.sqrt((ax - bx) ** 2 + (ay - by) ** 2)
    else:
        length = jnp.abs(ax - bx) + jnp.abs(ay - by)
    lat = link_const + link_per_mm * length + phy_lat2
    bw = jnp.minimum(bw_t[radix[:, None], deg[:, pair_u]],
                     bw_t[radix[:, None], deg[:, pair_v]])

    lat_pad = jnp.concatenate(
        [jnp.where(bitsb, lat, BIG).astype(jnp.float32),
         jnp.full((Pn, 1), BIG, jnp.float32)], axis=1)
    lat_full = lat_pad[:, pair_id]
    bw_pad = jnp.concatenate(
        [jnp.where(bitsb, bw, 0.0).astype(jnp.float32),
         jnp.zeros((Pn, 1), jnp.float32)], axis=1)
    adj_bw = bw_pad[:, pair_id]
    step_cost = jnp.where(adj, internal + lat_full, 0.0).astype(jnp.float32)
    return adj, step_cost, adj_bw, length


def _adjacency_eval(bits, pair_u, pair_v, pair_id, chain_slot, chain_eslot,
                    inv_j, inv_c, col, row, side_t, phyx_t, phyy_t,
                    cphyx_t, cphyy_t, bw_t, traffic, consts, *, n: int,
                    k_phys: int, euclid: bool, max_hops: int):
    """Fused device path: repaired bit genomes [P, G] -> per-design latency,
    throughput, and summed link length. Wrapped per mesh by
    ``_adjacency_eval_fn`` in ``shard_map`` over the population axis — each
    device runs this body on its own population shard (all tables
    replicated), so the whole pipeline scales across ``jax.devices()`` with
    zero cross-device communication. The decode/geometry half lives in
    ``_adjacency_structure`` (shared with the fault grid); this adds the
    batched routing tables and the two proxies. Returns (latency,
    throughput, summed link length, load-propagation hops), each [P]."""
    Pn, G = bits.shape
    _note_compile(("adjacency", Pn, G, n, k_phys, max_hops))
    internal = consts[4]
    adj, step_cost, adj_bw, length = _adjacency_structure(
        bits, pair_u, pair_v, pair_id, chain_slot, chain_eslot, inv_j,
        inv_c, col, row, side_t, phyx_t, phyy_t, cphyx_t, cphyy_t, bw_t,
        consts, n=n, k_phys=k_phys, euclid=euclid)

    # --- batched routing tables (hops metric, every chiplet relays) ---
    next_hop = hops_next_hop_batch(adj)

    # --- proxies ---
    node_weight = jnp.full((n,), internal, jnp.float32)
    lat_m, thr_m, hops = _eval_proxies(next_hop, step_cost, node_weight,
                                       adj_bw, traffic, max_hops)
    len_sum = jnp.sum(jnp.where(bits.astype(bool), length, 0.0), axis=1)
    return lat_m, thr_m, len_sum, hops


@_locked_factory
@functools.lru_cache(maxsize=None)
def _adjacency_eval_fn(mesh, n: int, k_phys: int, euclid: bool,
                       max_hops: int, donate: bool):
    """Jitted, population-sharded adjacency eval for one (mesh, statics)
    combination. Cached at module level (meshes over the same devices
    compare equal), so every pipeline with the same geometry shares ONE
    compiled program; ``donate`` hands the bits buffer to XLA for reuse
    (skipped on backends without donation support)."""
    impl = functools.partial(_adjacency_eval, n=n, k_phys=k_phys,
                             euclid=euclid, max_hops=max_hops)
    f = shard_map(impl, mesh=mesh, in_specs=(P("data"),) + (P(),) * 17,
                  out_specs=(P("data"),) * 4, check_vma=False)
    return jax.jit(f, donate_argnums=(0,) if donate else ())


def _donate_ok() -> bool:
    """Buffer donation is a no-op warning on CPU; enable it elsewhere."""
    return jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# AdjacencySpace: fused [P, F] population x fault grid (ISSUE 9)
# ---------------------------------------------------------------------------

def _eval_proxies_masked(next_hop, step_cost, node_weight, adj_bw, traffic,
                         alive, max_hops: int):
    """``_eval_proxies`` generalized to degraded structures: only traffic
    between *reachable* alive pairs enters the books, and unreachable
    traffic becomes an explicit reachable-fraction output instead of
    inf-poisoning the proxies (the pristine formulas divide by the full
    traffic total and let self-looped routes accumulate on the diagonal).

    next_hop/step_cost/adj_bw: [B, n, n] degraded structures; alive:
    [B, n] node-alive mask; traffic: [n, n] shared. Returns (latency,
    throughput, reachable_fraction) each [B] f32 — latency/throughput of
    the *delivered* traffic (BIG / 0.0 when nothing routes), and the
    delivered fraction of total offered traffic — and the hops the load
    propagation ran [B] int32 (undelivered pairs carry no load, so a
    degraded design stops at its reachable diameter). Reduces exactly to
    the pristine proxies when every node is alive and the graph is
    connected.
    """
    B, n, _ = next_hop.shape
    t32 = traffic.astype(jnp.float32)
    ids = jnp.arange(n, dtype=next_hop.dtype)
    # Unreachable pairs self-loop in the routing table (routing.device).
    reach = (next_hop != ids[None, :, None]) | (ids[:, None] ==
                                                ids[None, :])[None]
    deliver = reach & alive[:, :, None] & alive[:, None, :]
    t_m = t32[None] * deliver                        # [B, n, n] src-major
    t_tot = jnp.sum(t_m, axis=(1, 2))                # [B]
    dest_weight = jnp.sum(t_m * node_weight[None, None, :], axis=(1, 2))
    total, flow, hops = load_propagate_counted(
        next_hop, t_m.swapaxes(-1, -2), max_hops=max_hops, adaptive=True)
    f = flow + flow.swapaxes(-1, -2)
    ratio = jnp.where(f > 0, adj_bw / jnp.maximum(f, 1e-30), jnp.inf)
    min_ratio = jnp.min(ratio, axis=(1, 2))
    sc_next = jnp.take_along_axis(step_cost, next_hop.astype(jnp.int32),
                                  axis=2)
    path_cost = jnp.sum(total * sc_next.swapaxes(-1, -2), axis=(1, 2))
    safe_tot = jnp.maximum(t_tot, 1e-30)
    routed = t_tot > 0
    lat = jnp.where(routed, (path_cost + dest_weight) / safe_tot,
                    BIG).astype(jnp.float32)
    thr = jnp.where(routed, min_ratio * t_tot, 0.0).astype(jnp.float32)
    reach_frac = (t_tot / jnp.maximum(jnp.sum(t32), 1e-30)
                  ).astype(jnp.float32)
    return lat, thr, reach_frac, hops


def _adjacency_eval_faults(bits, link_alive, node_alive, pair_u, pair_v,
                           pair_id, chain_slot, chain_eslot, inv_j, inv_c,
                           col, row, side_t, phyx_t, phyy_t, cphyx_t,
                           cphyy_t, bw_t, traffic, consts, *, n: int,
                           k_phys: int, euclid: bool, max_hops: int):
    """Fused [P, F] population x fault grid: every genome evaluated under
    every fault scenario in ONE device program.

    bits: [P, G] repaired genomes (population-sharded); link_alive:
    [F, G] per-scenario link survival (False = failed); node_alive: [F, n]
    chiplet survival (both replicated). The pristine structure (geometry,
    PHY assignment, bandwidths) is built once per genome via
    ``_adjacency_structure``; each scenario then masks the adjacency —
    dead links vanish, dead chiplets lose all incident links and stop
    sourcing/sinking traffic — and the degraded routing tables are
    recomputed under the mask by the same batched BFS
    (``routing.device.hops_next_hop_batch``) over a flat [P*F] batch:
    the grid is materialized as [P*F, n, n] gathers (static iota row/
    scenario indices), never as a [P, F, n, n] transient (audited in
    ``analysis.registry``). Returns (latency, throughput,
    reachable_fraction) each [P, F] f32, the pristine summed link
    length [P] and the load-propagation hops [P, F] int32."""
    Pn, G = bits.shape
    F = link_alive.shape[0]
    _note_compile(("adjacency_faults", Pn, F, G, n, k_phys, max_hops))
    internal = consts[4]
    adj, step_cost, adj_bw, length = _adjacency_structure(
        bits, pair_u, pair_v, pair_id, chain_slot, chain_eslot, inv_j,
        inv_c, col, row, side_t, phyx_t, phyy_t, cphyx_t, cphyy_t, bw_t,
        consts, n=n, k_phys=k_phys, euclid=euclid)

    # Scenario masks in pair space: pad column G (the diagonal / non-pair
    # slot) stays alive — adj is already False there.
    alive_pad = jnp.concatenate(
        [link_alive.astype(bool), jnp.ones((F, 1), bool)], axis=1)
    alive_pairs = alive_pad[:, pair_id]                      # [F, n, n]
    node_ok = node_alive.astype(bool)                        # [F, n]

    # Flat [P*F] grid via static iota gathers — row p of the population
    # meets scenario f at flat index p*F + f.
    pf = Pn * F
    p_idx = jnp.arange(pf, dtype=jnp.int32) // F
    f_idx = jnp.arange(pf, dtype=jnp.int32) % F
    adj_pf = (adj[p_idx] & alive_pairs[f_idx]
              & node_ok[f_idx][:, :, None] & node_ok[f_idx][:, None, :])
    step_pf = jnp.where(adj_pf, step_cost[p_idx], 0.0)
    bw_pf = adj_bw[p_idx]          # dead links carry zero flow -> unused

    next_hop = hops_next_hop_batch(adj_pf)
    node_weight = jnp.full((n,), internal, jnp.float32)
    lat, thr, reach, hops = _eval_proxies_masked(
        next_hop, step_pf, node_weight, bw_pf, traffic, node_ok[f_idx],
        max_hops)
    len_sum = jnp.sum(jnp.where(bits.astype(bool), length, 0.0), axis=1)
    return (lat.reshape(Pn, F), thr.reshape(Pn, F), reach.reshape(Pn, F),
            len_sum, hops.reshape(Pn, F))


@_locked_factory
@functools.lru_cache(maxsize=None)
def _adjacency_faults_fn(mesh, n: int, k_phys: int, euclid: bool,
                         max_hops: int, donate: bool):
    """Jitted, population-sharded fault-grid eval per (mesh, statics):
    bits shard over the data axis, fault masks replicate, the [P, F]
    outputs shard over their population axis. Module-cached like
    ``_adjacency_eval_fn``; the compiled program is shared across
    generations for a fixed scenario count F."""
    impl = functools.partial(_adjacency_eval_faults, n=n, k_phys=k_phys,
                             euclid=euclid, max_hops=max_hops)
    f = shard_map(impl, mesh=mesh,
                  in_specs=(P("data"), P(), P()) + (P(),) * 17,
                  out_specs=(P("data"),) * 5, check_vma=False)
    return jax.jit(f, donate_argnums=(0,) if donate else ())


class AdjacencyPipeline:
    """Fused device path for ``opt.space.AdjacencySpace`` populations."""

    def __init__(self, space, mesh: jax.sharding.Mesh):
        from ..core.reports import die_cost
        from ..core.reports import _interposer_tech_default as _itech
        from ..core.graph import link_bandwidth
        from ..topologies.factory import grid_placement, make_chiplet
        from ..topologies.grid import grid_dims

        if space.routing != "dijkstra_lowest_id":
            raise ValueError(
                f"device path supports dijkstra_lowest_id routing only "
                f"(space routing: {space.routing!r}); use the host path")
        self.space = space
        self.mesh = mesh
        n = space.n_chiplets
        self.n = n
        pkg = space.packaging
        # Repair's soft cap: connectivity joins may exceed max_degree by one.
        k = min(n - 1, space.max_degree + 1)
        self.k_phys = max(k, 1)

        # Per-radix host tables (float64 geometry, cast once for the device).
        side = np.zeros(self.k_phys + 1, np.float64)
        phyx = np.zeros((self.k_phys + 1, self.k_phys), np.float64)
        phyy = np.zeros((self.k_phys + 1, self.k_phys), np.float64)
        cphyx = np.zeros((self.k_phys + 1, self.k_phys), np.float64)
        cphyy = np.zeros((self.k_phys + 1, self.k_phys), np.float64)
        bw = np.zeros((self.k_phys + 1, self.k_phys + 2), np.float64)
        chip_area = np.zeros(self.k_phys + 1, np.float64)
        chip_power = np.zeros(self.k_phys + 1, np.float64)
        ia = np.zeros(self.k_phys + 1, np.float64)
        cost_col = np.zeros(self.k_phys + 1, np.float64)
        tech = space.technology
        itech = None
        for r in range(1, self.k_phys + 1):
            ct = make_chiplet(r)
            side[r] = ct.width
            for pi, phy in enumerate(ct.phys):
                phyx[r, pi] = phy.x
                phyy[r, pi] = phy.y
                cphyx[r, pi] = phy.x - ct.width / 2
                cphyy[r, pi] = phy.y - ct.height / 2
            for d in range(1, self.k_phys + 2):
                bw[r, d] = link_bandwidth(ct.area, ct.bump_area_fraction, d,
                                          pkg.bump_pitch, pkg.non_data_wires)
            chip_area[r] = ct.area
            chip_power[r] = ct.power
            pos = grid_placement(n, ct.width, 1.0)
            x1 = max(px for px, py in pos) + ct.width
            y1 = max(py for px, py in pos) + ct.width
            ia[r] = x1 * y1
            if itech is None:
                # mirrors Design.technologies[0] for make_design-built points
                class _D:  # minimal shim for _interposer_tech_default
                    technologies = (tech,)
                itech = _itech(_D)
            cost_col[r] = (n * die_cost(ct.area, tech) + die_cost(ia[r], itech)
                           + pkg.packaging_cost_base
                           + pkg.packaging_cost_per_mm2 * ia[r])
        self._chip_area = chip_area
        self._chip_power = chip_power
        self._ia = ia
        self._cost = cost_col

        rows, cols = grid_dims(n)
        col_of = np.arange(n) % cols
        row_of = np.arange(n) // cols
        pu, pv = space.pair_u, space.pair_v
        G = len(pu)
        gridd = np.abs(col_of[pu] - col_of[pv]) + np.abs(row_of[pu] - row_of[pv])
        self.order = np.lexsort((np.arange(G), gridd)).astype(np.int64)
        # The greedy scan's used-set is per-chiplet, so the sequential pass
        # decomposes into n independent chains: chiplet c processes its n-1
        # incident slots in the greedy order restricted to c. chain step j,
        # chiplet c -> genome slot / (slot, endpoint) distance index.
        chain_slot = np.zeros((n - 1, n), np.int64)
        chain_eslot = np.zeros((n - 1, n), np.int64)
        inv_j = np.zeros(2 * G, np.int64)
        inv_c = np.zeros(2 * G, np.int64)
        cnt = np.zeros(n, np.int64)
        for g in self.order:
            for endpoint, c in ((0, pu[g]), (1, pv[g])):
                j = cnt[c]
                cnt[c] += 1
                chain_slot[j, c] = g
                chain_eslot[j, c] = g + endpoint * G
                inv_j[endpoint * G + g] = j
                inv_c[endpoint * G + g] = c
        assert (cnt == n - 1).all()
        pair_id = np.full((n, n), G, np.int64)
        pair_id[pu, pv] = np.arange(G)
        pair_id[pv, pu] = np.arange(G)

        from ..traffic import make_traffic
        traffic = make_traffic(space.traffic_pattern, n, seed=space.seed)

        rep = NamedSharding(mesh, P())
        put = lambda x, dt: jax.device_put(jnp.asarray(x, dt), rep)
        self._pair_u = put(pu, jnp.int32)
        self._pair_v = put(pv, jnp.int32)
        self._pair_id = put(pair_id, jnp.int32)
        self._chain_slot = put(chain_slot, jnp.int32)
        self._chain_eslot = put(chain_eslot, jnp.int32)
        self._inv_j = put(inv_j, jnp.int32)
        self._inv_c = put(inv_c, jnp.int32)
        self._col = put(col_of, jnp.float32)
        self._row = put(row_of, jnp.float32)
        self._side = put(side, jnp.float32)
        self._phyx = put(phyx, jnp.float32)
        self._phyy = put(phyy, jnp.float32)
        self._cphyx = put(cphyx, jnp.float32)
        self._cphyy = put(cphyy, jnp.float32)
        self._bw = put(bw, jnp.float32)
        self._traffic = put(traffic, jnp.float32)
        self._consts = put([1.0, pkg.link_latency_const, pkg.link_latency_per_mm,
                            2.0 * make_chiplet(1).phy_latency,
                            make_chiplet(1).internal_latency], jnp.float32)
        self._euclid = pkg.link_routing == "euclidean"
        self.max_hops = max(n - 1, 1)
        self._eval = _adjacency_eval_fn(mesh, self.n, self.k_phys,
                                        self._euclid, self.max_hops,
                                        _donate_ok())

    def evaluate_async(self, genomes: np.ndarray) -> PendingGenomeEval:
        """Dispatch one fused, population-sharded call for a whole
        (repaired) population and return without blocking on the device;
        ``result()`` materializes metrics + host reports."""
        genomes = np.asarray(genomes, np.int64)
        Pn = len(genomes)
        with _span("genomes.dispatch", space="adjacency", pop=Pn, n=self.n):
            deg = self.space.degrees(genomes)
            if deg.max(initial=0) > self.k_phys:
                raise ValueError(
                    f"genome exceeds the repaired degree bound "
                    f"({int(deg.max())} > {self.k_phys}); repair genomes "
                    f"before evaluate_genomes")
            ndev = int(np.prod(list(self.mesh.shape.values())))
            bp = bucket_population(Pn, ndev)
            padded = genomes
            if bp != Pn:
                padded = np.concatenate(
                    [genomes, np.repeat(genomes[-1:], bp - Pn, axis=0)],
                    axis=0)
            bits = jax.device_put(jnp.asarray(padded % 2, jnp.int32),
                                  NamedSharding(self.mesh, P("data")))
            lat, thr, len_sum, hops = self._eval(
                bits, self._pair_u, self._pair_v, self._pair_id,
                self._chain_slot, self._chain_eslot, self._inv_j,
                self._inv_c, self._col, self._row, self._side, self._phyx,
                self._phyy, self._cphyx, self._cphyy, self._bw,
                self._traffic, self._consts)

        # the finisher runs from ``pending.result()``, after ``pending``
        # below is bound
        def finish() -> GenomeEvalResult:
            with _span("genomes.finish", space="adjacency", pop=Pn):
                pending.block()
                with _span("genomes.reports"):
                    reports = self._report_arrays(genomes, deg,
                                                  np.asarray(len_sum)[:Pn])
                    res = GenomeEvalResult(latency=np.asarray(lat)[:Pn],
                                           throughput=np.asarray(thr)[:Pn],
                                           reports=reports)
                self._count_hops(hops)
                return res

        pending = PendingGenomeEval(finish, (lat, thr, len_sum, hops))
        return pending

    def evaluate(self, genomes: np.ndarray) -> GenomeEvalResult:
        """One fused jitted call for a whole (repaired) population."""
        return self.evaluate_async(genomes).result()

    def evaluate_faults_async(self, genomes: np.ndarray,
                              link_fail: np.ndarray,
                              node_fail: np.ndarray) -> PendingGenomeEval:
        """Dispatch the fused [P, F] population x fault grid without
        blocking. link_fail: [F, G] bool (True = link failed); node_fail:
        [F, n] bool (True = chiplet dead). ``result()`` returns a
        ``FaultGridResult``; pristine reports are computed on the host as
        in ``evaluate_async`` (faults are runtime events — the design is
        still manufactured with every link)."""
        genomes = np.asarray(genomes, np.int64)
        link_fail = np.atleast_2d(np.asarray(link_fail, bool))
        node_fail = np.atleast_2d(np.asarray(node_fail, bool))
        Pn = len(genomes)
        F = len(link_fail)
        if link_fail.shape[1] != self.space.genome_length:
            raise ValueError(
                f"link_fail has {link_fail.shape[1]} link slots; space "
                f"has {self.space.genome_length}")
        if node_fail.shape != (F, self.n):
            raise ValueError(
                f"node_fail shape {node_fail.shape} != ({F}, {self.n})")
        with _span("genomes.dispatch_faults", space="adjacency", pop=Pn,
                   n=self.n, faults=F) as sp:
            deg = self.space.degrees(genomes)
            if deg.max(initial=0) > self.k_phys:
                raise ValueError(
                    f"genome exceeds the repaired degree bound "
                    f"({int(deg.max())} > {self.k_phys}); repair genomes "
                    f"before evaluate_genomes")
            ndev = int(np.prod(list(self.mesh.shape.values())))
            bp = bucket_population(Pn, ndev)
            sp.set(rows=bp * F)      # grid rows the device program runs
            padded = genomes
            if bp != Pn:
                padded = np.concatenate(
                    [genomes, np.repeat(genomes[-1:], bp - Pn, axis=0)],
                    axis=0)
            rep = NamedSharding(self.mesh, P())
            bits = jax.device_put(jnp.asarray(padded % 2, jnp.int32),
                                  NamedSharding(self.mesh, P("data")))
            link_alive = jax.device_put(jnp.asarray(~link_fail), rep)
            node_alive = jax.device_put(jnp.asarray(~node_fail), rep)
            fn = _adjacency_faults_fn(self.mesh, self.n, self.k_phys,
                                      self._euclid, self.max_hops,
                                      _donate_ok())
            lat, thr, reach, len_sum, hops = fn(bits, link_alive,
                                                node_alive, *self._tables())

        def finish() -> FaultGridResult:
            with _span("genomes.finish_faults", space="adjacency", pop=Pn):
                pending.block()
                with _span("genomes.reports"):
                    reports = self._report_arrays(genomes, deg,
                                                  np.asarray(len_sum)[:Pn])
                    res = FaultGridResult(
                        latency=np.asarray(lat)[:Pn],
                        throughput=np.asarray(thr)[:Pn],
                        reachable_fraction=np.asarray(reach)[:Pn],
                        reports=reports)
                _metrics.counter("faults.rows").inc(Pn * F)
                _metrics.counter("faults.disconnected_rows").inc(int(
                    (res.reachable_fraction < 1.0 - REACH_EPS).sum()))
                self._count_hops(hops)
                return res

        pending = PendingGenomeEval(finish, (lat, thr, reach, len_sum, hops))
        return pending

    def evaluate_faults(self, genomes: np.ndarray, link_fail: np.ndarray,
                        node_fail: np.ndarray) -> FaultGridResult:
        """Blocking wrapper over ``evaluate_faults_async``."""
        return self.evaluate_faults_async(genomes, link_fail,
                                          node_fail).result()

    def faults_memory(self, pop: int, n_scenarios: int) -> dict[str, int]:
        """Bytes per device of the fault-grid program that
        ``evaluate_faults_async`` runs for ``pop`` genomes under
        ``n_scenarios`` scenarios, from the compiled executable's own
        accounting: arguments, outputs, temporaries and generated code.
        Compiles (or finds in the compilation caches) the same program;
        runs nothing."""
        ndev = int(np.prod(list(self.mesh.shape.values())))
        rep = NamedSharding(self.mesh, P())
        fn = _adjacency_faults_fn(self.mesh, self.n, self.k_phys,
                                  self._euclid, self.max_hops, _donate_ok())
        G = self.space.genome_length
        mem = fn.lower(
            jax.ShapeDtypeStruct((bucket_population(pop, ndev), G),
                                 jnp.int32,
                                 sharding=NamedSharding(self.mesh,
                                                        P("data"))),
            jax.ShapeDtypeStruct((n_scenarios, G), jnp.bool_, sharding=rep),
            jax.ShapeDtypeStruct((n_scenarios, self.n), jnp.bool_,
                                 sharding=rep),
            *self._tables()).compile().memory_analysis()
        return {"argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "code_bytes": int(mem.generated_code_size_in_bytes)}

    def _count_hops(self, hops) -> None:
        """The load propagation's hops over every row the program ran
        (bucket padding included; a fault grid's P·F rows), against its
        bound ``max_hops``."""
        hops = np.asarray(hops)
        _metrics.counter("kernels.load_propagate.hops",
                         max_hops=self.max_hops).inc(int(hops.sum()))
        _metrics.counter("kernels.load_propagate.designs",
                         max_hops=self.max_hops).inc(hops.size)

    def _tables(self) -> tuple:
        """The replicated geometry, PHY, bandwidth, traffic and constant
        tables every fault-grid call takes after its three inputs."""
        return (self._pair_u, self._pair_v, self._pair_id, self._chain_slot,
                self._chain_eslot, self._inv_j, self._inv_c, self._col,
                self._row, self._side, self._phyx, self._phyy, self._cphyx,
                self._cphyy, self._bw, self._traffic, self._consts)

    def _report_arrays(self, genomes, deg, len_sums) -> ReportArrays:
        """Constraint columns [P] in host float64, exact against
        ``core.reports`` (the per-mm link-power term uses the device's f32
        length sums; it is zero under default packaging)."""
        from ..core.reports import adjacency_connected_fraction
        pkg = self.space.packaging
        n = self.n
        radix = np.clip(deg.max(axis=1), 1, self.k_phys)
        n_links = (np.asarray(genomes, np.int64) % 2).sum(axis=1)
        power = (n * self._chip_power[radix]
                 + pkg.link_power_const * n_links
                 + pkg.link_power_per_mm * np.asarray(len_sums, np.float64))
        return ReportArrays(
            total_chiplet_area=n * self._chip_area[radix],
            interposer_area=self._ia[radix],
            power=power,
            cost=self._cost[radix],
            reachable_fraction=adjacency_connected_fraction(
                genomes, self.space.pair_u, self.space.pair_v, n))


# ---------------------------------------------------------------------------
# ParametricSpace: structure-table gather
# ---------------------------------------------------------------------------

def _parametric_eval(next_hop, step_cost, node_weight, adj_bw, traffic,
                     *, n_steps: int, max_hops: int):
    _note_compile(("parametric",) + tuple(next_hop.shape)
                  + (n_steps, max_hops))
    from .engine import _eval_one
    return jax.vmap(_eval_one, in_axes=(0, 0, 0, 0, 0, None, None))(
        next_hop, step_cost, node_weight, adj_bw, traffic, n_steps, max_hops)


@_locked_factory
@functools.lru_cache(maxsize=None)
def _parametric_eval_fn(mesh, n_steps: int, max_hops: int):
    """Jitted, population-sharded parametric eval per (mesh, statics) —
    module-cached, so every pipeline whose node count rounds to the same
    ``node_bucket`` shares ONE compiled program."""
    impl = functools.partial(_parametric_eval, n_steps=n_steps,
                             max_hops=max_hops)
    f = shard_map(impl, mesh=mesh, in_specs=(P("data"),) * 5,
                  out_specs=(P("data"),) * 2, check_vma=False)
    return jax.jit(f)


class ParametricPipeline:
    """Structure-table device path for ``opt.space.ParametricSpace``: the
    finite set of decodable structures is built lazily on the host (through
    the shared structure cache, so sweeps and optimizers reuse each other's
    builds) and stacked; each generation is an int-indexed gather plus one
    jitted proxy call, sharded over the population axis."""

    def __init__(self, space, mesh: jax.sharding.Mesh):
        self.space = space
        self.mesh = mesh
        # Heterogeneous-n sub-batches all pad to one power-of-two node
        # bucket: spaces with different max node counts reuse the same
        # compiled program instead of fragmenting the jit cache per exact n
        # (asserted with the COMPILE_COUNTS probe in tests).
        self.n = node_bucket(space.max_nodes)
        self.n_steps = num_doubling_steps(self.n)
        # the shape-stable safety bound; flows converge at the real routed
        # diameter regardless (the throughput loop is adaptive), so the
        # bucket-derived bound costs nothing
        self.max_hops = max(self.n - 1, 1)
        self._eval = _parametric_eval_fn(mesh, self.n_steps, self.max_hops)
        # Guards the lazily-grown structure tables (_sid/_next_hop/.../
        # _stacked/_reports): two server jobs sharing this pipeline may
        # encode new structures concurrently, and _ensure both reads and
        # invalidates _stacked.
        self._lock = threading.RLock()
        self._sid: dict[tuple, int] = {}
        self._next_hop: list[np.ndarray] = []
        self._step_cost: list[np.ndarray] = []
        self._node_weight: list[np.ndarray] = []
        self._adj_bw: list[np.ndarray] = []
        self._traffic: list[np.ndarray] = []
        self._reports: list[tuple] = []
        self._stacked = None

    def _point_for(self, key: tuple):
        from .sweep import DesignPoint
        ti, ci, ri, beff = key
        sp = self.space
        return DesignPoint(
            index=0, topology=sp.topologies[ti],
            n_chiplets=sp.chiplet_counts[ci],
            traffic_pattern=sp.traffic_pattern, routing=sp.routings[ri],
            seed=sp.seed, shg_bits=beff, packaging=sp.packaging,
            technology=sp.technology)

    def _key_of(self, genome: np.ndarray) -> tuple:
        from ..topologies.grid import grid_dims
        sp = self.space
        ti, ci, ri, bi = (int(x) for x in genome)
        beff = 0
        if sp.topologies[ti] == "shg":
            r, c = grid_dims(sp.chiplet_counts[ci])
            beff = int(sp.shg_bits_choices[bi]) % 2 ** (r + c - 4)
        return (ti, ci, ri, beff)

    def _ensure(self, keys) -> None:
        from ..core.reports import report_arrays
        from ..core.structure_cache import GLOBAL_STRUCTURE_CACHE
        from .batch import _structures_for

        missing = [k for k in dict.fromkeys(keys) if k not in self._sid]
        if not missing:
            return
        n = self.n
        points = [self._point_for(k) for k in missing]
        entries = _structures_for(points, validate=False,
                                  cache=GLOBAL_STRUCTURE_CACHE,
                                  keep_designs=True)
        designs = []
        for key, pt in zip(missing, points):
            entry = entries[pt.structure_key()]
            arrays = entry.arrays
            k = arrays.next_hop.shape[0]
            nc = arrays.n_chiplets
            # int16 resident tables (n < 32768 always); widened at gathers
            nh = np.tile(np.arange(n, dtype=np.int16)[:, None], (1, n))
            nh[:k, :k] = arrays.next_hop
            sc = np.zeros((n, n), np.float32)
            sc[:k, :k] = arrays.step_cost
            nw = np.zeros(n, np.float32)
            nw[:k] = arrays.node_weight
            bwm = np.zeros((n, n), np.float32)
            bwm[:k, :k] = arrays.adj_bw
            tr = np.zeros((n, n), np.float32)
            tr[:nc, :nc] = pt.traffic()
            self._sid[key] = len(self._next_hop)
            self._next_hop.append(nh)
            self._step_cost.append(sc)
            self._node_weight.append(nw)
            self._adj_bw.append(bwm)
            self._traffic.append(tr)
            design = entry.extra.get("design")
            designs.append(design if design is not None else pt.build())
        rep = report_arrays(designs)
        for i in range(len(missing)):
            self._reports.append((rep.total_chiplet_area[i],
                                  rep.interposer_area[i],
                                  rep.power[i], rep.cost[i]))
        self._stacked = None

    def evaluate_async(self, genomes: np.ndarray) -> PendingGenomeEval:
        """Dispatch one sharded proxy call for the population (structures
        built/gathered on the host first) without blocking on the device."""
        genomes = self.space.repair(np.asarray(genomes, np.int64))
        Pn = len(genomes)
        with _span("genomes.dispatch", space="parametric", pop=Pn,
                   n=self.n) as sp:
            keys = [self._key_of(g) for g in genomes]
            with self._lock:
                n_known = len(self._sid)
                with _span("genomes.build_structures"):
                    self._ensure(keys)
                sp.set(new_structures=len(self._sid) - n_known)
                sids = np.asarray([self._sid[k] for k in keys], np.int64)
                if self._stacked is None:
                    self._stacked = (np.stack(self._next_hop),
                                     np.stack(self._step_cost),
                                     np.stack(self._node_weight),
                                     np.stack(self._adj_bw),
                                     np.stack(self._traffic))
                stacked = self._stacked
            ndev = int(np.prod(list(self.mesh.shape.values())))
            bp = bucket_population(Pn, ndev)
            gsids = sids
            if bp != Pn:
                gsids = np.concatenate([sids, np.repeat(sids[-1:], bp - Pn)])
            sharding = NamedSharding(self.mesh, P("data"))
            args = [jax.device_put(t[gsids], sharding)
                    for t in stacked]
            lat, thr = self._eval(*args)

        def finish() -> GenomeEvalResult:
            with _span("genomes.finish", space="parametric", pop=Pn):
                with self._lock:
                    cols = np.asarray([self._reports[s] for s in sids],
                                      np.float64)
                reports = ReportArrays(total_chiplet_area=cols[:, 0],
                                       interposer_area=cols[:, 1],
                                       power=cols[:, 2], cost=cols[:, 3])
                pending.block()
                return GenomeEvalResult(latency=np.asarray(lat)[:Pn],
                                        throughput=np.asarray(thr)[:Pn],
                                        reports=reports)

        pending = PendingGenomeEval(finish, (lat, thr))
        return pending

    def evaluate(self, genomes: np.ndarray) -> GenomeEvalResult:
        return self.evaluate_async(genomes).result()


def make_pipeline(space, mesh: jax.sharding.Mesh):
    """Pipeline for a search space, or None when only the host path applies
    (e.g. adjacency spaces routed with the RNG-streamed updown_random)."""
    from ..opt.space import AdjacencySpace, ParametricSpace

    if isinstance(space, AdjacencySpace):
        if space.routing != "dijkstra_lowest_id":
            return None
        return AdjacencyPipeline(space, mesh)
    if isinstance(space, ParametricSpace):
        return ParametricPipeline(space, mesh)
    return None
