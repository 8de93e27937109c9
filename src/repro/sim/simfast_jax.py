"""Jitted XLA backend for ``FastSim.run_batch``.

The whole cycle loop runs as one ``lax.while_loop`` over fixed-shape state:
the same struct-of-arrays model as the numpy path (ring buffers per
(link, VC), dense head-flit mirrors, hashed rotating arbitration,
credit/VC-allocation rules), expressed as masked whole-array ops so XLA
compiles the ~hundred numpy dispatches per cycle into a handful of fused
kernels. Decisions are bit-identical to the numpy backend (asserted in
tests/test_simfast.py); only wall-clock differs.

Fixed-shape tricks:
- every scatter target array carries one spare row; masked-out lanes
  scatter into the spare, which is reset or sliced away before use
  (link-buffer arrays spare at ``nb_link``, unified route arrays at
  ``nb_tot``, injection arrays at ``n``, packet arrays at ``k_pad``);
- the packet schedule is padded to a power-of-two bucket so the compile
  cache (keyed only on shapes) is reused across injection rates;
- idle cycles are simply executed (no event jumping) — they cost
  microseconds once compiled.

Compiled callables are cached per shape signature, so a saturation search
compiles at most a few times (B=1 zero-load + B=chunk ladders) per network
size, and the cache is shared by all networks with the same shape.
"""
from __future__ import annotations

import numpy as np

from .cyclesim import SimConfig, SimStats

_FAR32 = np.int32(1 << 30)
_HASH_A = 2654435761
_HASH_B = 40503

_COMPILE_CACHE: dict = {}


def jax_available() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except Exception:
        return False


def _pow2_bucket(k: int) -> int:
    b = 1024
    while b < k:
        b *= 2
    return b


def _build_runner(shape_key):
    """Compile (or fetch) the jitted runner for one shape signature."""
    fn = _COMPILE_CACHE.get(shape_key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp
    from jax import lax

    (B, bn, L, V, cap, psize, k_pad, nb_base) = shape_key
    n = B * bn
    nb_link = L * V
    nb_tot = nb_link + n
    BIG = jnp.int64(1) << jnp.int64(62)
    i32 = jnp.int32

    iota_link = jnp.arange(nb_link, dtype=jnp.int64)
    iota_tot = jnp.arange(nb_tot, dtype=jnp.int64)
    iota_L = jnp.arange(L, dtype=i32)

    def runner(consts, scalars, init):
        # consts carry one spare row each where lanes can scatter/gather
        (out_link, lbn_sp, link_fwd_delay, node_delay, pa_u32,
         rep_node, rep_node_sp, rep_link, rep_buf, pk_dst_sp, pk_birth_sp,
         inj_end_sp) = consts
        (warm_end, meas_end, horizon, dc) = scalars

        def cond(st):
            cycle, _, _, _, cnt = st[0], st[1], st[2], st[3], st[4]
            inj_ready = st[13]
            return (cycle < horizon) & (
                jnp.any(cnt[:nb_link] > 0)
                | jnp.any(inj_ready[:n] < _FAR32))

        def body(st):
            (cycle, ring_code, ring_ready, head, cnt, head_ready, head_code,
             outl, routed, route_tgt, owner, inj_ptr, inj_seq, inj_ready,
             pk_head_arr, lat_sum, head_lat_sum, measured, accepted,
             last_progress, deadlock) = st
            cnt0 = cnt          # decisions use start-of-cycle occupancy
            ready_l = (cnt[:nb_link] > 0) & (head_ready[:nb_link] <= cycle)
            ready_i = inj_ready[:n] <= cycle
            prio = ((pa_u32 + jnp.uint32(cycle) * jnp.uint32(_HASH_B))
                    & jnp.uint32(0x7FFFFFFF)).astype(jnp.int64)

            # ---- ejection: one winner per node -----------------------
            ej_mask = ready_l & (outl[:nb_link] < 0)
            ekey = jnp.where(ej_mask, (prio[:nb_link] << 20) | iota_link,
                             BIG)
            node_min = jnp.full(n, BIG).at[lbn_sp[:nb_link]].min(ekey)
            ej_valid = node_min < BIG
            ebuf = jnp.where(ej_valid, (node_min & 0xFFFFF).astype(i32),
                             nb_link)
            ecode = head_code[ebuf]
            epkt = jnp.where(ej_valid, ecode // psize, k_pad)
            eseq = ecode - (ecode // psize) * psize
            head = head.at[ebuf].set((head[ebuf] + 1) % cap)
            cnt = cnt.at[ebuf].add(-1)
            nd = node_delay
            is_h = ej_valid & (eseq == 0)
            pk_head_arr = pk_head_arr.at[
                jnp.where(is_h, epkt, k_pad)].set(cycle + nd)
            is_t = ej_valid & (eseq == psize - 1)
            tpk = jnp.where(is_t, epkt, k_pad)
            tb = pk_birth_sp[tpk]
            meas = is_t & (tb >= warm_end) & (tb < meas_end)
            lat = (cycle + nd - tb).astype(jnp.float64)
            hlat = (pk_head_arr[tpk] - tb).astype(jnp.float64)
            lat_sum = lat_sum.at[rep_node].add(jnp.where(meas, lat, 0.0))
            head_lat_sum = head_lat_sum.at[rep_node].add(
                jnp.where(meas, hlat, 0.0))
            md = meas.astype(i32)
            measured = measured.at[rep_node].add(md)
            accepted = accepted.at[rep_node].add(psize * md)

            # ---- forwarding: one winner per output link --------------
            free_vc = (owner[:nb_link] < 0) & (cnt0[:nb_link] < cap)
            alloc_sp = jnp.concatenate(
                [jnp.any(free_vc.reshape(L, V), axis=1),
                 jnp.zeros(1, bool)])
            credit = cnt0[route_tgt[:nb_tot]] < cap  # route_tgt default 0
            outl_r = outl[:nb_tot]
            outl_cl = jnp.where(outl_r >= 0, outl_r, L).astype(i32)
            ready_cat = jnp.concatenate([ready_l, ready_i])
            elig = ready_cat & (outl_r >= 0) & jnp.where(
                routed[:nb_tot], credit, alloc_sp[outl_cl])
            fkey = jnp.where(elig, (prio << 20) | iota_tot, BIG)
            link_min = jnp.full(L + 1, BIG).at[outl_cl].min(fkey)
            w_key = link_min[:L]
            w_valid = w_key < BIG
            wb = jnp.where(w_valid, (w_key & 0xFFFFF).astype(i32), nb_tot)
            is_i = w_valid & (wb >= nb_link)
            lb = jnp.where(w_valid & ~is_i, wb, nb_link)     # link sources
            il = jnp.where(is_i, wb - nb_link, n)            # inj sources
            codel = head_code[lb]
            pktl = codel // psize
            seql = codel - pktl * psize
            pkt = jnp.where(is_i, inj_ptr[il], pktl)
            seq = jnp.where(is_i, inj_seq[il], seql)
            # VC allocation: lowest free, non-full VC on this link
            alloc_t = iota_L * V + jnp.argmax(
                free_vc.reshape(L, V), axis=1).astype(i32)
            rt = routed[wb]
            tgt = jnp.where(rt, route_tgt[wb], alloc_t).astype(i32)
            do_alloc = w_valid & ~rt
            owner = owner.at[jnp.where(do_alloc, tgt, nb_link)].set(
                jnp.where(do_alloc, wb, -1))
            routed = routed.at[jnp.where(do_alloc, wb, nb_tot)].set(True)
            route_tgt = route_tgt.at[
                jnp.where(do_alloc, wb, nb_tot)].set(tgt)
            # pops: link sources
            head = head.at[lb].set((head[lb] + 1) % cap)
            cnt = cnt.at[lb].add(-1)
            # pops: injection sources (advance packet on tail)
            s2 = inj_seq[il] + 1
            fin = is_i & (s2 == psize)
            inj_seq = inj_seq.at[il].set(jnp.where(fin, 0, s2))
            p2 = inj_ptr[il] + jnp.where(fin, 1, 0)
            inj_ptr = inj_ptr.at[il].set(p2)
            alive = fin & (p2 < inj_end_sp[il])
            pslot = jnp.where(alive, p2, k_pad)
            inj_ready = inj_ready.at[il].set(
                jnp.where(fin, jnp.where(alive, pk_birth_sp[pslot], _FAR32),
                          inj_ready[il]))
            nol = out_link[jnp.where(il < n, il, 0), pk_dst_sp[pslot]]
            outl = outl.at[jnp.where(fin, nb_link + il, nb_tot)].set(nol)
            # pushes (slots exact after pops)
            pt = jnp.where(w_valid, tgt, nb_link)
            newly = (cnt[pt] == 0) & w_valid
            slot = (head[pt] + cnt[pt]) % cap
            ring_code = ring_code.at[pt, slot].set(pkt * psize + seq)
            ring_ready = ring_ready.at[pt, slot].set(cycle + link_fwd_delay)
            cnt = cnt.at[pt].add(1)
            # tails release route + VC ownership
            tail = w_valid & (seq == psize - 1)
            owner = owner.at[jnp.where(tail, tgt, nb_link)].set(-1)
            routed = routed.at[jnp.where(tail, wb, nb_tot)].set(False)
            route_tgt = route_tgt.at[jnp.where(tail, wb, nb_tot)].set(0)

            # ---- refresh dense head mirrors for changed buffers ------
            refresh = jnp.concatenate(
                [ebuf, lb, jnp.where(newly, pt, nb_link)])
            rb = jnp.where(cnt[refresh] > 0, refresh, nb_link)
            h2 = head[rb]
            rcode = ring_code[rb, h2]
            head_code = head_code.at[rb].set(rcode)
            head_ready = head_ready.at[rb].set(ring_ready[rb, h2])
            rpkt = jnp.clip(rcode // psize, 0, k_pad)
            rd = pk_dst_sp[rpkt]
            rnodes = lbn_sp[rb]
            rol = out_link[rnodes, rd]
            rej = rd == rnodes
            outl = outl.at[jnp.where(rb < nb_link, rb, nb_tot)].set(
                jnp.where(rej, -1, rol))

            # ---- progress + deadlock watchdog ------------------------
            prog = jnp.zeros(B, bool).at[rep_node].max(ej_valid)
            prog = prog.at[rep_link].max(w_valid)
            last_progress = jnp.where(prog, cycle, last_progress)
            stale = (cycle - last_progress) > dc
            has_flits = jnp.any(cnt[:nb_link].reshape(B, nb_base) > 0,
                                axis=1)
            born = jnp.any((inj_ready[:n] <= cycle).reshape(B, bn), axis=1)
            trip = stale & (has_flits | born)
            deadlock = deadlock | trip
            cnt = cnt.at[:nb_link].set(
                jnp.where(trip[rep_buf], 0, cnt[:nb_link]))
            inj_ready = jnp.where(trip[rep_node_sp], _FAR32, inj_ready)
            inj_ptr = jnp.where(trip[rep_node_sp], inj_end_sp, inj_ptr)
            last_progress = jnp.where(stale & ~trip, cycle, last_progress)

            # spare rows must stay inert
            cnt = cnt.at[nb_link].set(0)
            head = head.at[nb_link].set(0)
            head_ready = head_ready.at[nb_link].set(_FAR32)

            return (cycle + 1, ring_code, ring_ready, head, cnt, head_ready,
                    head_code, outl, routed, route_tgt, owner, inj_ptr,
                    inj_seq, inj_ready, pk_head_arr, lat_sum, head_lat_sum,
                    measured, accepted, last_progress, deadlock)

        final = lax.while_loop(cond, body, init)
        return final[15], final[16], final[17], final[18], final[20]

    fn = jax.jit(runner)
    _COMPILE_CACHE[shape_key] = fn
    return fn


def run_batch_jax(sim, rates, cfg: SimConfig) -> list[SimStats]:
    """Execute ``FastSim.run_batch`` semantics on the XLA backend."""
    import jax
    import jax.numpy as jnp

    rates = [float(r) for r in rates]
    B = len(rates)
    net = sim if B == 1 else sim._replicated(B)
    bn = sim.n
    n = net.n
    V, cap, psize = cfg.num_vcs, cfg.buf_flits_per_vc, cfg.packet_size_flits
    L = net.n_links
    nb_link = L * V
    nb_tot = nb_link + n
    nb_base = nb_link // B
    warm_end = cfg.warmup_cycles
    meas_end = warm_end + cfg.measure_cycles
    horizon = meas_end + cfg.drain_cycles
    if nb_tot >= (1 << 20):
        raise RuntimeError("network too large for the packed-key jax "
                           "backend; use the numpy backend")

    # ---- schedules (identical to the numpy backend) ----------------------
    pk_dst, pk_birth, offsets, offered, total = \
        sim._prep_schedules(rates, cfg)
    k_pad = _pow2_bucket(max(total, 1))
    pk_dst_sp = np.zeros(k_pad + 1, np.int32)
    pk_birth_sp = np.full(k_pad + 1, _FAR32, np.int32)
    if total:
        pk_dst_sp[:total] = pk_dst
        pk_birth_sp[:total] = pk_birth
    offsets = offsets.astype(np.int32)

    # ---- constants --------------------------------------------------------
    out_link = net.out_link.astype(np.int32)
    rep_col = np.arange(n) // bn
    same_rep = rep_col[:, None] == rep_col[None, :]
    if not bool(((out_link >= 0) | ~same_rep
                 | np.eye(n, dtype=bool)).all()):
        raise RuntimeError("jax backend requires a complete routing table")
    lbn_sp = np.zeros(nb_link + 1, np.int32)
    lbn_sp[:nb_link] = np.repeat(net.link_dst, V)
    loc = np.concatenate((np.tile(np.arange(nb_base, dtype=np.int64), B),
                          nb_base + np.arange(n, dtype=np.int64) % bn))
    pa_u32 = (((loc + 1) * _HASH_A) % (1 << 32)).astype(np.uint32)
    rep_node = (np.arange(n, dtype=np.int32) // bn)
    rep_node_sp = np.zeros(n + 1, np.int32)
    rep_node_sp[:n] = rep_node
    rep_link = (net.link_src // bn).astype(np.int32)
    rep_buf = np.repeat(rep_link, V).astype(np.int32)
    inj_end_sp = np.zeros(n + 1, np.int32)
    inj_end_sp[:n] = offsets[1:]

    # ---- initial state ----------------------------------------------------
    inj_ptr0 = np.zeros(n + 1, np.int32)
    inj_ptr0[:n] = offsets[:-1]
    inj_ready0 = np.full(n + 1, _FAR32, np.int32)
    outl0 = np.full(nb_tot + 1, -1, np.int32)
    live = (inj_ptr0[:n] < inj_end_sp[:n]).nonzero()[0]
    if live.size:
        p = inj_ptr0[live]
        inj_ready0[live] = pk_birth_sp[p]
        outl0[nb_link + live] = out_link[live, pk_dst_sp[p]]

    shape_key = (B, bn, L, V, cap, psize, k_pad, nb_base)
    i32 = np.int32
    with jax.enable_x64(True):
        fn = _build_runner(shape_key)
        consts = tuple(jnp.asarray(x) for x in (
            out_link, lbn_sp, net.link_fwd_delay.astype(i32),
            net.node_delay.astype(i32), pa_u32, rep_node, rep_node_sp,
            rep_link, rep_buf, pk_dst_sp, pk_birth_sp, inj_end_sp))
        scalars = tuple(jnp.asarray(i32(x)) for x in (
            warm_end, meas_end, horizon, cfg.deadlock_cycles))
        init = (jnp.asarray(i32(0)),
                jnp.full((nb_link + 1, cap), -1, jnp.int32),   # ring_code
                jnp.zeros((nb_link + 1, cap), jnp.int32),      # ring_ready
                jnp.zeros(nb_link + 1, jnp.int32),             # head
                jnp.zeros(nb_link + 1, jnp.int32),             # cnt
                jnp.full(nb_link + 1, _FAR32, jnp.int32),      # head_ready
                jnp.zeros(nb_link + 1, jnp.int32),             # head_code
                jnp.asarray(outl0),                            # outl
                jnp.zeros(nb_tot + 1, bool),                   # routed
                jnp.zeros(nb_tot + 1, jnp.int32),              # route_tgt
                jnp.full(nb_link + 1, -1, jnp.int32),          # owner
                jnp.asarray(inj_ptr0),
                jnp.zeros(n + 1, jnp.int32),                   # inj_seq
                jnp.asarray(inj_ready0),
                jnp.zeros(k_pad + 1, jnp.int32),               # pk_head_arr
                jnp.zeros(B, jnp.float64), jnp.zeros(B, jnp.float64),
                jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
                jnp.zeros(B, jnp.int32), jnp.zeros(B, bool))
        res = fn(consts, scalars, init)
        lat_sum, head_lat_sum, measured, accepted, deadlock = [
            np.asarray(x) for x in res]

    from .simfast import assemble_stats
    return assemble_stats(bn, cfg, offered, lat_sum, head_lat_sum,
                          measured, accepted, deadlock)
