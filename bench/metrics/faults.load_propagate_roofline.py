"""Share (%) of its roofline that the load-propagation kernel reaches in a
fault cell: the least time the chip needs for the operations and bytes the
algorithm requires (``kernel_cost.load_propagate_cost``: unpadded n, the
P * F scenario rows each chip evaluates in one call, and the longest
routed path of those degraded designs) over the kernel's device time per
call (device trace, by the kernel's name). The bound (HBM or compute)
comes from ``peaks.json``."""
import re

import numpy as np

import compare_faults
import kernel_cost

KERNEL = re.compile(r"^load_prop_pallas")


def degraded_diameter(bits: np.ndarray, link_fail: np.ndarray,
                      node_fail: np.ndarray, n: int) -> int:
    """Longest finite hop-count shortest path over every design of
    ``bits`` [R, G] under every scenario (``link_fail`` [F, G],
    ``node_fail`` [F, n]): a vectorised breadth-first search over each
    (design, scenario) pair whose scenario kills a link the design has or
    a chiplet, and over the pristine graph of each design that some
    scenario leaves whole."""
    pu, pv = np.triu_indices(n, 1)
    bits = np.asarray(bits, bool)

    def adjacency(rows):
        a = np.zeros((len(rows), n, n), bool)
        a[:, pu, pv] = rows
        return a | a.transpose(0, 2, 1)

    hit = compare_faults.degraded_rows(bits, link_fail, node_fail)
    r, f = np.nonzero(hit)
    stacks = [adjacency(bits[~hit.all(axis=1)])]
    if len(r):
        alive = ~node_fail[f]
        a = adjacency(bits[r] & ~link_fail[f])
        stacks.append(a & alive[:, :, None] & alive[:, None, :])
    a = np.concatenate(stacks).astype(np.float32)
    reached = np.broadcast_to(np.eye(n, dtype=bool), a.shape).copy()
    frontier = reached.copy()
    hops = -1
    while frontier.any():
        hops += 1
        frontier = (np.matmul(frontier.astype(np.float32), a) > 0) & ~reached
        reached |= frontier
    return hops


def read(rec):
    tr = rec.get("trace")
    if not tr or "fault_link_fail" not in rec:
        return None
    lo, hi = tr["window_ns"]
    calls = [e - s for d in rec["device_ids"]
             for name, s, e in tr["ops"].get(d, [])
             if KERNEL.search(name) and lo <= s < hi]
    if not calls:
        return None
    n, G = rec["n_chiplets"], rec["genome_length"]
    link_fail, node_fail = rec["fault_link_fail"], rec["fault_node_fail"]
    F = len(link_fail)
    peak = kernel_cost.peaks(rec["device_kind"])
    chips = len(rec["device_ids"])
    need = []
    for packed in rec["window_bits"]:
        bits = np.unpackbits(packed, axis=1, count=G)
        for shard in np.array_split(bits, chips):
            dia = degraded_diameter(shard, link_fail, node_fail, n)
            ops, nbytes = kernel_cost.load_propagate_cost(n, len(shard) * F,
                                                          dia)
            need.append(kernel_cost.min_seconds(ops, nbytes, peak)[0])
    return 100.0 * (float(np.mean(need)) * 1e9) / float(np.mean(calls))
