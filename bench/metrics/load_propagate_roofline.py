"""Share (%) of its roofline that the load-propagation kernel reaches:
the least time the chip needs for the operations and bytes the algorithm
requires (``kernel_cost.py``: unpadded n, the rows each chip evaluates,
their routed diameter) over the kernel's device time per call (device
trace, by the kernel's name). The bound (HBM or compute) comes from
``peaks.json``."""
import re

import numpy as np

import kernel_cost

KERNEL = re.compile(r"^load_prop_pallas")


def read(rec):
    tr = rec.get("trace")
    if not tr or "window_bits" not in rec:
        return None
    lo, hi = tr["window_ns"]
    calls = [e - s for d in rec["device_ids"]
             for name, s, e in tr["ops"].get(d, [])
             if KERNEL.search(name) and lo <= s < hi]
    if not calls:
        return None
    n, G = rec["n_chiplets"], rec["genome_length"]
    peak = kernel_cost.peaks(rec["device_kind"])
    chips = len(rec["device_ids"])
    need = []
    for packed in rec["window_bits"]:
        bits = np.unpackbits(packed, axis=1, count=G)
        for shard in np.array_split(bits, chips):
            dia = max(kernel_cost.routed_diameter(b, n) for b in shard)
            ops, nbytes = kernel_cost.load_propagate_cost(n, len(shard), dia)
            need.append(kernel_cost.min_seconds(ops, nbytes, peak)[0])
    return 100.0 * (float(np.mean(need)) * 1e9) / float(np.mean(calls))
