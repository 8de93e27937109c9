"""Share (%) of the window in which the search waits for the device: the
``genomes.block`` spans (the block on the generation's outputs, without
the host reports that follow it; program span)."""
import spans


def read(rec):
    blocks = spans.in_window(rec, "genomes.block") if rec.get("spans") else []
    if not blocks:
        return None
    lo, hi = rec["mono_window_ns"]
    return 100.0 * sum(e - s for s, e in blocks) / (hi - lo)
