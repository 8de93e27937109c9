"""``genomes.dispatch`` per generation: degrees, padding and the transfer
of the population's bits to the device (program span)."""
import spans


def read(rec):
    gens = spans.in_window(rec, "opt.generation") if rec.get("spans") else []
    disp = spans.in_window(rec, "genomes.dispatch") if gens else []
    if not disp:
        return None
    return spans.total_ns(rec, "genomes.dispatch") / len(gens) / 1e6
