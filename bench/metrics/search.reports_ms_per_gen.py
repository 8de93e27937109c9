"""``genomes.reports`` per generation: the host report arrays (with the
union-find connectivity pass) and the copies of the results, after the
device block (program span)."""
import spans

STAGE = "genomes.reports"


def read(rec):
    gens = spans.in_window(rec, "opt.generation") if rec.get("spans") else []
    if not gens or not spans.in_window(rec, STAGE):
        return None
    return spans.total_ns(rec, STAGE) / len(gens) / 1e6
