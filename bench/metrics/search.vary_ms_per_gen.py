"""``opt.vary`` per generation: tournaments, crossover and mutation
(program span)."""
import spans

STAGE = "opt.vary"


def read(rec):
    gens = spans.in_window(rec, "opt.generation") if rec.get("spans") else []
    if not gens or not spans.in_window(rec, STAGE):
        return None
    return spans.total_ns(rec, STAGE) / len(gens) / 1e6
