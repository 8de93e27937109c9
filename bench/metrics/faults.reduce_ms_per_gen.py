"""``faults.reduce`` per generation: the host fold of the [P, F] grid into
the robust columns and the disconnection constraint (program span)."""
import spans

STAGE = "faults.reduce"


def read(rec):
    gens = spans.in_window(rec, "opt.generation") if rec.get("spans") else []
    if not gens or not spans.in_window(rec, STAGE):
        return None
    return spans.total_ns(rec, STAGE) / len(gens) / 1e6
