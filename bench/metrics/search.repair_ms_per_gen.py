"""``space.repair`` per generation: degree cap, reachability and the
connection of disconnected rows (program span)."""
import spans

STAGE = "space.repair"


def read(rec):
    gens = spans.in_window(rec, "opt.generation") if rec.get("spans") else []
    if not gens or not spans.in_window(rec, STAGE):
        return None
    return spans.total_ns(rec, STAGE) / len(gens) / 1e6
