"""``opt.rank`` plus ``opt.select`` per generation: non-dominated sorting
and crowding of the parents, and the environmental selection over parents
and children (program span)."""
import spans

STAGES = ("opt.rank", "opt.select")


def read(rec):
    gens = spans.in_window(rec, "opt.generation") if rec.get("spans") else []
    if not gens or not any(spans.in_window(rec, s) for s in STAGES):
        return None
    return sum(spans.total_ns(rec, s) for s in STAGES) / len(gens) / 1e6
