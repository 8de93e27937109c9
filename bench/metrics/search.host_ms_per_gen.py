"""Host stages per generation: self time of ``opt.generation`` without its
``opt.dispatch`` (selection, ranking, variation, repair), plus
``opt.flush_deferred`` (archive ingest), per generation in the window."""
import spans


def read(rec):
    gens = spans.in_window(rec, "opt.generation") if rec.get("spans") else []
    if not gens:
        return None
    host = (spans.self_ns(rec, "opt.generation", ("opt.dispatch",))
            + spans.total_ns(rec, "opt.flush_deferred"))
    return host / len(gens) / 1e6
