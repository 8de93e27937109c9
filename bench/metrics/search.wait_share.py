"""Share of the window in which the search driver blocks on the device:
each ``opt.device_wait`` span from its start until the last device op of
the generation it waits for ends (device trace on the spans' clock). The
rest of that span (materializing results, report arrays) is host work."""
import spans


def read(rec):
    if "trace" not in rec or not rec.get("spans"):
        return None
    waits = spans.device_waits(rec, "opt.device_wait")
    if not waits:
        return None
    lo, hi = rec["mono_window_ns"]
    return 100.0 * sum(d - s for s, d, _ in waits) / (hi - lo)
