"""Device idle share (%) in a search cell, averaged over the chips used:
1 - (union of device op intervals) / traced window (device trace)."""
import spans


def read(rec):
    if "completed_evals" not in rec:
        return None
    return spans.idle_share(rec)
