"""Designs whose results were returned to the optimizer in the window,
over the window's wall time (host clock; the window holds whole
generations)."""


def read(rec):
    if "completed_evals" not in rec:
        return None
    return rec["completed_evals"] / rec["window_s"]
