"""Process start (the first line of ``run.py``) to the first timed
generation or arrival: imports, JAX start-up, building the program's
state, warm-up and compilation (host clock)."""


def read(rec):
    return rec["setup_s"]
