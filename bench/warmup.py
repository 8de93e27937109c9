"""Warm-up shared by the drivers: compile, before the window, every
shape the window can meet."""
from __future__ import annotations

import numpy as np


def warm_repair(space, pop: int) -> None:
    """Run repair's jitted degree-cap scan once at every power-of-two
    candidate bucket it can meet on ``pop`` genomes (16 up to the bucket
    that holds all G genes; an over-cap chiplet has at least
    max_degree + 1 > 8 candidates), so no bucket first compiles inside the
    window. The scan is called directly with the arguments ``repair``
    gives it (gene-major int32 bits with a sentinel row, degrees, the
    candidate list): the candidate list is all sentinel, so every step is
    a no-op, and none of repair's host passes runs."""
    G, n = space.genome_length, space.n_chiplets
    scan = space._degree_cap_fn()
    bits_t = np.zeros((G + 1, pop), np.int32)
    deg_t = np.zeros((n, pop), np.int32)
    b = 16
    while True:
        np.asarray(scan(bits_t, deg_t, np.full(b, G, np.int32))[0])
        if b >= G:
            return
        b *= 2
