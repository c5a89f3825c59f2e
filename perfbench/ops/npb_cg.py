"""NPB CG (conjugate gradient) analogue: short blocks between a ring
halo exchange and an Allreduce, ``15 * sqrt(scale)`` iterations."""

import math

ITERATIONS = 15


def _skew(rng, spread):
    return rng.uniform(1.0 - spread, 1.0 + spread)


def script(tb, n_ranks, scale, rng):
    """Record the op script for ``n_ranks`` ranks into ``tb``."""
    group = list(range(n_ranks))
    for _ in range(int(ITERATIONS * math.sqrt(scale))):
        for node in range(n_ranks):
            tb.compute(node, 0.8 * _skew(rng, 0.30), cpu_frac=0.65)
        for node in range(n_ranks):
            tb.send(node, (node + 1) % n_ranks)
        for node in range(n_ranks):
            tb.recv(node, (node - 1) % n_ranks)
        for node in range(n_ranks):
            tb.compute(node, 0.5 * _skew(rng, 0.30), cpu_frac=0.65)
        tb.collective("allreduce", group)
