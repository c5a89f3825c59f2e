"""NPB IS (integer sort) analogue: memory-bound ranks, alltoall-heavy.

Each iteration follows NPB IS ``rank()``: bucket count, Allreduce, key
redistribution, Alltoall, Alltoallv, local ranking; a final barrier.
Work per block is in seconds at the nominal frequency, times the
problem class's scale, with a per-rank skew drawn from ``rng``.
NPB IS times 10 iterations; the program's ``npb_family`` records 4, the
default here.
"""

ITERATIONS = 4


def _skew(rng, spread):
    return rng.uniform(1.0 - spread, 1.0 + spread)


def script(tb, n_ranks, scale, rng, iterations=ITERATIONS):
    """Record the op script for ``n_ranks`` ranks into ``tb``."""
    group = list(range(n_ranks))
    for _ in range(iterations):
        for node in range(n_ranks):
            tb.compute(node, 6.0 * scale * _skew(rng, 0.35),
                       cpu_frac=0.45)
        tb.collective("allreduce", group)
        for node in range(n_ranks):
            tb.compute(node, 3.0 * scale * _skew(rng, 0.35),
                       cpu_frac=0.40)
        tb.collective("alltoall", group)
        for node in range(n_ranks):
            tb.compute(node, 2.0 * scale * _skew(rng, 0.50),
                       cpu_frac=0.40)
        tb.collective("alltoallv", group)
        for node in range(n_ranks):
            tb.compute(node, 4.0 * scale * _skew(rng, 0.35),
                       cpu_frac=0.50)
    tb.collective("barrier", group)
