"""NPB EP (embarrassingly parallel) analogue: one long CPU-bound block
per rank with a wide skew, then a few short blocks and Allreduces."""


def _skew(rng, spread):
    return rng.uniform(1.0 - spread, 1.0 + spread)


def script(tb, n_ranks, scale, rng):
    """Record the op script for ``n_ranks`` ranks into ``tb``."""
    group = list(range(n_ranks))
    for node in range(n_ranks):
        tb.compute(node, 60.0 * scale * _skew(rng, 0.45),
                   cpu_frac=0.95)
    tb.collective("allreduce", group)
    for _ in range(3):
        for node in range(n_ranks):
            tb.compute(node, 1.0 * scale * _skew(rng, 0.20),
                       cpu_frac=0.90)
        tb.collective("allreduce", group)
