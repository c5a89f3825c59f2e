"""NPB LU (lower-upper SSOR) analogue on NPB's two-dimensional process
grid: per iteration a lower and an upper wavefront sweep over the
k-planes, point to point between neighbours, then ``rhs``'s halo
exchange; one allreduce (``l2norm``) at the end.

The grid follows NPB's ``proc_grid`` (``xdim = 2**(ndim//2)``, doubled
when ``ndim`` is odd, ``ydim = n / xdim``); rank ``r`` sits at row
``r % xdim`` and column ``r // xdim`` (``neighbors.f``: north and south
are the rows either side, west and east the columns).  Its block holds
``ni x nj`` points of the 102 x 102 plane, split as NPB's ``subdomain``
splits it.  Work per plane, in seconds at the nominal frequency:
``0.125 * scale * ni * nj / 169`` times ``U(0.9, 1.1)``, ``cpu_frac``
0.75; ``rhs`` is ``nz - 2`` planes' worth times one such draw,
``cpu_frac`` 0.60.
"""

GRID = 102
ITERATIONS = 2


def _skew(rng, spread):
    return rng.uniform(1.0 - spread, 1.0 + spread)


def _extent(points, parts, index):
    return points // parts + (1 if index < points % parts else 0)


def script(tb, n_ranks, scale, rng, iterations=ITERATIONS, nz=GRID):
    """Record the op script for ``n_ranks`` ranks (a power of two)."""
    if n_ranks < 1 or n_ranks & (n_ranks - 1):
        raise ValueError(f"NPB LU needs a power of two ranks, got {n_ranks}")
    ndim = n_ranks.bit_length() - 1
    xdim = 2 ** (ndim // 2) * (2 if ndim % 2 else 1)
    ydim = n_ranks // xdim
    planes = nz - 2
    plane_w, around = [], []
    for r in range(n_ranks):
        row, col = r % xdim, r // xdim
        ni, nj = _extent(GRID, xdim, row), _extent(GRID, ydim, col)
        plane_w.append(0.125 * scale * ni * nj / 169)
        around.append({"n": r - 1 if row > 0 else None,
                       "s": r + 1 if row < xdim - 1 else None,
                       "w": r - xdim if col > 0 else None,
                       "e": r + xdim if col < ydim - 1 else None})

    def sweep(r, recv_from, send_to):
        for side in recv_from:
            if around[r][side] is not None:
                tb.recv(r, around[r][side])
        tb.compute(r, plane_w[r] * _skew(rng, 0.1), cpu_frac=0.75)
        for side in send_to:
            if around[r][side] is not None:
                tb.send(r, around[r][side])

    for _ in range(iterations):
        for _k in range(planes):               # jacld + blts
            for r in range(n_ranks):
                sweep(r, "nw", "se")
        for _k in range(planes):               # jacu + buts
            for r in range(n_ranks):
                sweep(r, "se", "nw")
        for r in range(n_ranks):               # rhs: exchange_3, fluxes
            for side in "nswe":
                if around[r][side] is not None:
                    tb.send(r, around[r][side])
            for side in "nswe":
                if around[r][side] is not None:
                    tb.recv(r, around[r][side])
            tb.compute(r, planes * plane_w[r] * _skew(rng, 0.1),
                       cpu_frac=0.60)
    tb.collective("allreduce", list(range(n_ranks)))   # l2norm
