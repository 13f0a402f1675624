"""Plain float64 reference of the isotropic acoustic update, from
upstream's equation (intel/yask ``src/stencils/Iso3dfdStencil.cpp``):

    p(t+1) = 2 p(t) - p(t-1) + vel * lap(p(t))

with ``lap`` the sum over x, y, z of the order-2r centred second
difference.  Imports nothing of the program.  Outside the domain the
field reads as zero (upstream's halo at a physical boundary).
"""

from math import factorial

import numpy as np

#: what a run must know to seed and read this stencil's state
STATE_VAR = "pressure"
LEVELS = 2        # time levels of STATE_VAR a step reads: p(t), p(t-1)
SLOTS = 2         # ring slots the benchmark seeds (oldest first)
CONSTS = ("vel",)  # read-only arrays, one value each in the cells
READ_ARRAYS = 3   # p(t), p(t-1), vel


def second_diff_coefficients(radius: int):
    """Centred second-derivative weights of order 2*radius at unit
    spacing, ``c[0]`` the centre, ``c[k]`` both neighbours at distance
    k (the closed form of Fornberg's recursion on a symmetric grid)."""
    r = radius
    c = [0.0] * (r + 1)
    for k in range(1, r + 1):
        c[k] = (2.0 * (-1) ** (k + 1) * factorial(r) ** 2
                / (k * k * factorial(r - k) * factorial(r + k)))
    c[0] = -2.0 * sum(c[1:])
    return c


def laplacian(cur, radius, dtype=np.float64):
    """Sum over the three axes of the centred second difference, zero
    outside the box: shifted slices of a zero-padded copy."""
    c = second_diff_coefficients(radius)
    r = radius
    pad = np.pad(cur, r)
    n = cur.shape
    lap = (3.0 * c[0]) * cur
    for k in range(1, r + 1):
        acc = (pad[r - k:r - k + n[0], r:-r, r:-r]
               + pad[r + k:r + k + n[0], r:-r, r:-r]
               + pad[r:-r, r - k:r - k + n[1], r:-r]
               + pad[r:-r, r + k:r + k + n[1], r:-r]
               + pad[r:-r, r:-r, r - k:r - k + n[2]]
               + pad[r:-r, r:-r, r + k:r + k + n[2]])
        lap = lap + dtype(c[k]) * acc
    return lap


def laplacian_fast(cur, radius):
    """The same sum as three 1-D correlations in C (scipy), which is
    what keeps a run's reference shorter than its window; without
    scipy, :func:`laplacian`."""
    try:
        from scipy.ndimage import correlate1d
    except ImportError:
        return laplacian(cur, radius)
    c = second_diff_coefficients(radius)
    taps = np.array(c[:0:-1] + c, dtype=np.float64)
    return sum(correlate1d(cur, taps, axis=ax, mode="constant", cval=0.0)
               for ax in range(3))


def step(levels, consts, radius, dtype=np.float64, rounder=None):
    """One time step on a box whose outside is zero.  ``levels`` is
    ``[p(t-1), p(t)]``; returns ``[p(t), p(t+1)]``.  ``rounder`` (the
    control's) rounds every stored value to a lower precision."""
    old, cur = levels
    new = (dtype(2.0) * cur - old
           + dtype(consts["vel"]) * laplacian_fast(cur, radius))
    if rounder is not None:
        new = rounder(new)
    return [cur, new.astype(dtype, copy=False)]


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """Bytes the algorithm must move per point and step when
    ``wf_steps`` steps are fused: every array read once per group plus
    every time level that must exist after it, over the group."""
    return (READ_ARRAYS + LEVELS) * itemsize / wf_steps
