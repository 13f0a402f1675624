"""Plain float64 reference of the isotropic acoustic update with an
absorbing layer, from upstream's equation (intel/yask
``src/stencils/Iso3dfdStencil.cpp``, solution ``iso3dfd_sponge``):

    p(t+1) = (2 p(t) - p(t-1) + vel * lap(p(t))) * sponge

with ``lap`` the sum over x, y, z of the order-2r centred second
difference and ``sponge`` upstream's ``cr_x * cr_y * cr_z``, here one
array over x, y, z (the configuration's ``consts`` give it as three
profiles, an axis each; their float32 product is what the device
holds).  **Where the sponge multiplies**: the whole new value, after
the update, as the program does (``yask_tpu/stencils/iso3dfd.py``
``Iso3dfdSpongeStencil``): not ``p(t)`` before the update, and not the
Laplacian's term alone.  Outside the domain the field reads as zero
(upstream's halo at a physical boundary), so a face without a taper is
a pressure-release surface.

Imports nothing of the program and nothing of ``iso3dfd.py`` beside it:
the Laplacian is written out again here, so that a fault planted in one
reference cannot pass in the other.
"""

from math import factorial

import numpy as np

#: what a run must know to seed, advance and read this stencil's state
#: (``README.md``, "A stencil's file"): one field in a ring of two
#: slots, both read by a step (p(t-1), p(t)), seeded at the full scale,
#: carrying the point source, probed; two read-only arrays that vary
#: with position; a step reaches ``radius`` points (no ``REACH``: the
#: configuration's radius)
FIELDS = {"pressure": {"slots": 2}}
ARRAYS = ("vel", "sponge")
LEVELS = 2        # time levels that must exist after a fused group
READ_ARRAYS = 4   # p(t), p(t-1), vel, sponge


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


def eigenvalue_bound(radius: int) -> float:
    """The largest magnitude an eigenvalue of ``lap`` can have: three
    axes of ``|c[0]| + 2 sum |c[k]|`` (the checkerboard mode).  The
    update is stable while ``vel`` times it stays under 4."""
    c = second_diff_coefficients(radius)
    return 3.0 * (abs(c[0]) + 2.0 * sum(abs(a) for a in c[1:]))


def laplacian(cur, radius):
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
        lap = lap + c[k] * acc
    return lap


def laplacian_fast(cur, radius):
    """The same sum as three 1-D correlations in C (scipy), which keeps
    a run's reference of three 168^3 cones under its window; without
    scipy, :func:`laplacian`."""
    try:
        from scipy.ndimage import correlate1d
    except ImportError:
        return laplacian(cur, radius)
    c = second_diff_coefficients(radius)
    taps = np.array(c[:0:-1] + c, dtype=np.float64)
    return sum(correlate1d(cur, taps, axis=ax, mode="constant", cval=0.0)
               for ax in range(3))


def step(state, coeffs, radius, lo=None, domain=None, rounder=None):
    """One time step on a box whose outside is zero.  ``state`` holds
    ``[p(t-1), p(t)]``; returns ``[p(t), p(t+1)]``.  ``coeffs`` are the
    box's own rows of ``vel`` and ``sponge`` (a float, or the float32
    array the device holds), so nothing here depends on where the box
    lies.  ``rounder`` (the control's) rounds every stored value to a
    lower precision."""
    old, cur = state["pressure"]
    vel = np.asarray(coeffs["vel"], dtype=np.float64)
    sponge = np.asarray(coeffs["sponge"], dtype=np.float64)
    new = (2.0 * cur - old + vel * laplacian_fast(cur, radius)) * sponge
    if rounder is not None:
        new = rounder(new)
    return {"pressure": [cur, new]}


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """Bytes the algorithm must move per point and step when
    ``wf_steps`` steps are fused: every array read once per group plus
    every time level that must exist after it, over the group (12 B at
    ``wf_steps`` 2)."""
    return (READ_ARRAYS + LEVELS) * itemsize / wf_steps
