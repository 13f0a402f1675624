"""Plain float64 reference of ``tti`` as ``yask_tpu/stencils/tti.py
TTIStencil`` defines it: the pseudo-acoustic propagator for tilted
transverse isotropy of Fletcher, Du and Fowler (Geophysics 74, 2009),
which intel/yask ships as its largest stencil
(``src/stencils/TTIStencil.cpp``, solution ``tti``, generated by
Devito).  Two coupled wavefields ``u`` and ``v``, second order in time,
over six read-only arrays: square slowness ``m``, boundary damping
``damp``, dip ``theta``, azimuth ``phi``, Thomsen's ``epsilon`` and
``delta``:

    G(f)  = sin(theta) cos(phi) Dx f + sin(theta) sin(phi) Dy f
            + cos(theta) Dz f        (derivative along the symmetry axis)
    Hz(f) = G(G(f)),    H0(f) = lap(f) - Hz(f)
    (2 m + damp dt) u(t+1) = (damp dt - 2 m) u(t-1) + 4 m u(t)
        + 2 dt^2 ((1 + 2 epsilon) H0(u) + sqrt(1 + 2 delta) Hz(v))
    (2 m + damp dt) v(t+1) = (damp dt - 2 m) v(t-1) + 4 m v(t)
        + 2 dt^2 (sqrt(1 + 2 delta) H0(u) + Hz(v))

``Dx`` is the centred first difference of order ``2 * radius`` over the
spacing ``H``, ``lap`` the sum over the axes of the centred second
difference of the same order.

**The inner ``G(f)`` and the outside of the domain.**  The program
keeps the inner ``G(u)``, ``G(v)`` and the trig in scratch vars (``gu``,
``gv``, ``ti0 .. ti3``) that the outer ``G`` reads ``radius`` points
away, so they are evaluated on the box grown by ``radius``, and one
step reaches ``2 * radius`` a side (``REACH``).  Outside the domain
every field and every array reads as zero, and a scratch value there
is **computed from those zero ghost cells, not set to zero**: the trig
of a zero tilt is ``sin 0 = 0``, ``cos 0 = 1``, so beyond a face in z
the inner ``G(f)`` is ``Dz f``, which reaches back across the face and
is not zero; beyond a face in x or y it is ``Dz`` of zeros.  The rows
within ``radius`` of the z faces read those values (the probes
``corner`` and ``far`` lie there).

Where the program's ``tti`` departs from upstream's, this follows the
program.  The departures, each as recalled (these sessions have neither
the upstream source nor a network):

* trig: upstream reads ``ti0 .. ti3`` (sin theta, cos phi, cos theta,
  sin phi) as precomputed input arrays; the program (and this file)
  takes ``theta`` and ``phi`` and evaluates the four every step, as
  recalled;
* the operator: upstream's generated code inlines the twice-applied
  rotated derivative into one expression per field; the program
  applies the centred ``G`` twice through a scratch array -- derived
  from the equations, not transcribed, as recalled;
* ``dt`` and ``H`` are Devito's defaults, recovered from upstream's
  generated coefficients (``1 / (0.8858... damp + 2 m)``; the first
  difference's ``2.5e-2 = 1 / (2 H)``), as recalled.

Imports nothing of the program.
"""

from math import factorial

import numpy as np

#: two wavefields in rings of two, both levels read (t-1 and t), both
#: seeded dense, ``v`` lighter than ``u``; the source on ``u``
FIELDS = {"u": {"slots": 2, "levels": 2, "weight": 1.0},
          "v": {"slots": 2, "levels": 2, "weight": 0.7}}
SOURCE = "u"
ARRAYS = ("m", "damp", "theta", "phi", "epsilon", "delta")
REACH = 8          # G applied twice at the cell's radius 4
READ_ARRAYS = 10   # u, v at t and t-1, and the six arrays

DT = 0.8858795678228
H = 20.0


def first_diff_weights(radius: int):
    """Centred first-derivative weights of order ``2 * radius`` at unit
    spacing, offsets ``-radius .. radius`` (the closed form of
    Fornberg's recursion on a symmetric grid)."""
    r = radius
    half = [(-1) ** (k + 1) * factorial(r) ** 2
            / (k * factorial(r - k) * factorial(r + k))
            for k in range(1, r + 1)]
    return [-w for w in reversed(half)] + [0.0] + half


def second_diff_weights(radius: int):
    """Centred second-derivative weights of the same order."""
    r = radius
    half = [2.0 * (-1) ** (k + 1) * factorial(r) ** 2
            / (k * k * factorial(r - k) * factorial(r + k))
            for k in range(1, r + 1)]
    return list(reversed(half)) + [-2.0 * sum(half)] + half


def taps_along(a, taps, axis: int):
    """``sum_k taps[k] * a[i + k - radius]`` along ``axis``, zero
    outside the box."""
    r = len(taps) // 2
    width = [(0, 0)] * 3
    width[axis] = (r, r)
    pad = np.pad(a, width)
    n = a.shape[axis]
    out = np.zeros_like(a)
    for k, c in enumerate(taps):
        if c != 0.0:
            cut = [slice(None)] * 3
            cut[axis] = slice(k, k + n)
            out += c * pad[tuple(cut)]
    return out


def d1(a, axis: int, radius: int):
    return taps_along(a, [w / H for w in first_diff_weights(radius)], axis)


def laplacian(a, radius: int):
    taps = [w / (H * H) for w in second_diff_weights(radius)]
    return sum(taps_along(a, taps, axis) for axis in range(3))


def trig(theta, phi):
    """The program's scratch ``ti0 .. ti3``."""
    return np.sin(theta), np.cos(phi), np.cos(theta), np.sin(phi)


def rotated(f, ti, radius: int):
    """``G(f)``: the first derivative along the tilted symmetry axis."""
    ti0, ti1, ti2, ti3 = ti
    return (ti0 * ti1 * d1(f, 0, radius) + ti0 * ti3 * d1(f, 1, radius)
            + ti2 * d1(f, 2, radius))


def grown(a, by: int):
    """A box with ``by`` rows of zeros on every side: what lies beyond
    the domain, as the program's ghost cells hold it."""
    return np.pad(a, by)


def inner(f, wide, radius: int):
    """The program's scratch ``gu`` / ``gv``: ``G(f)`` on the box grown
    by the radius, the grown rows computed like any other (from zeros
    and a zero tilt); ``wide`` is the trig on the grown box."""
    return rotated(grown(f, radius), wide, radius)


def hz(f, wide, radius: int):
    """``G(G(f))`` on ``f``'s own box."""
    r = radius
    return rotated(inner(f, wide, r), wide, r)[(slice(r, -r),) * 3]


def step(state, coeffs, radius, lo, domain, rounder=None):
    """One time step on the box that starts at ``lo`` of ``domain``,
    whose outside is zero.  ``state[f]`` holds ``[f(t-1), f(t)]`` and
    the result ``[f(t), f(t+1)]``.  Nothing here depends on where the
    box lies.  ``rounder`` (the control's) rounds every stored value."""
    keep = rounder if rounder is not None else (lambda a: a)
    r = radius
    (u_old, u), (v_old, v) = state["u"], state["v"]
    m, damp, theta, phi, eps, dlt = (
        np.broadcast_to(np.asarray(coeffs[n], np.float64), u.shape)
        for n in ARRAYS)

    wide = trig(grown(theta, r), grown(phi, r))    # once per box
    hz_u, hz_v = hz(u, wide, r), hz(v, wide, r)
    h0_u = laplacian(u, r) - hz_u

    sq_d = np.sqrt(1.0 + 2.0 * dlt)
    inv = 1.0 / (2.0 * m + damp * DT)
    back = damp * DT - 2.0 * m
    two_dt2 = 2.0 * DT * DT
    u_new = inv * (back * u_old + 4.0 * m * u
                   + two_dt2 * ((1.0 + 2.0 * eps) * h0_u + sq_d * hz_v))
    v_new = inv * (back * v_old + 4.0 * m * v
                   + two_dt2 * (sq_d * h0_u + hz_v))
    return {"u": [u, keep(u_new)], "v": [v, keep(v_new)]}


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """Every array read once per fused group (four levels of ``u`` and
    ``v``, six arrays) and the levels written after it (one each at
    ``wf_steps`` 1, both from 2 up), over the group: 48 B at
    ``wf_steps`` 1.  The scratch vars never touch HBM."""
    written = 2 * min(int(wf_steps), 2)
    return (READ_ARRAYS + written) * itemsize / wf_steps
