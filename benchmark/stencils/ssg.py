"""Plain float64 reference of ``ssg`` as ``yask_tpu/stencils/elastic.py
SSGElasticStencil`` defines it: isotropic elastic velocity-stress on a
standard staggered grid (intel/yask ``src/stencils/SSGElasticStencil.cpp``
on ``src/stencils/ElasticStencil/ElasticStencil.hpp``,
``ElasticStencilBase``), three velocities and six stresses, two stages a
step, the stresses from the velocities of the same step:

    v_c(t+1)  = v_c(t) + 1 / avg_c(rho) * sum_j D_j s_cj(t)
    s_cc(t+1) = s_cc(t) + lambda * tr(e) + 2 mu e_cc,    e from v(t+1)
    s_ab(t+1) = s_ab(t) + avg_a(mu) * (e_ab + e_ba)

``D_j`` is the staggered first difference of order ``2 * radius`` along
axis ``j``: ``radius`` taps either side of the half point, the forward
half point where the derivative is taken along the component's own axis
in stage 1 and across it in stage 2 (the program's ``_dstag`` shifts);
``avg_a(m)`` is ``(m(i) + m(i + 1 along a)) / 2``.  Outside the domain
every field and every material array reads as zero, so at the last
index along ``a`` the average is half the value there.

Where the program's ``ssg`` departs from upstream's, this follows the
program.  The departures, each as recalled (these sessions have neither
the upstream source nor a network):

* weights: upstream's ``ElasticStencilBase`` differentiates with
  ``stencil_O8_X/_Y/_Z`` over four taps either side and ships the
  constants ``c0_8 .. c3_8`` as placeholders; the program (and this
  file) takes the staggered Fornberg weights for ``radius``
  (1225/1024, -245/3072, 49/5120, -5/7168 at radius 4; 9/8, -1/24 at
  radius 2), as recalled;
* ``delta_t`` and the inverse grid spacings: upstream multiplies the
  updates by them; here they are folded into the material arrays
  (``lambda_``, ``mu`` times dt/h, ``rho`` over dt/h), as recalled;
* interpolation: upstream interpolates ``rho`` and ``mu`` to each
  staggered position over the points around it (up to eight for
  ``mu`` at an edge position), as recalled; the program takes one
  two-point average along the component's axis (``rho``) or the
  stress's first axis (``mu``).

Imports nothing of the program.
"""

import numpy as np

#: three velocities in rings of one slot, at rest; six stresses in rings
#: of two of which a step reads the newest, each seeded at a weight of
#: its own; the source on ``s_xx``
FIELDS = {
    "v_x": {"slots": 1, "weight": 0.0},
    "v_y": {"slots": 1, "weight": 0.0},
    "v_z": {"slots": 1, "weight": 0.0},
    "s_xx": {"slots": 2, "levels": 1, "weight": 1.0},
    "s_yy": {"slots": 2, "levels": 1, "weight": 0.9},
    "s_zz": {"slots": 2, "levels": 1, "weight": 0.8},
    "s_xy": {"slots": 2, "levels": 1, "weight": 0.5},
    "s_xz": {"slots": 2, "levels": 1, "weight": 0.4},
    "s_yz": {"slots": 2, "levels": 1, "weight": 0.3},
}
SOURCE = "s_xx"
ARRAYS = ("rho", "lambda_", "mu")
REACH = 8         # two stages of radius 4: stresses read new velocities
LEVELS = 9        # every stepped field exists after a step
READ_ARRAYS = 12  # nine stepped fields and three material arrays

AXIS = {"x": 0, "y": 1, "z": 2}
ROWS = {"x": ("xx", "xy", "xz"), "y": ("xy", "yy", "yz"),
        "z": ("xz", "yz", "zz")}


def staggered_weights(radius: int):
    """First-derivative weights at the half point for samples at
    ``-(radius - 1/2) .. radius - 1/2`` (unit spacing), lowest first:
    the odd interpolant through ``+-x_k`` gives the sample at ``x_k``
    the weight ``1 / (2 x_k) * prod_{j != k} x_j^2 / (x_j^2 - x_k^2)``
    (what Fornberg's recursion yields on this symmetric grid)."""
    xs = [k - 0.5 for k in range(1, radius + 1)]
    half = []
    for k, xk in enumerate(xs):
        w = 1.0 / (2.0 * xk)
        for j, xj in enumerate(xs):
            if j != k:
                w *= xj * xj / (xj * xj - xk * xk)
        half.append(w)
    return [-w for w in reversed(half)] + half


def diff(a, axis: int, shift: int, radius: int):
    """The staggered first difference along ``axis``: the ``2 radius``
    points at offsets ``-radius + shift .. radius - 1 + shift``, zero
    outside the box."""
    width = [(0, 0)] * 3
    width[axis] = (radius, radius)
    pad = np.pad(a, width)
    n = a.shape[axis]
    out = np.zeros_like(a)
    for k, c in enumerate(staggered_weights(radius)):
        cut = [slice(None)] * 3
        cut[axis] = slice(k + shift, k + shift + n)
        out = out + c * pad[tuple(cut)]
    return out


def avg2(m, axis: int, shape):
    """``(m(i) + m(i + 1 along axis)) / 2``, zero outside the box."""
    m = np.broadcast_to(np.asarray(m, np.float64), shape)
    width = [(0, 0)] * 3
    width[axis] = (0, 1)
    cut = [slice(None)] * 3
    cut[axis] = slice(1, None)
    return 0.5 * (m + np.pad(m, width)[tuple(cut)])


def buoyancy(rho, axis: int, shape):
    """``1 / rho`` at a velocity's staggered position: ``rho`` averaged
    along that component's axis."""
    return 1.0 / avg2(rho, axis, shape)


def step(state, coeffs, radius, lo, domain, rounder=None):
    """One time step on the box that starts at ``lo`` of ``domain``,
    whose outside is zero: every field's newest level in, the next
    out.  Nothing here depends on where the box lies.  ``rounder``
    (the control's) rounds every stored value."""
    keep = rounder if rounder is not None else (lambda a: a)
    v = {c: state["v_" + c][-1] for c in "xyz"}
    s = {c: state["s_" + c][-1]
         for c in ("xx", "yy", "zz", "xy", "xz", "yz")}
    shape = v["x"].shape
    rho, lam, mu = (np.asarray(coeffs[n], np.float64)
                    for n in ("rho", "lambda_", "mu"))

    # stage 1: velocities from the stresses at t
    for c in "xyz":
        div = sum(diff(s[ROWS[c][j]], j, 1 if "xyz"[j] == c else 0,
                       radius) for j in range(3))
        v[c] = keep(v[c] + buoyancy(rho, AXIS[c], shape) * div)

    # stage 2: stresses from the new velocities
    e = {(c, j): diff(v[c], AXIS[j], 0 if c == j else 1, radius)
         for c in "xyz" for j in "xyz"}
    tr = e["x", "x"] + e["y", "y"] + e["z", "z"]
    for c in "xyz":
        s[c + c] = keep(s[c + c] + lam * tr + 2.0 * mu * e[c, c])
    for a, b in ("xy", "xz", "yz"):
        s[a + b] = keep(s[a + b] + avg2(mu, AXIS[a], shape)
                        * (e[a, b] + e[b, a]))

    out = {"v_" + c: [a] for c, a in v.items()}
    out.update({"s_" + c: [a] for c, a in s.items()})
    return out


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """Every array read once per fused group and every stepped field
    written after it, over the group: 84 B at ``wf_steps`` 1."""
    return (READ_ARRAYS + LEVELS) * itemsize / wf_steps
