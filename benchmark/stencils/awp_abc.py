"""Plain float64 reference of ``awp_abc`` as ``yask_tpu/stencils/awp.py``
defines it: the AWP-ODC velocity-stress update (Cui et al., SC'10;
intel/yask ``src/stencils/AwpStencil.cpp``), 4th-order staggered in
space, two stages a step:

    v_c(t+1)  = sponge * (v_c(t) + h / rho * div_c(stress(t)))
    el_cc     = lambda * tr(e) + 2 mu e_cc,   e from v(t+1)
    r_cc(t+1) = qp * (r_cc(t) + el_cc)
    s_cc(t+1) = sponge * (s_cc(t) + h * (el_cc - r_cc(t+1)))
    s_ab(t+1) = sponge * (s_ab(t) + h * mu * (e_ab + e_ba))

with the free surface at the top of z: ``stress_zz`` is 0 on the rows
``z == last``, ``stress_xz`` and ``stress_yz`` on ``z >= last - 1``.

Where the program's definition departs from upstream's, this follows
the program (it is the program that the benchmark holds to its own
semantics): the sponge is one 3-D var that multiplies every velocity
and stress update (upstream: a 3-D var or 1-D factors); anelasticity is
three coarse-grained memory variables on the normal stresses relaxed by
one ``qp`` array (upstream keeps one per stress component with its
relaxation-time and weight arrays); material arrays are read at the
point (no averaging between staggered positions); the free surface
zeroes stress rows and leaves the velocities to the zero halo.

Imports nothing of the program.  Outside the domain every field reads
as zero; a throwaway fixture of the benchmark's tests, not a cell.
"""

import numpy as np

#: three velocities and three memory variables in rings of one slot, at
#: rest; six stresses in rings of two of which a step reads the newest,
#: each seeded at a weight of its own; the source on ``stress_xx``
FIELDS = {
    "vel_x": {"slots": 1, "weight": 0.0},
    "vel_y": {"slots": 1, "weight": 0.0},
    "vel_z": {"slots": 1, "weight": 0.0},
    "stress_xx": {"slots": 2, "levels": 1, "weight": 1.0},
    "stress_yy": {"slots": 2, "levels": 1, "weight": 0.9},
    "stress_zz": {"slots": 2, "levels": 1, "weight": 0.8},
    "stress_xy": {"slots": 2, "levels": 1, "weight": 0.5},
    "stress_xz": {"slots": 2, "levels": 1, "weight": 0.4},
    "stress_yz": {"slots": 2, "levels": 1, "weight": 0.3},
    "mem_xx": {"slots": 1, "weight": 0.0},
    "mem_yy": {"slots": 1, "weight": 0.0},
    "mem_zz": {"slots": 1, "weight": 0.0},
}
SOURCE = "stress_xx"
ARRAYS = ("rho", "lambda_", "mu", "sponge", "qp")
SCALARS = ("h",)
REACH = 4         # two stages of radius 2: stresses read new velocities
LEVELS = 12       # every stepped field exists after a step
READ_ARRAYS = 17  # twelve stepped fields and five read-only arrays

#: first-derivative weights at -1.5, -0.5, 0.5, 1.5 (unit spacing)
C = (1.0 / 24.0, -9.0 / 8.0, 9.0 / 8.0, -1.0 / 24.0)
AXIS = {"x": 0, "y": 1, "z": 2}
ROWS = {"x": ("xx", "xy", "xz"), "y": ("xy", "yy", "yz"),
        "z": ("xz", "yz", "zz")}


def diff(a, axis: int, shift: int):
    """The staggered first difference along ``axis``: the four points
    at offsets ``-2 + shift .. 1 + shift``, zero outside the box."""
    width = [(0, 0)] * 3
    width[axis] = (2, 2)
    pad = np.pad(a, width)
    n = a.shape[axis]
    out = np.zeros_like(a)
    for k, c in enumerate(C):
        cut = [slice(None)] * 3
        cut[axis] = slice(k + shift, k + shift + n)
        out = out + c * pad[tuple(cut)]
    return out


def free_surface(name: str, new, z, last: int):
    """The rows of stress ``name`` that the free surface holds at 0."""
    if name == "zz":
        return np.where(z == last, 0.0, new)
    if "z" in name:
        return np.where(z >= last - 1, 0.0, new)
    return new


def step(state, coeffs, radius, lo, domain, rounder=None):
    """One time step on the box that starts at ``lo`` of ``domain``,
    whose outside is zero: every field's newest level in, the next
    out.  ``rounder`` (the control's) rounds every stored value."""
    keep = rounder if rounder is not None else (lambda a: a)
    v = {c: state["vel_" + c][-1] for c in "xyz"}
    s = {c: state["stress_" + c][-1]
         for c in ("xx", "yy", "zz", "xy", "xz", "yz")}
    r = {c: state["mem_" + c][-1] for c in ("xx", "yy", "zz")}
    rho, lam, mu = coeffs["rho"], coeffs["lambda_"], coeffs["mu"]
    sponge, qp, h = coeffs["sponge"], coeffs["qp"], coeffs["h"]
    z = (lo[2] + np.arange(v["x"].shape[2]))[None, None, :]
    last = domain[2] - 1

    # stage 1: velocities from the stresses at t
    for c in "xyz":
        div = sum(diff(s[ROWS[c][j]], j, 1 if "xyz"[j] == c else 0)
                  for j in range(3))
        v[c] = keep(sponge * (v[c] + h / rho * div))

    # stage 2: stresses and memory variables from the new velocities
    e = {(c, j): diff(v[c], AXIS[j], 0 if c == j else 1)
         for c in "xyz" for j in "xyz"}
    tr = e["x", "x"] + e["y", "y"] + e["z", "z"]
    for c in "xyz":
        cc = c + c
        el = lam * tr + 2.0 * mu * e[c, c]
        r[cc] = keep(qp * (r[cc] + el))
        s[cc] = keep(free_surface(
            cc, sponge * (s[cc] + h * (el - r[cc])), z, last))
    for a, b in ("xy", "xz", "yz"):
        s[a + b] = keep(free_surface(
            a + b, sponge * (s[a + b] + h * mu * (e[a, b] + e[b, a])),
            z, last))

    out = {"vel_" + c: [a] for c, a in v.items()}
    out.update({"stress_" + c: [a] for c, a in s.items()})
    out.update({"mem_" + c: [a] for c, a in r.items()})
    return out


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """Every array read once per fused group and every stepped field
    written after it, over the group."""
    return (READ_ARRAYS + LEVELS) * itemsize / wf_steps
