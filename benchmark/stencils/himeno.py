"""Plain float64 reference of the Himeno benchmark's ``jacobi`` sweep
(Ryutaro Himeno, RIKEN; ``himenoBMTxps.c``): point Jacobi on the
pressure Poisson equation of an incompressible Navier-Stokes code, a
19-point stencil whose every term is multiplied by a coefficient array
read at the point (as recalled; no source, no network here):

    s0 = a0 p(i+1,j,k) + a1 p(i,j+1,k) + a2 p(i,j,k+1)
       + b0 (p(i+1,j+1,k) - p(i+1,j-1,k) - p(i-1,j+1,k) + p(i-1,j-1,k))
       + b1 (p(i,j+1,k+1) - p(i,j-1,k+1) - p(i,j+1,k-1) + p(i,j-1,k-1))
       + b2 (p(i+1,j,k+1) - p(i-1,j,k+1) - p(i+1,j,k-1) + p(i-1,j,k-1))
       + c0 p(i-1,j,k) + c1 p(i,j-1,k) + c2 p(i,j,k-1) + wrk1
    ss = (s0 a3 - p) bnd
    p_new = p + omega ss

``i, j, k`` are x, y, z here (``k`` fastest, as z is).  Every point of
the box is updated; the published loop leaves the outermost layer of
the grid alone, which the configuration states as ``bnd`` = 0 there.
Outside the domain ``p`` reads as zero.  ``gosa`` (the sum of ``ss``
squared) is not accumulated: ``p`` is what the loop gives.

Imports nothing of the program.  The nineteen terms are written out one
by one, so that a term misplaced here cannot hide behind a loop.
"""

import numpy as np

#: what a run must know to seed, advance and read this stencil's state
#: (``README.md``, "A stencil's file"): one field in a ring of two slots
#: of which a sweep reads the newest only (the published ``p`` and its
#: write target ``wrk2``), seeded at the full scale, carrying the point
#: source, probed; twelve read-only arrays that vary with position; the
#: relaxation factor as a 0-dim scalar; a sweep reaches one point
FIELDS = {"p": {"slots": 2, "levels": 1}}
ARRAYS = ("a0", "a1", "a2", "a3", "b0", "b1", "b2", "c0", "c1", "c2",
          "wrk1", "bnd")
SCALARS = ("omega",)
REACH = 1
READ_ARRAYS = 13  # p and the twelve arrays at the point
LEVELS = 1        # one time level written after a fused group


def step(state, coeffs, radius, lo=None, domain=None, rounder=None):
    """One sweep on a box whose outside is zero: ``[p(t)]`` in,
    ``[p(t+1)]`` out.  ``coeffs`` are the box's own rows of the twelve
    arrays (a float, or the float32 array the device holds) and the
    float ``omega``, so nothing here depends on where the box lies.
    ``rounder`` (the control's) rounds every stored value to a lower
    precision."""
    cur = state["p"][-1]
    c = {name: np.asarray(coeffs[name], dtype=np.float64)
         for name in ARRAYS}
    pad = np.pad(cur, 1)
    nx, ny, nz = cur.shape

    def at(i, j, k):
        return pad[1 + i:1 + i + nx, 1 + j:1 + j + ny, 1 + k:1 + k + nz]

    s0 = (c["a0"] * at(1, 0, 0)
          + c["a1"] * at(0, 1, 0)
          + c["a2"] * at(0, 0, 1)
          + c["b0"] * (at(1, 1, 0) - at(1, -1, 0)
                       - at(-1, 1, 0) + at(-1, -1, 0))
          + c["b1"] * (at(0, 1, 1) - at(0, -1, 1)
                       - at(0, 1, -1) + at(0, -1, -1))
          + c["b2"] * (at(1, 0, 1) - at(-1, 0, 1)
                       - at(1, 0, -1) + at(-1, 0, -1))
          + c["c0"] * at(-1, 0, 0)
          + c["c1"] * at(0, -1, 0)
          + c["c2"] * at(0, 0, -1)
          + c["wrk1"])
    ss = (s0 * c["a3"] - cur) * c["bnd"]
    new = cur + float(coeffs["omega"]) * ss
    if rounder is not None:
        new = rounder(new)
    return {"p": [new]}


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """Bytes the algorithm must move per point and sweep when
    ``wf_steps`` sweeps are fused: thirteen arrays read once a group
    and one time level written after it (56 B at ``wf_steps`` 1)."""
    return (READ_ARRAYS + LEVELS) * itemsize / wf_steps
