"""Plain float64 reference of ``lbm_d3q19``: one stream-and-collide step
of a D3Q19 lattice-Boltzmann flow solver with the BGK collision, as
Thomas Pohl's ``lbm.c`` (SPEC CPU2006 ``470.lbm`` / CPU2017
``519.lbm_r``, ``LBM_performStreamCollide``) does it in a lid-driven
cavity (as recalled; no source, no network here).  Nineteen
populations a cell, ``f_i`` moving along the lattice vector ``c_i`` with
weight ``w_i``; ``opp(i)`` is the direction with ``c = -c_i``:

    g_i   = f_i(t, x - c_i)                  what arrives at the cell
    rho   = sum_i g_i
    u     = (sum_i c_i g_i) / rho
    u     = accel (u_lid_x, u_lid_y, 0) + (1 - accel) u
    feq_i = w_i rho (1 + 3 (c_i.u) + 4.5 (c_i.u)^2 - 1.5 (u.u))
    f_i(t+1, x) = fluid ((1 - omega) g_i + omega feq_i)
                  + (1 - fluid) g_opp(i)

The arrays hold what each cell SENDS: a fluid cell collides what it
pulled, an ``OBSTACLE`` cell (``fluid`` = 0) sends it back reversed, an
``ACCEL`` cell (``accel`` = 1) collides towards the lid's velocity at
its own density.  The published loop pushes instead and keeps what each
cell received: the same numbers one streaming shift later.  Outside the
domain every population reads as zero.  An obstacle cell divides by 1
instead of by its ``rho``: its collision is masked out, and must not be
a 0/0 on the way.

Imports nothing of the program.  The nineteen directions are a table
written out, so that a direction misplaced here cannot hide in a loop
that the program shares.
"""

import numpy as np

#: direction -> (name in ``lbm.c``, c_x, c_y, c_z, weight, opposite)
TABLE = (
    ("C",   0,  0,  0, 1.0 / 3.0,   0),
    ("N",   0,  1,  0, 1.0 / 18.0,  2),
    ("S",   0, -1,  0, 1.0 / 18.0,  1),
    ("E",   1,  0,  0, 1.0 / 18.0,  4),
    ("W",  -1,  0,  0, 1.0 / 18.0,  3),
    ("T",   0,  0,  1, 1.0 / 18.0,  6),
    ("B",   0,  0, -1, 1.0 / 18.0,  5),
    ("NE",  1,  1,  0, 1.0 / 36.0, 10),
    ("NW", -1,  1,  0, 1.0 / 36.0,  9),
    ("SE",  1, -1,  0, 1.0 / 36.0,  8),
    ("SW", -1, -1,  0, 1.0 / 36.0,  7),
    ("NT",  0,  1,  1, 1.0 / 36.0, 14),
    ("NB",  0,  1, -1, 1.0 / 36.0, 13),
    ("ST",  0, -1,  1, 1.0 / 36.0, 12),
    ("SB",  0, -1, -1, 1.0 / 36.0, 11),
    ("ET",  1,  0,  1, 1.0 / 36.0, 18),
    ("EB",  1,  0, -1, 1.0 / 36.0, 17),
    ("WT", -1,  0,  1, 1.0 / 36.0, 16),
    ("WB", -1,  0, -1, 1.0 / 36.0, 15),
)

#: what a run must know to seed, advance and read this stencil's state
#: (``README.md``, "A stencil's file"): nineteen fields, every one
#: probed.  The eighteen that move are rings of two slots of which a
#: step reads the newest; the rest population ``f0``, which a step reads
#: at the point alone, the program keeps in a ring of ONE (it is
#: written where it was read).  The seeding law multiplies by ``slot +
#: 1``, so the weights 2, 1/6 and 1/12 seed the newest levels in the
#: lattice weights' ratio 12 : 2 : 1: every cell starts at rest and in
#: equilibrium (``f_i = w_i rho``) at a density ``6 scale (i % 17 +
#: 1)`` that varies 1 : 17 from cell to cell.  The point source goes on
#: the rest population; two read-only masks vary with position; three
#: 0-dim scalars; a step reaches one point
FIELDS = {"f0": {"slots": 1, "weight": 2.0}}
FIELDS.update({f"f{i}": {"slots": 2, "levels": 1, "weight": 3.0 * row[4]}
               for i, row in enumerate(TABLE) if i})
SOURCE = "f0"
ARRAYS = ("fluid", "accel")
SCALARS = ("omega", "u_lid_x", "u_lid_y")
REACH = 1
READ_ARRAYS = 21  # nineteen populations and the two masks
LEVELS = 19       # every population written after a fused group


def pulled(level, cx: int, cy: int, cz: int):
    """``level`` read at ``x - c``: zero where that lies outside."""
    nx, ny, nz = level.shape
    pad = np.pad(level, 1)
    return pad[1 - cx:1 - cx + nx, 1 - cy:1 - cy + ny, 1 - cz:1 - cz + nz]


def step(state, coeffs, radius, lo=None, domain=None, rounder=None):
    """One step on a box whose outside is zero: every population's
    newest level in, the next out.  ``coeffs`` are the box's own rows
    of the two masks (a float, or the float32 array the device holds)
    and the three floats, so nothing here depends on where the box
    lies.  ``rounder`` (the control's) rounds every stored value to a
    lower precision."""
    keep = rounder if rounder is not None else (lambda a: a)
    fluid = np.asarray(coeffs["fluid"], dtype=np.float64)
    accel = np.asarray(coeffs["accel"], dtype=np.float64)
    omega = float(coeffs["omega"])
    lid = (float(coeffs["u_lid_x"]), float(coeffs["u_lid_y"]), 0.0)

    g = [pulled(state[f"f{i}"][-1], cx, cy, cz)
         for i, (_n, cx, cy, cz, _w, _o) in enumerate(TABLE)]
    rho = sum(g)
    over = np.where(fluid == 0.0, 1.0, rho)
    u = []
    for ax in range(3):
        moved = sum(row[1 + ax] * g[i] for i, row in enumerate(TABLE))
        u.append(accel * lid[ax] + (1.0 - accel) * (moved / over))
    uu = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]

    out = {}
    for i, (_n, cx, cy, cz, w, o) in enumerate(TABLE):
        cu = cx * u[0] + cy * u[1] + cz * u[2]
        feq = w * rho * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * uu)
        new = (fluid * ((1.0 - omega) * g[i] + omega * feq)
               + (1.0 - fluid) * g[o])
        out[f"f{i}"] = [keep(new)]
    return out


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """Bytes the algorithm must move per point and step when
    ``wf_steps`` steps are fused: nineteen populations and two masks
    read once a group and nineteen populations written after it (160 B
    at ``wf_steps`` 1)."""
    return (READ_ARRAYS + LEVELS) * itemsize / wf_steps
