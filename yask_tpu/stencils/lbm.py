"""A lattice-Boltzmann flow solver ('lbm_d3q19').

Thomas Pohl's ``lbm.c`` as SPEC CPU2006 ``470.lbm`` / CPU2017
``519.lbm_r`` run it, function ``LBM_performStreamCollide``: the D3Q19
lattice with the BGK collision, a cell's nineteen populations named by
where they go (``C``, ``N S E W T B``, ``NE NW SE SW``, ``NT NB ST SB``,
``ET EB WT WB``; ``E`` = +x, ``N`` = +y, ``T`` = +z), three kinds of
cell told apart by a flag word --

    if OBSTACLE:  DST_C = SRC_C, DST_S = SRC_N, DST_N = SRC_S, ...
                  (every population sent back the way it came)
    rho = SRC_C + SRC_N + ... + SRC_WB
    ux  = (SRC_E − SRC_W + SRC_NE − SRC_NW + SRC_SE − SRC_SW
           + SRC_ET + SRC_EB − SRC_WT − SRC_WB) / rho;   uy, uz alike
    if ACCEL:     ux = 0.005, uy = 0.002, uz = 0
    u2  = 1.5 (ux² + uy² + uz²)
    DST_C  = (1 − OMEGA) SRC_C  + 1/3  OMEGA rho (1 − u2)
    DST_N  = (1 − OMEGA) SRC_N  + 1/18 OMEGA rho (1 + uy (4.5 uy + 3) − u2)
    DST_NE = (1 − OMEGA) SRC_NE + 1/36 OMEGA rho (1 + (ux + uy)
                                          (4.5 (ux + uy) + 3) − u2)
    ... (nineteen of them; OMEGA 1.95)

-- where ``SRC_X`` is the cell's own entry and ``DST_X`` the entry of
the neighbour the population goes to: 19 values read, 19 written, a
cell and step.  In every direction ``i`` with lattice vector ``c_i`` and
weight ``w_i`` (1/3, 1/18, 1/36) that is

    feq_i = w_i rho (1 + 3 c_i·u + 4.5 (c_i·u)² − 1.5 u·u)
    f_i   ← (1 − omega) f_i + omega feq_i

Here a step is, at every point of the domain,

    g_i   = f_i(t, x − c_i)                                   (the pull)
    rho   = Σ g_i;  u = Σ c_i g_i / rho
    u     = accel (u_lid_x, u_lid_y, 0) + (1 − accel) u
    f_i(t+1, x) = fluid ((1 − omega) g_i + omega feq_i)
                  + (1 − fluid) g_opp(i)

Departures from the published loop: populations are PULLED from the
neighbours and the arrays hold what each cell SENDS, where the
published loop pushes and its array holds what each cell received (the
same numbers one streaming shift later: what a cell sends in direction
``i`` at ``t`` is what the published array has at ``x + c_i``); a
structure of arrays, nineteen stepped vars and two float masks
``fluid`` (0 in an ``OBSTACLE`` cell, else 1) and ``accel`` (1 in an
``ACCEL`` cell), for the published twenty doubles a cell of which one is
the flag word, and the kinds of cell blended by the masks where the
published loop branches; float32 for double; ``z`` is the fastest dim
where the published ``x`` is; ``omega`` and the lid's velocity are 0-dim
vars where the published code compiles them in.  ``rho`` is divided by
as ``rho + (1 − fluid)``: ``rho`` itself in a fluid cell, and never 0/0
in an obstacle cell whose masked-out collision would else poison the
blend.  ``f0``, the rest population, is read at the point alone, so the
framework keeps it in a ring of one slot (written where it was read);
the eighteen that move are rings of two.
"""

from __future__ import annotations

from yask_tpu.compiler.solution_base import (
    register_solution,
    yc_solution_base,
)

#: direction -> lattice vector (x, y, z), in ``lbm.c``'s own order
DIRECTIONS = (
    ("C", (0, 0, 0)),
    ("N", (0, 1, 0)), ("S", (0, -1, 0)),
    ("E", (1, 0, 0)), ("W", (-1, 0, 0)),
    ("T", (0, 0, 1)), ("B", (0, 0, -1)),
    ("NE", (1, 1, 0)), ("NW", (-1, 1, 0)),
    ("SE", (1, -1, 0)), ("SW", (-1, -1, 0)),
    ("NT", (0, 1, 1)), ("NB", (0, 1, -1)),
    ("ST", (0, -1, 1)), ("SB", (0, -1, -1)),
    ("ET", (1, 0, 1)), ("EB", (1, 0, -1)),
    ("WT", (-1, 0, 1)), ("WB", (-1, 0, -1)),
)
#: lattice weight by the number of non-zero components of ``c_i``
WEIGHTS = (1.0 / 3.0, 1.0 / 18.0, 1.0 / 36.0)


@register_solution
class LbmD3Q19Stencil(yc_solution_base):
    """'lbm_d3q19': one stream-and-collide step of all nineteen
    populations ``f0 .. f18`` (``lbm.c``'s order) a step."""

    def __init__(self, name: str = "lbm_d3q19"):
        super().__init__(name)

    def define(self):
        t = self.new_step_index("t")
        x = self.new_domain_index("x")
        y = self.new_domain_index("y")
        z = self.new_domain_index("z")
        f = [self.new_var(f"f{i}", [t, x, y, z])
             for i in range(len(DIRECTIONS))]
        fluid = self.new_var("fluid", [x, y, z])(x, y, z)
        accel = self.new_var("accel", [x, y, z])(x, y, z)
        omega = self.new_var("omega", [])()
        lid = (self.new_var("u_lid_x", [])(), self.new_var("u_lid_y", [])())

        vec = [c for _n, c in DIRECTIONS]
        opp = [vec.index(tuple(-a for a in c)) for c in vec]
        # what arrives at the point in direction i
        g = [f[i](t, x - c[0], y - c[1], z - c[2])
             for i, c in enumerate(vec)]

        def along(ax, sign):
            terms = [g[i] for i, c in enumerate(vec) if c[ax] == sign]
            return sum(terms[1:], terms[0])

        # the expression builder flattens an n-ary node into a taker
        # of its own kind (a sum into a sum, a product into a product),
        # and a flattened operand is evaluated again by every taker: so
        # what the nineteen equations share is a sum where products
        # take it and a product where sums do (``rho`` takes ``solid``
        # into its one sum and is only ever multiplied and divided by)
        solid = 1.0 - fluid
        rho = sum(g[1:], g[0]) + solid
        still = 1.0 - accel
        u = [(along(ax, 1) - along(ax, -1)) / rho for ax in range(3)]
        u = [accel * lid[0] + still * u[0],
             accel * lid[1] + still * u[1],
             still * u[2]]
        rest = 1.0 - 1.5 * (u[0] * u[0] + u[1] * u[1] + u[2] * u[2])
        rest_w = [w * rest for w in WEIGHTS]
        keep = 1.0 - omega

        def poly(i):
            """``feq_i / rho``: ``w (1 − 1.5 u·u) + (c·u) (4.5 w (c·u)
            ± 3 w)``, the pair ``i``, ``opp(i)`` on one ``c·u`` (that of
            the two whose first non-zero component is positive)."""
            c = vec[i]
            order = sum(1 for a in c if a)   # 0 rest, 1 axis, 2 diagonal
            if not order:
                return rest_w[0]
            w = WEIGHTS[order]
            lead = next(a for a in c if a)
            cu = None
            for ax in range(3):
                if c[ax]:
                    cu = u[ax] if cu is None else (
                        cu + u[ax] if c[ax] == lead else cu - u[ax])
            quad = cu * (4.5 * w)
            inner = quad + 3.0 * w if lead > 0 else quad - 3.0 * w
            return rest_w[order] + cu * inner

        for i in range(len(vec)):
            collided = keep * g[i] + omega * (rho * poly(i))
            f[i](t + 1, x, y, z).EQUALS(
                fluid * collided + solid * g[opp[i]])
