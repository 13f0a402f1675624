"""The Himeno benchmark's pressure solver ('himeno').

Ryutaro Himeno's (RIKEN) ``himenoBMTxps.c``, function ``jacobi``: the
pressure Poisson solve of an incompressible Navier-Stokes code by point
Jacobi, the HPC field's memory-bandwidth yardstick.  A 19-point stencil
(six axis neighbours, twelve in-plane diagonals of the mixed
derivatives) whose every term is multiplied by a coefficient array read
at the point --

    s0 = a0·p(i+1,j,k) + a1·p(i,j+1,k) + a2·p(i,j,k+1)
       + b0·(p(i+1,j+1,k) − p(i+1,j−1,k) − p(i−1,j+1,k) + p(i−1,j−1,k))
       + b1·(p(i,j+1,k+1) − p(i,j−1,k+1) − p(i,j+1,k−1) + p(i,j−1,k−1))
       + b2·(p(i+1,j,k+1) − p(i−1,j,k+1) − p(i+1,j,k−1) + p(i−1,j,k−1))
       + c0·p(i−1,j,k) + c1·p(i,j−1,k) + c2·p(i,j,k−1) + wrk1
    ss = (s0·a3 − p)·bnd
    p_new = p + omega·ss

-- thirteen arrays read and one written a point and sweep (56 B for 34
flops), ``i, j, k`` → ``x, y, z`` (``k`` is fastest, as ``z`` is here).

Departures from the published loop: the outermost layer of points is
held fixed by ``bnd`` = 0 there (the published code sets ``bnd`` = 1 and
shortens the loops: the same points get the same values); the
coefficient arrays ``a[4] b[3] c[3]`` are ten 3-D vars; ``p`` steps in
the framework's ring where the published code writes ``wrk2`` and copies
back; ``gosa = Σ ss²`` is not accumulated (the DSL has no reduction).
"""

from __future__ import annotations

from yask_tpu.compiler.solution_base import (
    register_solution,
    yc_solution_base,
)


@register_solution
class HimenoStencil(yc_solution_base):
    """'himeno': one Jacobi sweep of the 19-point pressure solve a step."""

    def __init__(self, name: str = "himeno"):
        super().__init__(name)

    def define(self):
        t = self.new_step_index("t")
        x = self.new_domain_index("x")
        y = self.new_domain_index("y")
        z = self.new_domain_index("z")
        p = self.new_var("p", [t, x, y, z])
        a0, a1, a2, a3, b0, b1, b2, c0, c1, c2, wrk1, bnd = (
            self.new_var(n, [x, y, z])(x, y, z)
            for n in ("a0", "a1", "a2", "a3", "b0", "b1", "b2",
                      "c0", "c1", "c2", "wrk1", "bnd"))
        omega = self.new_var("omega", [])

        def at(i, j, k):
            return p(t, x + i, y + j, z + k)

        s0 = (a0 * at(1, 0, 0) + a1 * at(0, 1, 0) + a2 * at(0, 0, 1)
              + b0 * (at(1, 1, 0) - at(1, -1, 0)
                      - at(-1, 1, 0) + at(-1, -1, 0))
              + b1 * (at(0, 1, 1) - at(0, -1, 1)
                      - at(0, 1, -1) + at(0, -1, -1))
              + b2 * (at(1, 0, 1) - at(-1, 0, 1)
                      - at(1, 0, -1) + at(-1, 0, -1))
              + c0 * at(-1, 0, 0) + c1 * at(0, -1, 0) + c2 * at(0, 0, -1)
              + wrk1)
        ss = (s0 * a3 - at(0, 0, 0)) * bnd
        p(t + 1, x, y, z).EQUALS(at(0, 0, 0) + omega() * ss)
