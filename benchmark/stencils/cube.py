"""Plain float64 reference of upstream's ``cube`` at any radius
(intel/yask ``src/stencils/SimpleStencils.cpp``; radius 1 is the
27-point kernel of Datta et al., SC'08):

    A(t+1) = mean of A(t) over the (2r+1)^3 box around the point

Imports nothing of the program.  Outside the domain reads as zero.
"""

import numpy as np

STATE_VAR = "A"
LEVELS = 1        # a step reads A(t) only
SLOTS = 2         # ring slots the benchmark seeds (oldest first)
CONSTS = ()
READ_ARRAYS = 1


def step(levels, consts, radius, dtype=np.float64, rounder=None):
    """One time step on a box whose outside is zero: ``[A(t)]`` in,
    ``[A(t+1)]`` out.  The box sum is separable: three 1-D passes."""
    cur = levels[-1]
    r = radius
    out = cur
    for ax in range(3):
        width = [(0, 0)] * 3
        width[ax] = (r, r)
        pad = np.pad(out, width)
        n = out.shape[ax]
        acc = np.zeros_like(out)
        for k in range(2 * r + 1):
            acc = acc + np.take(pad, range(k, k + n), axis=ax)
        out = acc
    new = out / dtype((2 * r + 1) ** 3)
    if rounder is not None:
        new = rounder(new)
    return [new.astype(dtype, copy=False)]


def need_bytes_per_point_step(wf_steps: int, itemsize: int = 4) -> float:
    """One array read per fused group, one time level written after
    it (the ring's second slot is only the write target)."""
    return (READ_ARRAYS + LEVELS) * itemsize / wf_steps
