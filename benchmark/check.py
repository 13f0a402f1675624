"""How ``correct`` is decided: seeded probe blocks of the field the
timed path produced, against the plain float64 reference advanced from
the same seeded initial state.

The initial state is a law of the seed and of interior coordinates
alone (:func:`seq_box`), so the reference rebuilds any box of it
without reading anything the program made; a block of B^3 after n steps
needs its (B + 2 r n)^3 neighbourhood, cut at the domain's edge where
the outside reads as zero.  Imports nothing of the program.
"""

import numpy as np


def draw_fill(seed: int, domain, ranks, block: int) -> dict:
    """Everything a run draws from ``--seed``: the dense field's scale,
    the seam probe's origin and the point source inside it."""
    rng = np.random.default_rng(int(seed))
    scale = float(np.float32(0.03 + 0.04 * rng.random()))
    seam = []
    for size, nr in zip(domain, ranks):
        every = size // nr if nr > 1 else min(64, size // 2)
        k = int(rng.integers(1, max(2, size // every)))
        seam.append(k * every - block // 2)
    source = [lo + int(rng.integers(0, block)) for lo in seam]
    return {"scale": scale, "seam": seam, "source": source,
            "amplitude": 1.0}


def probes(domain, fill, block: int) -> dict:
    """Probe name -> origin.  ``corner``/``far`` touch the domain's
    edge in every dimension; ``seam`` straddles a multiple of 64 (a
    Pallas tile boundary of any power-of-two block) in every dimension
    and, where x is split over chips, a shard boundary."""
    return {"corner": [0, 0, 0],
            "far": [s - block for s in domain],
            "seam": list(fill["seam"])}


def seq_box(domain, lo, hi, scale: float, slot: int, dtype=np.float32):
    """Rows ``[lo, hi)`` of the dense initial field in ring slot
    ``slot``: element ``i`` of the domain in C order is
    ``(i % 17 + 1) * scale * (slot + 1)``, worked in float64 and cast
    (the law ``set_elements_in_seq`` documents)."""
    ix = [np.arange(a, b, dtype=np.int64) for a, b in zip(lo, hi)]
    flat = ((ix[0][:, None, None] * domain[1] + ix[1][None, :, None])
            * domain[2] + ix[2][None, None, :])
    return ((flat % 17 + 1.0) * (scale * (slot + 1))).astype(dtype)


def initial_levels(stencil, domain, lo, hi, fill, dtype=np.float32):
    """The seeded state in box ``[lo, hi)``, oldest level first, as
    the device holds it (float32), with the point source on the newest
    level."""
    levels = []
    for slot in range(stencil.SLOTS - stencil.LEVELS, stencil.SLOTS):
        levels.append(seq_box(domain, lo, hi, fill["scale"], slot, dtype))
    src = fill["source"]
    if all(a <= s < b for s, a, b in zip(src, lo, hi)):
        levels[-1][tuple(s - a for s, a in zip(src, lo))] = \
            fill["amplitude"]
    return levels


def reference_block(stencil, cfg, domain, origin, block, steps, fill,
                    rounder=None):
    """The reference's ``block``^3 at ``origin`` after ``steps`` steps
    from the seeded state.  Each step the box shrinks to what the
    remaining steps still need, so the work is a cone, not a slab."""
    r = int(cfg["radius"])

    def box(left):
        lo = [max(0, o - r * left) for o in origin]
        hi = [min(s, o + block + r * left)
              for o, s in zip(origin, domain)]
        return lo, hi

    lo, hi = box(steps)
    levels = [a.astype(np.float64) for a in initial_levels(
        stencil, domain, lo, hi, fill)]
    if rounder is not None:
        levels = [rounder(a) for a in levels]
    consts = dict(cfg.get("consts", {}))
    for done in range(1, steps + 1):
        levels = stencil.step(levels, consts, r, rounder=rounder)
        nlo, nhi = box(steps - done)
        cut = tuple(slice(a - b, a - b + (c - a))
                    for a, b, c in zip(nlo, lo, nhi))
        levels = [a[cut] for a in levels]
        lo, hi = nlo, nhi
    return levels[-1]


def bf16_round(a):
    """The control's rounding: every stored value to bfloat16."""
    import ml_dtypes
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def block_error(got, want) -> float:
    """The one number compared per probe: the largest gap, as a share
    of the reference block's largest magnitude; infinite where the
    device's block holds a non-finite value."""
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max()
                 / max(float(np.abs(want).max()), 1e-30))


def compare(stencil, cfg, domain, block, steps, fill, device_blocks,
            limit: float, say, control: bool = False):
    """Hold every probed block to the reference.  Prints each number
    beside its limit; with ``control`` the device's blocks are replaced
    by the reference computed in bfloat16 (which must then fail)."""
    ok = True
    for name, origin in probes(domain, fill, block).items():
        want = reference_block(stencil, cfg, domain, origin, block,
                               steps, fill)
        if control:
            got = reference_block(stencil, cfg, domain, origin, block,
                                  steps, fill, rounder=bf16_round)
        else:
            got = device_blocks[name]
        err = block_error(got, want)
        say(f"check {'control(bf16) ' if control else ''}{name} at "
            f"{origin} after {steps} steps: error {err:.3e} "
            f"limit {limit:.3e} (max |ref| "
            f"{float(np.abs(want).max()):.4g})")
        ok = ok and err <= limit
    return ok


def alive(blocks) -> bool:
    """The final field, where probed, is finite and not all zero."""
    arrs = [np.asarray(b) for b in blocks.values()]
    return (all(np.isfinite(a).all() for a in arrs)
            and any(np.any(a != 0) for a in arrs))
