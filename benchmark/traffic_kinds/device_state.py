"""The seeded state, built on the device in one jitted call per ring
slot, and small boxes of it read back without pulling whole arrays.

The program's public fills (``set_elements_in_seq``, ``set_element``,
``set_elements_in_slice``) go through the host for a solution that is
not sharded: every one pulls the whole padded array to the host and
pushes it back, 144 s of set-up at 768^3 and 314 s at 1024^3 on a v5e
host (PR 23); ``get_elements_in_slice`` pulls the whole array for an
8^3 box.  So, for those modes, this one file reaches past the public
API: it installs the ring slots itself -- the same law
(:func:`check.seq_box`), laid out by the solution's own geometry
(``ctx._program.geoms``), ghost cells zero -- and slices a box on the
device before it crosses to the host.  A public device-side fill and
slice in the program would retire it (PERF.md, Open questions).
"""

import numpy as np


def install(ctx, name: str, domain, fill, slots: int,
            const=None) -> None:
    """Ring slots of var ``name`` (oldest first) <- the seeded law,
    with the point source on the newest; or, with ``const``, that one
    value over the interior of a read-only array."""
    import jax
    import jax.numpy as jnp

    geom = ctx._program.geoms[name]
    ring = list(ctx._state[name])
    if len(ring) != slots or any(k != "domain" for _n, k in geom.axes):
        raise SystemExit(
            f"benchmark: var '{name}' is not a ring of {slots} arrays "
            f"over domain dims only; device_state does not know it")
    shape = tuple(int(n) for n in ring[0].shape)
    starts = [int(geom.origin[dn]) for dn, _k in geom.axes]
    sizes = [int(n) for n in domain]
    strides = [(sizes[1] * sizes[2]) % 17, sizes[2] % 17, 1]
    src = [int(a) for a in fill["source"]]

    def build(table, amplitude):
        idx = [jax.lax.broadcasted_iota(jnp.int32, shape, ax) - starts[ax]
               for ax in range(3)]
        m = sum((i % 17) * s for i, s in zip(idx, strides)) % 17
        val = jnp.zeros(shape, table.dtype)
        for k in range(17):
            val = jnp.where(m == k, table[k], val)
        inside = jnp.ones(shape, bool)
        at_src = jnp.ones(shape, bool)
        for i, n, s in zip(idx, sizes, src):
            inside &= (i >= 0) & (i < n)
            at_src &= i == s
        val = jnp.where(at_src, amplitude, val)
        return jnp.where(inside, val, 0)

    built = jax.jit(build)
    ctx._state[name] = ring
    for slot in range(slots):
        dtype = ring[slot].dtype
        table = ((np.arange(17, dtype=np.float64) + 1.0)
                 * (fill["scale"] * (slot + 1))).astype(dtype)
        if const is not None:
            table = np.full(17, const, dtype)
        # the source sits on the newest level only; elsewhere the
        # "source" writes the law's own value back
        newest = slot == slots - 1 and const is None
        here = table[sum(s * st for s, st in zip(src, strides)) % 17]
        ring[slot] = None                   # free before the new one
        ring[slot] = built(table, np.asarray(
            fill["amplitude"] if newest else here, table.dtype))
    jax.block_until_ready(ring)


def read_box(ctx, name: str, lo, hi):
    """Rows ``[lo, hi)`` of the newest ring slot of ``name`` (rings
    run oldest to newest), sliced on the device."""
    geom = ctx._program.geoms[name]
    cut = tuple(slice(int(geom.origin[dn]) + a, int(geom.origin[dn]) + b)
                for (dn, _k), a, b in zip(geom.axes, lo, hi))
    return np.asarray(ctx._state[name][-1][cut])
