"""What no input DMA of a grid step copies, and what a launch finds in
a ring slot it writes its output onto, made NaN -- in the tests, not in
the program.

Since PR 45 the input DMA of a ``(var, slot)`` copies the window the
kernel's stages read of it and the rest of its VMEM buffer holds
whatever was there: on the chip the tile of two grid steps before,
which looks like the field it is not.  Interpret mode hands a kernel
NaN buffers once, at its first grid step; later steps find the earlier
ones' data.  :func:`poison_unfetched_rows` wraps every kernel built
while it is in force so that each grid step starts from NaN in every
input tile buffer its own DMAs then fill: a stage that reads a row
outside a window reads NaN, at every grid step, and the every-point
comparisons see it.

How it knows the buffers (``build_pallas_chunk``'s ``scratch_shapes``):
the input tiles come first, one a DMA'd ``(var, slot)``, and the input
DMAs' semaphores, last but one, are ``(2, tiles)`` where the fetch is
double-buffered across grid steps and ``(tiles,)`` where it is not.
Pipelined, grid step ``li`` starts the copies of step ``li + 1`` into
parity ``(li + 1) % 2`` (step 0 also its own, into parity 0, which is
the interpreter's NaN still): that parity is poisoned at the top of
step ``li``, when nothing reads it any more -- step ``li - 1``'s output
copies have landed (interpret mode copies at ``start``).
"""

import math


def poison_unfetched_rows(monkeypatch):
    """From here to the end of the test, every ``pl.pallas_call`` runs
    its kernel with the input tile buffers poisoned as above.  Returns
    a list that collects, per kernel built, how many buffers it
    poisons."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    poisoned = []

    def pallas_call(kernel, **kw):
        grid = kw["grid"]
        first = len(kw["in_specs"]) + len(kw["out_shape"])
        sems = kw["scratch_shapes"][-2]
        piped, tiles = len(sems.shape) == 2, sems.shape[-1]
        poisoned.append(tiles)

        def nan_first(*refs):
            li = 0
            for i, n in enumerate(grid):
                li = li * n + pl.program_id(i)
            for ref in refs[first:first + tiles]:
                if piped:
                    ref[pl.ds((li + 1) % 2, 1)] = jnp.full(
                        (1,) + ref.shape[1:], math.nan, ref.dtype)
                else:
                    ref[...] = jnp.full(ref.shape, math.nan, ref.dtype)
            kernel(*refs)

        return real(nan_first, **kw)

    monkeypatch.setattr(pl, "pallas_call", pallas_call)
    return poisoned


def poison_reused_slots(monkeypatch):
    """From here to the end of the test, every chunk built with
    ``reuse_evicted`` finds NaN in each ring slot it writes a new level
    onto (``chunk.tiling["reused"]``), everywhere: interior, ghost
    bands, lane pads.  The launch's output IS that array (the slot's
    operand is aliased to it), so a cell that no output window writes
    and nothing re-zeroes or refreshes afterwards -- a ghost band two
    groups old on the chip -- stays NaN and reaches the comparison.
    Returns a list that collects, per chunk built, how many slots it
    poisons."""
    import jax.numpy as jnp
    from yask_tpu.ops import pallas_stencil

    real = pallas_stencil.build_pallas_chunk
    poisoned = []

    def build_pallas_chunk(*args, **kw):
        built = real(*args, **kw)
        if not isinstance(built, tuple):        # plan_only / sizer
            return built
        chunk, tile_bytes = built
        reused = [slot.split("/") for slot in chunk.tiling["reused"]]
        poisoned.append(len(reused))
        if not reused:
            return built
        written = chunk.written

        def written_poisoned(state, t0, offsets=None, base=None):
            state = dict(state)
            for name, j in reused:
                ring = list(state[name])
                ring[int(j)] = jnp.full_like(ring[int(j)], math.nan)
                state[name] = ring
            return written(state, t0, offsets, base)

        def chunk_poisoned(state, t0, offsets=None):
            return chunk.merge(state, written_poisoned(state, t0, offsets))

        chunk_poisoned.written = written_poisoned
        chunk_poisoned.merge = chunk.merge
        chunk_poisoned.tiling = chunk.tiling
        return chunk_poisoned, tile_bytes

    monkeypatch.setattr(pallas_stencil, "build_pallas_chunk",
                        build_pallas_chunk)
    return poisoned


def poison_unrefreshed_ghosts(monkeypatch):
    """From here to the end of the test, every exchange round of a
    shard program (``shard_step.exchange_many``) leaves NaN in each
    ghost row it did NOT refresh: of every array the round was handed,
    in every sharded dim, the rows of both pad bands beyond the width
    that side was exchanged with -- the whole band where the side is
    not sent at all.  The Pallas shard program hands a round every
    array it could have refreshed, widths or none, so a var sent one
    way, or not at all, has the ghost rows nothing fills poisoned
    before each step: an equation that reads one reads NaN, and the
    every-point comparisons see it.  (Rows inside an exchanged width
    hold what the neighbour sent, or the zeros of a physical boundary.)
    Returns a list that collects, per round, how many bands it
    poisoned."""
    import jax.numpy as jnp
    from yask_tpu.parallel import shard_step

    real = shard_step.exchange_many
    poisoned = []

    def exchange_many(items, nr, local_sizes, plan=None):
        out, bands = [], 0
        for a, (_a, g, w) in zip(real(items, nr, local_sizes, plan), items):
            for d in g.domain_dims:
                if nr.get(d, 1) <= 1:
                    continue
                ax, o = g.axis_of(d), g.origin[d]
                left, right = w.get(d, (0, 0))
                for lo, hi in ((0, o - left),
                               (o + local_sizes[d] + right, a.shape[ax])):
                    if hi > lo:
                        band = tuple(slice(lo, hi) if i == ax
                                     else slice(None)
                                     for i in range(a.ndim))
                        a = a.at[band].set(math.nan)
                        bands += 1
            out.append(a)
        poisoned.append(bands)
        return out

    monkeypatch.setattr(shard_step, "exchange_many", exchange_many)
    return poisoned


def poison_resting_ghosts(ctx):
    """The padded shards a shard program left at rest
    (``RunState.padded``) get NaN in every pad row that faces a
    neighbour shard: in each dim the mesh splits, the whole left band
    of every shard but the first and the whole right band of every
    shard but the last -- the rows the last call's exchanges filled or
    left alone, and beyond them to the end of the band.  The next
    call's up-front exchange has to rewrite every one of them that a
    kernel reads.  Returns how many bands were made NaN."""
    rs = ctx._run
    geom, bands = rs.padded_geom, 0
    ranks = dict(geom.mesh.shape)
    for name in geom.names:
        local, cut = geom.local[name], geom.cuts[name]
        ring = []
        for a in rs.padded[name]:
            for ax, dim in enumerate(geom.specs[name]):
                if dim is None:
                    continue
                n, (lo, hi) = local[ax], (cut[ax].start, cut[ax].stop)
                for r in range(ranks[dim]):
                    for b0, b1 in ((0, lo) if r else (0, 0),
                                   (hi, n) if r < ranks[dim] - 1
                                   else (0, 0)):
                        if b1 > b0:
                            band = tuple(
                                slice(r * n + b0, r * n + b1)
                                if i == ax else slice(None)
                                for i in range(a.ndim))
                            a = a.at[band].set(math.nan)
                            bands += 1
            ring.append(a)
        rs.padded[name] = ring
    return bands
