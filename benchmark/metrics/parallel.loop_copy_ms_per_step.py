"""Parallel: device time of the whole-array ``copy`` operations XLA puts
into the shard program beside its kernels, over the steps traced: the
``copy`` instructions of the module ``yt_shard_*`` that lie under no
``jax.named_scope`` of the program (a pack, an unpack, a pad or a strip
is scoped and is ``parallel.pack_ms_per_step``'s), on the busiest
device.

What they are (``PERF.md`` section 5): the K-group loop of
``parallel/shard_step.py _prep_shard_pallas`` is a ``lax.scan``, whose
carry lives in fixed buffers, one a position.  A group's kernels are
out of place, so a program that runs ONE group a scan iteration copies
every array the group wrote into the carry's buffer, and every ring
slot that only changed position, at every iteration: bytes no equation
asked for, at the HBM roof.  A program that runs the carry's period of
groups an iteration (``shard_step.carry_period``) and writes a rotating
ring's new level onto the slot it evicts hands the carry on in place
and reads 0.0 here, or what its peeled groups copy once a call.

``program_spans.reduce`` puts every unscoped operation of a module the
program named down to the module, copies among the rest, so this reader
walks the traced slice itself (the same slice, device and scope join).
``None`` without a trace, without a module line (a ``--tiny`` run on
the CPU has none) or where no shard module ran in the slice."""

import os

import program_spans
import trace_reduce as tr

MODULE_PREFIX = "yt_shard"


def copy_ms(events, scopes) -> float:
    """Milliseconds of unscoped ``copy`` operations inside shard modules
    on the busiest device of the traced slice; ``None`` where no shard
    module ran there.  ``events`` as ``program_spans.load_xplane`` gives
    them, ``scopes`` as ``program_spans.scope_map``."""
    spans = events["spans"]
    marks = [s for s in spans if s[0].startswith("bench.")] or spans
    if not marks:
        return None
    lo = min(s[1] for s in marks)
    hi = max(s[1] + s[2] for s in marks)
    best = None
    for plane, ops in events["devices"].items():
        mods = [m for m in events.get("modules", {}).get(plane, [])
                if m[0].startswith(MODULE_PREFIX)
                and m[1] + m[2] > lo and m[1] < hi]
        if not mods:
            continue
        busy, copied = [], 0.0
        for n, s, d, _kernel in ops:
            if s + d <= lo or s >= hi:
                continue
            a, b = max(s, lo), min(s + d, hi)
            busy.append([a, b])
            name, _, opcode = n.rpartition(" ")
            mod = next((m[0] for m in mods if m[1] <= s <= m[1] + m[2]),
                       None)
            if (opcode == "copy" and mod
                    and not scopes.get(mod, {}).get(name)):
                copied += b - a
        busy = tr.total(tr.union(busy))
        if best is None or busy > best[0]:
            best = (busy, copied)
    return None if best is None else best[1] / 1e6


def read(run):
    steps = program_spans.load(run).get("steps")
    base = os.path.join(run.cell.scratch, "trace")
    paths = [os.path.join(d, f) for d, _s, files in os.walk(base)
             for f in files if f.endswith(".xplane.pb")]
    if not steps or not paths or run.cell.tiny:
        return None
    ms = copy_ms(program_spans.load_xplane(paths[0]),
                 program_spans.scope_map(program_spans.compiled_texts(run)))
    return None if ms is None else ms / steps
