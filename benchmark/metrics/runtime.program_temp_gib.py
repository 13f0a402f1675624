"""Runtime: GiB of temporaries in the compiler's own analysis of the
largest executable the cell's context holds -- the largest
``temp_bytes`` over ``StencilContext.compiled_memory()``
(``memory_analysis()`` of each held executable, per device).  A sharded
program's per-shard padded copies are temporaries;
``runtime.peak_device_gib`` counts none of them.  The compiler's count,
not a measured residency.  ``None`` where the program offers no such
accessor (an older commit, a served cell) or the backend no analysis."""


def read(run):
    memory = getattr(getattr(run.cell.kind, "ctx", None),
                     "compiled_memory", None)
    rows = memory() if memory else []
    if not rows:
        return None
    return max(r["temp_bytes"] for r in rows) / 2 ** 30
