"""The program's own word on the kernels it built: the rows of
``StencilContext.compiled_plans()`` (one a held Pallas chunk: ``k``,
``kernel``, ``stages``, ``block``, ``grid``, ``tile_bytes``,
``scoped_need_bytes``, ``margin_overhead``, ``fetch_overhead`` ...),
for the per-layer readers that take a plan's number rather than a
trace's.  With a program that has no such accessor (an older commit)
or a cell whose kind holds no context (served), there are no rows and
every reader returns ``None``."""


def plans(run) -> list:
    held = getattr(getattr(run.cell.kind, "ctx", None),
                   "compiled_plans", None)
    return held() if held else []


def main_plan(run):
    """The row of the chunk that advances most steps a launch: the
    call's main group (a shorter last group has a row of its own)."""
    rows = plans(run)
    return max(rows, key=lambda r: r["k"]) if rows else None
