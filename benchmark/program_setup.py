"""The program's own record of its set-up: the rows of
``yask_tpu.obs.kept_spans()`` (one a ``span(..., keep=True)`` that has
ended: ``name`` without the ``yt.`` prefix, ``phase``, ``t0`` on the
clock of ``run.units``, ``secs``, ``tid``, ``parent`` -- the enclosing
kept span on that thread, '' at the top -- and ``attrs``), kept where
no profiler runs: the harness opens its profiler at the window's start,
after set-up.  For the per-layer readers that split ``setup_s``.

What the readers share:

* :func:`rows`: the kept rows that ended before the window's first unit
  began.  ``[]`` for a program without the accessor (an older commit):
  every reader then returns ``None``.
* :func:`top`: the rows a sum may count, each second once: those with
  no ``parent``, less any whose interval lies inside another such row's
  (another thread's: a worker's fill under a client's upload).  Decided
  by interval, not by thread.
* :func:`split`: the seconds of each part, memoised on ``run`` and
  said once on an earlier line of the run's output, ``since_start_s``
  (the process's age when the program's import began) beside them.

By construction ``import_s + prepare_s + fill_s + build_s + warm_run_s
+ unattributed_s = setup_s``: ``warm_run_s`` is the warm-up units' time
outside kept rows (``sum(run.first_call_s)`` less the counted rows
inside the units), ``unattributed_s`` what is left -- the time outside
the warm-up units that no kept row covers: the process's start-up, the
harness's own imports, ``device_state``'s seeding, the probe reads.

The harness keeps the warm-up units' seconds and not their starts, and
its kinds build first and run afterwards.  So a row belongs to the
warm-up units when it began after the last row of phase ``setup``
(import, env, solution, prepare, a public fill, a session's opening or
upload) ended: builds, pushes and derived fills a first call does.
"""

#: kept span names (the tracer's, without ``yt.``) by the metric that
#: sums them; a name in none of them stays in ``unattributed_s``
PARTS = {
    "import_s": ("setup.import",),
    "prepare_s": ("setup.env", "setup.solution", "setup.prepare",
                  "serve.open"),
    "fill_s": ("state.fill", "serve.set_var", "state.to_device",
               "state.derive"),
    "build_s": ("compile.chunk", "tuner.trial", "halo_cal", "cache.aot"),
}


def rows(run) -> list:
    try:
        from yask_tpu.obs import kept_spans
    except ImportError:             # the parent: no kept record
        return []
    if not run.units:
        return []
    start = run.units[0][0]
    return [r for r in kept_spans() if r["t0"] + r["secs"] <= start]


def top(kept) -> list:
    tops = [r for r in kept if not r["parent"]]

    def inside(r, o):
        return (o["secs"] > r["secs"] and o["t0"] <= r["t0"]
                and r["t0"] + r["secs"] <= o["t0"] + o["secs"])
    return [r for r in tops if not any(inside(r, o) for o in tops)]


def split(run):
    """``{part: seconds}`` for the four parts, ``warm_run_s``,
    ``unattributed_s``, ``cache_misses``, ``since_start_s`` and
    ``rows``; ``None`` without a kept record."""
    if hasattr(run, "setup_split"):
        return run.setup_split
    kept = rows(run)
    run.setup_split = None
    if not kept:
        return None
    part_of = {name: part for part, names in PARTS.items()
               for name in names}
    counted = [r for r in top(kept) if r["name"] in part_of]
    out = {part: 0.0 for part in PARTS}
    for r in counted:
        out[part_of[r["name"]]] += r["secs"]
    built = max((r["t0"] + r["secs"] for r in kept
                 if r["phase"] == "setup"), default=float("-inf"))
    in_units = sum(r["secs"] for r in counted if r["t0"] >= built)
    out["warm_run_s"] = sum(run.first_call_s) - in_units
    out["unattributed_s"] = (run.setup_s - sum(out[p] for p in PARTS)
                             - out["warm_run_s"])
    out["cache_misses"] = sum(
        1 for r in kept if r["name"] == "cache.aot"
        and r["attrs"].get("hit") == "miss")
    out["since_start_s"] = next(
        (r["attrs"].get("since_start_s") for r in kept
         if r["name"] == "setup.import"), None)
    out["rows"] = len(kept)
    run.setup_split = out
    label = "cpu dry-run: " if run.cell.tiny else ""
    print(label + "set-up by the program's kept spans: " + ", ".join(
        f"{k} {v!r}" for k, v in out.items())
        + f", setup_s {run.setup_s!r}", flush=True)
    return out


def read(run, key):
    """One number of :func:`split`; ``None`` without a kept record."""
    parts = split(run)
    return None if parts is None else parts[key]
