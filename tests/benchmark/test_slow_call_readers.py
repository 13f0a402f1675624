"""The two readers of the program's call record
(``benchmark/metrics/runtime.slow_call_share.py`` and
``runtime.slow_call_worst_ms.py``) over a made-up ``run``, and both in
the dry run of a cell.  The rows' verdicts are the program's own
(``run_state.judge_calls``), so the rule has one home."""

import importlib.util
import os
import sys
import types

import pytest

from bench_util import BENCH, manifest, result_line, run_cell

from yask_tpu.runtime.run_state import judge_calls

SHARE, WORST = "runtime.slow_call_share", "runtime.slow_call_worst_ms"
ADVANCE = ["iso3dfd-r8-1chip.advance", "cube-r1-1chip.advance",
           "iso3dfd-r8-4chip.advance", "awp-abc-r2-4chip.advance",
           "ssg-r4-1chip.advance", "tti-r4-1chip.advance"]


def reader(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # the readers' own helper
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def log_of(secs, t0=100.0):
    """A call log of back-to-back calls of these seconds, judged by
    the program's rule."""
    rows = []
    for s in secs:
        rows.append({"t0": t0, "secs": s, "mode": "pallas", "n": 10,
                     "compiles": 0, "launches": [], "wait_secs": 0.0})
        t0 += s
    for r, v in zip(rows, judge_calls(rows)):
        r.update(v)
    return rows


def a_run(rows, window=None, accessor=True):
    ctx = types.SimpleNamespace()
    if accessor:
        ctx.call_log = lambda: [dict(r) for r in rows]
    window = window or (rows[0]["t0"], rows[-1]["t0"] + rows[-1]["secs"])
    units = [(window[0], window[0] + 0.5, 10),
             (window[1] - 0.5, window[1], 10)]
    return types.SimpleNamespace(
        units=units, cell=types.SimpleNamespace(
            kind=types.SimpleNamespace(ctx=ctx)))


@pytest.mark.parametrize("name", [SHARE, WORST])
def test_no_record_reads_nothing(name):
    rows = log_of([0.27] * 5)
    assert reader(name)(a_run(rows, accessor=False)) is None    # the parent
    served = a_run(rows)
    del served.cell.kind.ctx                    # a kind with no context
    assert reader(name)(served) is None
    nothing = a_run(rows)
    nothing.units = []
    assert reader(name)(nothing) is None


@pytest.mark.parametrize("name", [SHARE, WORST])
def test_a_steady_window_reads_zero(name):
    assert reader(name)(a_run(log_of([0.27] * 40))) == 0.0


def test_one_stall_reads_its_share_and_its_excess():
    rows = log_of([0.27] * 100 + [0.42] + [0.27] * 46)
    run = a_run(rows)
    total = 146 * 0.27 + 0.42
    assert reader(SHARE)(run) == pytest.approx(100 * 0.15 / total)
    assert reader(SHARE)(run) == pytest.approx(0.376, abs=0.001)
    assert reader(WORST)(run) == pytest.approx(150.0)
    # a call of seconds among tti's 31 a window: a tenth of it
    tti = a_run(log_of([1.28] * 10 + [5.48] + [1.28] * 20))
    assert reader(SHARE)(tti) == pytest.approx(
        100 * 4.2 / (30 * 1.28 + 5.48))
    assert reader(WORST)(tti) == pytest.approx(4200.0)


def test_calls_outside_the_window_are_left_out():
    # warm-up: a compiling call of 20 s and a stalled one, then the
    # window, steady; and a stalled call after its end
    rows = log_of([20.0, 0.27, 0.27, 0.9] + [0.27] * 30 + [3.0])
    rows[-1]["t0"] += 1.0       # the reference ran in between
    start = rows[4]["t0"]
    end = rows[33]["t0"] + rows[33]["secs"]
    run = a_run(rows, window=(start, end))
    assert [r["slow"] for r in rows].count(True) >= 2
    assert reader(SHARE)(run) == 0.0 and reader(WORST)(run) == 0.0
    # with the window opened over the last call both see it
    wide = a_run(rows, window=(start, rows[-1]["t0"] + 3.0))
    assert reader(SHARE)(wide) == pytest.approx(
        100 * (3.0 - 0.27) / (30 * 0.27 + 3.0))
    assert reader(WORST)(wide) == pytest.approx(2730.0)


def test_the_manifest_lists_both_for_the_six_advance_cells():
    rows = {m["name"]: m for m in manifest()["per_layer"]}
    for name, unit in ((SHARE, "%"), (WORST, "ms")):
        m = rows[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, "lower", "program_counter",
                                "runtime", "gpts_per_s")
        assert m["workloads"] == ADVANCE


def test_the_dry_run_of_an_advance_cell_prints_both():
    r = run_cell("cube-r1-1chip.advance", trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    metrics = result_line(r.stdout)["metrics"]
    for name, unit in ((SHARE, "%"), (WORST, "ms")):
        assert metrics[name]["unit"] == unit
        assert metrics[name]["value"] >= 0.0
        assert f"metric {name} = " in r.stdout
