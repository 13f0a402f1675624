"""The call record (``runtime/run_state.py``): one row a leaf
``run_solution`` call, always on; the slow-call rule over hand-made
rows; the counter and the marker span a slow call leaves.  No test
here reads a wall clock to decide: durations are made up, or only
compared with one another inside one row."""

import io
import json

import pytest

from yask_tpu.obs import tracer
from yask_tpu.obs.metrics import get_registry
from yask_tpu.runtime import run_state
from yask_tpu.runtime.run_state import (CALL_LOG_LEN, SLOW_LOOKBACK,
                                        CallRecord, RunState, judge_call,
                                        judge_calls)


def row(secs, launches=(), wait=0.0, mode="pallas", n=10, compiles=0):
    return {"secs": secs, "mode": mode, "n": n, "compiles": compiles,
            "launches": [(2, s) for s in launches], "wait_secs": wait}


def steady(count, secs=0.27, **kw):
    # a steady window's calls differ by under 1 %
    return [row(secs * (1 + 0.004 * (i % 3 - 1)), **kw)
            for i in range(count)]


# ------------------------------------------------------------ the rule

def test_a_steady_log_has_no_slow_call():
    verdicts = judge_calls(steady(150))
    assert not any(v["slow"] for v in verdicts)
    assert verdicts[0]["median"] is None     # nothing before the first
    assert verdicts[-1]["median"] == pytest.approx(0.27, rel=0.005)


@pytest.mark.parametrize("secs", [0.42, 5.48])      # mild; of seconds
def test_one_stalled_call_is_named_with_its_median(secs):
    rows = steady(120) + [row(secs)] + steady(20)
    verdicts = judge_calls(rows)
    assert [i for i, v in enumerate(verdicts) if v["slow"]] == [120]
    assert verdicts[120]["median"] == pytest.approx(0.27, rel=0.005)
    # the stall is in the history of the calls behind it and does not
    # move their median
    assert verdicts[121]["median"] == pytest.approx(0.27, rel=0.005)


def test_just_under_the_factor_is_not_slow():
    rows = [row(1.0)] * 5
    assert not judge_call(rows, row(1.25))["slow"]
    assert judge_call(rows, row(1.2501))["slow"]


def test_the_first_calls_are_judged_by_what_exists_and_no_further_back():
    # one call before it is history enough
    assert judge_calls([row(0.27), row(0.42)])[1]["slow"]
    # only the last SLOW_LOOKBACK calls count: 40 long ones, then 32
    # short ones, and a long one is slow again
    rows = [row(1.0)] * 40 + [row(0.27)] * SLOW_LOOKBACK + [row(1.0)]
    verdicts = judge_calls(rows)
    assert verdicts[-1]["slow"] and verdicts[-1]["median"] == 0.27
    # one short of that the median still holds one long call's weight
    assert not judge_calls(rows[:40] + [row(0.27)] * 15
                           + [row(1.0)])[-1]["slow"]


def test_two_classes_interleaved_are_judged_apart():
    # 10-step calls of 0.27 s beside 100-step calls of 2.7 s
    rows = []
    for _ in range(20):
        rows += [row(0.27, n=10), row(2.7, n=100)]
    rows += [row(0.42, n=10), row(2.7, n=100), row(0.27, mode="jit")]
    verdicts = judge_calls(rows)
    assert [i for i, v in enumerate(verdicts) if v["slow"]] == [40]
    assert verdicts[41]["median"] == 2.7
    assert verdicts[42]["median"] is None       # the first of its mode


def test_a_call_that_compiled_is_no_yardstick():
    rows = [row(20.0, compiles=1), row(0.27), row(0.42)]
    verdicts = judge_calls(rows)
    assert verdicts[1]["median"] is None and verdicts[2]["slow"]
    assert verdicts[2]["median"] == 0.27


@pytest.mark.parametrize("launches,wait,secs,held,worst", [
    # the third enqueue rose by 0.15 s: not the largest, the one that rose
    ((0.01, 0.01, 0.19, 0.06, 0.06), 0.09, 0.42, "launch", 2),
    # the final wait rose
    ((0.01, 0.01, 0.04, 0.06, 0.06), 0.24, 0.42, "wait", 0),
    # neither: the time went by outside every launch and the wait
    ((0.01, 0.01, 0.04, 0.06, 0.06), 0.09, 0.42, "host", 0),
])
def test_a_slow_row_is_told_where_its_excess_lies(launches, wait, secs,
                                                  held, worst):
    before = [row(0.27, (0.01, 0.01, 0.04, 0.06, 0.06), 0.09)] * 8
    v = judge_call(before, row(secs, launches, wait))
    assert v["slow"] and v["held_by"] == held
    if held == "launch":
        assert v["worst_launch"] == worst
        assert v["worst_enqueue_secs"] == launches[worst]


def test_a_slow_row_without_launches_names_none():
    v = judge_call([row(0.27)] * 4, row(0.5))
    assert v["worst_launch"] == -1 and v["worst_enqueue_secs"] == 0.0


# ------------------------------------------- the record, clock made up

class Clock:
    """A clock that only moves when told."""

    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(CallRecord, "clock", staticmethod(c))
    return c


def a_call(rs, clock, enqueues=(0.01, 0.04, 0.06), wait=0.09, first=0):
    rec = rs.begin_call("pallas", first, 10)
    for s in enqueues:
        t0 = rec.clock()
        clock.now += s
        rec.launch(2, rec.clock() - t0)
    t0 = rec.clock()
    clock.now += wait
    rec.wait_secs += rec.clock() - t0
    return rs.end_call(rec)


class Annotations:
    """Stands in for ``TraceAnnotation``: every span that opens."""

    opened = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        Annotations.opened.append((self.name, self.attrs))
        return self

    def __exit__(self, *_exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(tracer, "_annotation_cls", Annotations)
    Annotations.opened = []
    return Annotations.opened


def test_the_ring_is_bounded(clock):
    rs = RunState()
    for i in range(CALL_LOG_LEN + 50):
        a_call(rs, clock, first=i * 10)
    assert len(rs.calls) == CALL_LOG_LEN
    assert rs.calls[0]["first"] == 500 and rs.calls[-1]["secs"] \
        == pytest.approx(0.2)
    assert not any(r["slow"] for r in rs.calls)


def test_a_slow_call_is_counted_and_marked_once_and_writes_no_file(
        clock, annotations, tmp_path, monkeypatch):
    path = tmp_path / "never.jsonl"
    monkeypatch.delenv("YT_TRACE", raising=False)
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    reg = get_registry()
    calls0 = reg.counter("run.calls").value
    slow0 = reg.counter("run.slow_calls").value
    seen0 = reg.histogram("run.call_ms").count
    rs = RunState()
    for i in range(12):
        a_call(rs, clock, first=i * 10)
    stalled = a_call(rs, clock, enqueues=(0.01, 0.19, 0.06), first=120)
    a_call(rs, clock, first=130)
    assert reg.counter("run.calls").value - calls0 == 14
    assert reg.histogram("run.call_ms").count - seen0 == 14
    assert reg.counter("run.slow_calls").value - slow0 == 1
    marks = [a for n, a in annotations if n == "yt.run.slow"]
    assert len(marks) == 1
    # the marker names the launch that held the excess
    assert marks[0]["first"] == 120 and marks[0]["worst_launch"] == 1
    assert marks[0]["worst_enqueue_secs"] == pytest.approx(0.19)
    assert marks[0]["secs"] == pytest.approx(0.35)
    assert marks[0]["median"] == pytest.approx(0.20)
    assert marks[0]["held_by"] == "launch"
    assert stalled["slow"] and [r["slow"] for r in rs.calls].count(True) == 1
    assert not path.exists()


def test_under_yt_trace_the_marker_row_carries_the_attrs(
        clock, tmp_path, monkeypatch):
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE", "1")
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    rs = RunState()
    for i in range(6):
        a_call(rs, clock, first=i * 10)
    a_call(rs, clock, wait=2.09, first=60)
    rows = [r for r in tracer.read_spans(str(path))
            if r["name"] == "run.slow"]
    assert len(rows) == 1 and rows[0]["phase"] == "compute"
    attrs = rows[0]["attrs"]
    assert {"first", "n", "secs", "median", "worst_launch",
            "worst_enqueue_secs", "wait_secs", "gc_secs", "nivcsw",
            "compiles", "held_by"} <= set(attrs)
    assert attrs["held_by"] == "wait"
    assert attrs["wait_secs"] == pytest.approx(2.09)
    # the operator's view of it
    import tools.obs_report as obs_report
    buf = io.StringIO()
    assert obs_report.slow_calls_report(tracer.read_spans(str(path)),
                                        out=buf) == 1
    line = buf.getvalue().splitlines()[1].split()
    assert line[1:4] == ["60", "10", "2.2000"] and line[-1] == "wait"
    assert obs_report.main(["--path", str(path), "--slow-calls"]) == 0
    buf = io.StringIO()
    assert obs_report.slow_calls_report([], out=buf) == 0
    assert "no slow calls" in buf.getvalue()


# ------------------------------------------------- the record, real calls

def make(mode, wf, g=16, stencil="iso3dfd", radius=2, x_ranks=0):
    from yask_tpu import yk_factory
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)
    ctx.apply_command_line_options(f"-g {g} -mode {mode} -wf_steps {wf}")
    if x_ranks:
        ctx.set_num_ranks("x", x_ranks)
    ctx.prepare_solution()
    return ctx


@pytest.mark.parametrize("mode,wf,kw,ks", [
    ("pallas", 4, {"stencil": "cube", "radius": 1}, [4, 4, 2]),
    ("jit", 4, {}, [4, 4, 2]),
    ("shard_map", 5, {"g": 32, "x_ranks": 2}, [5, 5]),
    ("shard_pallas", 2, {"g": 32, "x_ranks": 2}, [10]),
])
def test_a_call_leaves_one_row_whose_launches_are_the_spans(
        mode, wf, kw, ks, tmp_path, monkeypatch):
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    ctx = make(mode, wf, **kw)
    ctx.run_solution(0, 9)
    ctx.run_solution(10, 19)
    log = ctx.call_log()
    assert [(r["mode"], r["first"], r["n"]) for r in log] \
        == [(mode, 0, 10), (mode, 10, 10)]
    spans = tracer.read_spans(str(path))
    calls = [s for s in spans if s["name"] == "run.call"]
    assert len(calls) == 2
    for r, call in zip(log, calls):
        mine = [s["attrs"]["k"] for s in spans if s["name"] == "run.launch"
                and s["parent"] == call["span"]]
        assert [k for k, _s in r["launches"]] == mine == ks
        # the parts lie inside the span, the span inside the row
        parts = sum(s for _k, s in r["launches"]) + r["wait_secs"]
        assert 0 < parts <= call["dur"] <= r["secs"]
        assert r["t0"] > 0 and r["cpu_secs"] >= 0 and r["gc_secs"] >= 0
        assert min(r["nivcsw"], r["nvcsw"], r["majflt"], r["gc_runs"]) >= 0
    # the first call compiled its chunks where the mode opens the span,
    # the second nothing
    assert log[1]["compiles"] == 0 and log[1]["median"] is None \
        or log[1]["median"] == log[0]["secs"]
    if mode in ("pallas", "jit", "shard_pallas"):
        assert log[0]["compiles"] == len(
            [s for s in spans if s["name"] == "compile.chunk"]) >= 1
    # what the accessor hands out is the caller's to keep
    log[0]["secs"] = -1.0
    assert ctx.call_log()[0]["secs"] > 0
    ctx.end_solution()


def test_a_swapped_run_state_answers_with_its_own_calls():
    ctx = make("jit", 2)
    ctx.run_solution(0, 3)
    mine = ctx.get_run_state()
    other = ctx.new_run_state()
    ctx.set_run_state(other)
    assert ctx.call_log() == []
    ctx.run_solution(0, 1)
    ctx.run_solution(2, 3)
    assert [(r["first"], r["n"]) for r in ctx.call_log()] \
        == [(0, 2), (2, 2)]
    ctx.set_run_state(mine)
    assert [(r["first"], r["n"]) for r in ctx.call_log()] == [(0, 4)]
    ctx.end_solution()


def test_a_call_that_fails_leaves_no_row_and_no_open_record(monkeypatch):
    ctx = make("jit", 2)
    ctx.run_solution(0, 1)

    def boom(start, n):
        raise RuntimeError("no launch today")
    monkeypatch.setattr(ctx, "_run_steps", boom)
    with pytest.raises(RuntimeError):
        ctx.run_solution(2, 3)
    assert len(ctx.call_log()) == 1 and ctx.get_run_state().call is None
    ctx.end_solution()


def test_the_rows_are_json_and_the_gc_hook_is_one(clock):
    import gc
    rs = RunState()
    a_call(rs, clock)
    a_call(RunState(), clock)
    assert gc.callbacks.count(run_state._on_gc) == 1
    json.dumps(list(rs.calls))
