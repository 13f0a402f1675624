"""The observability spine (yask_tpu/obs/ + the exporters).

The contract under test, end to end:

* **No-op guarantee** — with ``YT_TRACE`` unset, ``span()`` yields a
  shared null handle, NO trace file is ever created, and a supervised
  run produces bit-identical state to a traced twin (tracing must be
  free to not use).
* **One trace id** joins every artifact: a request's id propagates
  front → scheduler → journal rows → span rows, and
  survives a fleet worker crash into the replacement's (gen+1)
  journal via the re-issued wire message.
* **Metrics parity** — ``obs.metrics.percentile`` IS the historical
  ``server._pctl`` (nearest-rank on ``round(q*(n-1))``), asserted
  value-for-value.
* **Exporters** — ``tools/obs_report.py`` renders a per-phase
  self-time breakdown (queue/exchange separated from compute,
  halo-cal instability surfaced) and valid Chrome/Perfetto JSON;
  ``log_to_csv --traces`` flattens the same rows.

Wired into ``make obscheck`` (and ``make check``).
"""

import csv
import io
import json
import os

import numpy as np
import pytest

from yask_tpu.obs import metrics as obs_metrics
from yask_tpu.obs import tracer
from yask_tpu.resilience.faults import reset_faults

G = 12
STEPS = 4


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv("YT_FAULT_PLAN", raising=False)
    monkeypatch.delenv("YT_TRACE", raising=False)
    monkeypatch.delenv("YT_TRACE_EVENTS", raising=False)
    monkeypatch.delenv("YT_TRACE_MAX_MB", raising=False)
    # re-arm the once-per-process compaction probe per test
    monkeypatch.setattr(tracer, "_compact_checked", False)
    reset_faults()
    yield
    reset_faults()


@pytest.fixture()
def trace_file(tmp_path, monkeypatch):
    p = tmp_path / "TRACE_EVENTS.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(p))
    monkeypatch.setenv("YT_TRACE", "1")
    return p


def _mk_iso(mode="jit", g=G, x_ranks=0, **knobs):
    """Small prepared iso3dfd context with deterministic interiors."""
    from yask_tpu import yk_factory
    fac = yk_factory()
    env = fac.new_env()
    ctx = fac.new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(f"-g {g}")
    o = ctx.get_settings()
    o.mode = mode
    for k, v in knobs.items():
        setattr(o, k, v)
    if x_ranks:
        ctx.set_num_ranks("x", x_ranks)
    ctx.prepare_solution()
    rng = np.random.RandomState(7)
    for vn in ctx.get_var_names():
        v = ctx.get_var(vn)
        if vn == "vel":
            v.set_all_elements_same(0.05)
        else:
            arr = rng.rand(g, g, g).astype(np.float32)
            v.set_elements_in_slice(arr, [0, 0, 0, 0],
                                    [0, g - 1, g - 1, g - 1])
    return ctx


# -------------------------------------------------- the no-op guarantee

def test_disabled_tracer_is_noop_and_creates_no_file(tmp_path,
                                                     monkeypatch):
    p = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(p))
    assert not tracer.trace_enabled()
    with tracer.span("x", phase="compute", a=1) as sp:
        assert sp is tracer._NULL
        assert sp.set(b=2) is sp
        with tracer.span("y") as inner:
            assert inner is tracer._NULL
    tracer.record_span("z", "queue", 0.0, 1.0)
    assert not p.exists()
    assert tracer.current_trace_id() == ""
    # journal rows stay bit-identical: no trace_id key appears
    from yask_tpu.serve.journal import ServeJournal
    row = ServeJournal(str(tmp_path / "J.jsonl")).record(
        "r0", "s0", "received")
    assert "trace_id" not in row


def test_disabled_supervised_run_bit_identical_to_traced(tmp_path,
                                                         monkeypatch):
    """YT_TRACE on vs off around the SAME supervised run: identical
    state; off writes no file, on writes a joined span tree."""
    off_file = tmp_path / "off.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(off_file))
    plain = _mk_iso("jit", ckpt_every=2, ckpt_dir=str(tmp_path))
    plain.run_solution(0, STEPS - 1)
    assert not off_file.exists()
    # the telemetry plane is off too: no YT_SLO_* knob → no monitor
    from yask_tpu.obs.slo import SloMonitor, slo_enabled
    for k in list(os.environ):
        if k.startswith("YT_SLO_"):
            monkeypatch.delenv(k)
    assert not slo_enabled()
    assert SloMonitor.from_env() is None

    on_file = tmp_path / "on.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(on_file))
    monkeypatch.setenv("YT_TRACE", "1")
    traced = _mk_iso("jit", ckpt_every=2, ckpt_dir=str(tmp_path))
    traced.run_solution(0, STEPS - 1)
    assert traced.compare_data(plain) == 0

    rows = tracer.read_spans(str(on_file))
    names = {r["name"] for r in rows}
    assert "run.supervised" in names
    assert "guard:run.chunk" in names
    assert "ckpt.save" in names
    sup = next(r for r in rows if r["name"] == "run.supervised")
    # every chunk is a child of the supervised root, same trace id
    chunks = [r for r in rows if r["name"] == "guard:run.chunk"]
    assert chunks and all(r["trace"] == sup["trace"]
                          and r["parent"] == sup["span"]
                          for r in chunks)
    assert all(r["v"] == tracer.TRACE_SCHEMA for r in rows)
    ck = next(r for r in rows if r["name"] == "ckpt.save")
    assert ck["phase"] == "checkpoint"
    # session-journal evidence written under the trace joins it
    from yask_tpu.resilience.journal import SessionJournal
    with tracer.activate(sup["trace"]):
        row = SessionJournal(str(tmp_path / "J.jsonl")).record(
            "validate", case="obs")
    assert row["trace_id"] == sup["trace"]


# --------------------------------------------------- span fundamentals

def test_span_nesting_parent_links_and_attrs(trace_file):
    with tracer.span("outer", phase="compute", k=2) as a:
        with tracer.span("inner", phase="dma") as b:
            b.set(bytes=4096, arr=np.float32(1.5))
        a.set(done=True)
    rows = tracer.read_spans(str(trace_file))
    assert [r["name"] for r in rows] == ["inner", "outer"]  # close order
    inner, outer = rows
    assert inner["parent"] == outer["span"]
    assert outer["parent"] == ""
    assert inner["trace"] == outer["trace"]
    assert outer["attrs"] == {"k": 2, "done": True}
    assert inner["attrs"]["bytes"] == 4096
    assert isinstance(inner["attrs"]["arr"], (str, float))  # jsonable
    assert all(r["dur"] >= 0 and r["ts"] > 0 for r in rows)
    assert all(r["pid"] == os.getpid() for r in rows)


def test_activate_and_stamp_work_without_enablement(monkeypatch):
    # ids are independent of the write gate: propagation still works
    # when span-writing is off (a worker joining an upstream trace)
    assert not tracer.trace_enabled()
    row = {}
    with tracer.activate("t123"):
        assert tracer.current_trace_id() == "t123"
        tracer.stamp_trace(row)
        with tracer.activate(""):  # empty id = passthrough
            assert tracer.current_trace_id() == "t123"
    assert row == {"trace_id": "t123"}
    assert tracer.current_trace_id() == ""
    assert tracer.stamp_trace({}) == {}


def test_phase_for_site_table():
    assert tracer.phase_for_site("ckpt.save") == "checkpoint"
    assert tracer.phase_for_site("cache.load") == "compile"
    assert tracer.phase_for_site("exchange.ghosts") == "exchange"
    assert tracer.phase_for_site("tuner.measure") == "tune"
    assert tracer.phase_for_site("fleet.route") == "front"
    assert tracer.phase_for_site("run.chunk") == "compute"
    assert tracer.phase_for_site("serve.run") == "compute"
    assert tracer.phase_for_site("state.to_device") == "dma"
    assert tracer.phase_for_site("mystery.site") == "guard"


def test_compaction_bounds_growth_and_bad_env_never_raises(
        tmp_path, monkeypatch):
    p = tmp_path / "T.jsonl"
    lines = [json.dumps({"v": tracer.TRACE_SCHEMA, "trace": f"t{i}",
                         "span": f"s{i}", "parent": "", "name": "n",
                         "phase": "compute", "ts": float(i), "dur": 0.1,
                         "pid": 1, "tid": 1, "attrs": {}})
             for i in range(200)]
    p.write_text("\n".join(lines) + "\n")
    size = p.stat().st_size
    assert tracer.compact_if_large(str(p), max_bytes=size // 4)
    kept = tracer.read_spans(str(p))
    assert 0 < len(kept) < 200
    assert kept[-1]["trace"] == "t199"          # newest tail survives
    assert p.stat().st_size <= size // 8 + 200  # half the limit-ish
    # bad env values: default, never a raise
    monkeypatch.setenv("YT_TRACE_MAX_MB", "garbage")
    assert tracer.trace_max_bytes() == 64 << 20
    monkeypatch.setenv("YT_TRACE_MAX_MB", "-3")
    assert tracer.trace_max_bytes() == 64 << 20
    monkeypatch.setenv("YT_TRACE_MAX_MB", "0.0001")
    assert tracer.trace_max_bytes() == int(0.0001 * (1 << 20))
    assert tracer.compact_if_large(str(tmp_path / "missing.jsonl")) \
        is False


# ------------------------------------------------------------- metrics

def _old_pctl(xs, q):
    """The historical serve.server._pctl, verbatim."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


def test_percentile_matches_old_server_pctl_exactly():
    rng = np.random.RandomState(3)
    for n in (1, 2, 3, 7, 100, 101):
        xs = [float(x) for x in rng.rand(n) * 100]
        for q in (0.0, 0.5, 0.9, 0.99, 1.0):
            assert obs_metrics.percentile(xs, q) == _old_pctl(xs, q)
    assert obs_metrics.percentile([], 0.5) == 0.0


def test_registry_instruments_and_snapshot():
    reg = obs_metrics.Registry()
    reg.counter("req.ok").inc()
    reg.counter("req.ok").inc(2)
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_ms")
    xs = [5.0, 1.0, 9.0, 3.0]
    for x in xs:
        h.observe(x)
    snap = reg.snapshot()
    assert snap["counters"]["req.ok"] == 3
    assert snap["gauges"]["depth"] == 7.0
    s = snap["histograms"]["lat_ms"]
    assert s["count"] == 4 and s["max"] == 9.0
    assert s["p50"] == _old_pctl(xs, 0.50)
    assert s["p99"] == _old_pctl(xs, 0.99)
    assert s["mean"] == pytest.approx(4.5)
    json.dumps(snap)  # JSON-able, whole
    # bounded window: evicts oldest, count keeps the lifetime total
    hb = obs_metrics.Histogram(window=2)
    for x in (1.0, 2.0, 3.0):
        hb.observe(x)
    assert hb.count == 3 and hb.summary()["window"] == 2
    assert hb.percentile(0.0) == 2.0


# ------------------------------------- serve: one trace id, end to end

def test_scheduler_propagates_trace_through_artifacts(tmp_path,
                                                      monkeypatch,
                                                      trace_file):
    from yask_tpu.serve import ServeRequest, StencilServer
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.05, preflight=False)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=8,
                               mode="jit", wf=2)
        srv.init_vars(sid)
        tid = "t0123456789abcde"
        h = srv.submit(ServeRequest(session=sid, first_step=0,
                                    last_step=STEPS - 1, trace=tid))
        resp = srv.wait(h, timeout=600)
        assert resp.ok
        assert resp.trace == tid                     # rides the response
        events = srv.journal.events(resp.rid)
        assert events and all(e.get("trace_id") == tid for e in events)
        rows = tracer.read_spans(str(trace_file))
        mine = [r for r in rows if r["trace"] == tid]
        names = {r["name"] for r in mine}
        assert "serve.chunk" in names                # batch execution
        assert "serve.queue_wait" in names           # retroactive span
        qw = next(r for r in mine if r["name"] == "serve.queue_wait")
        assert qw["phase"] == "queue"
        # the registry saw the release
        m = srv.metrics()
        assert m["registry"]["counters"]["serve.requests.ok"] == 1
        assert m["registry"]["histograms"]["serve.total_ms"]["count"] \
            == 1
        # the scheduler's sample of the request joins back by trace id
        assert tid in {s.get("trace") for s in srv.scheduler.samples()}
    finally:
        srv.shutdown()


def test_untraced_request_mints_id_only_when_enabled(tmp_path,
                                                     monkeypatch):
    from yask_tpu.serve.scheduler import _Pending
    from yask_tpu.serve import ServeRequest
    req = ServeRequest(session="s", first_step=0, last_step=0)
    assert _Pending(req, "r0").trace == ""          # off: stays ""
    monkeypatch.setenv("YT_TRACE", "1")
    monkeypatch.setenv("YT_TRACE_EVENTS",
                       str(tmp_path / "T.jsonl"))
    assert _Pending(req, "r1").trace.startswith("t")  # on: minted
    req2 = ServeRequest(session="s", first_step=0, last_step=0,
                        trace="twire")
    assert _Pending(req2, "r2").trace == "twire"     # wire id wins


# --------------------------------------- fleet: survival across gen+1

def test_fleet_trace_survives_worker_failover(tmp_path, monkeypatch):
    """One front-stamped trace id rides open/run wire msgs, lands in
    the gen-0 worker's journal, survives the chaos kill into the
    replacement's (gen+1) re-issued run, and joins the span file
    across processes."""
    trace_path = tmp_path / "TRACE_EVENTS.jsonl"
    for k, v in (("JAX_PLATFORMS", "cpu"),
                 ("YT_TRACE", "1"), ("YT_TRACE_EVENTS", str(trace_path))):
        monkeypatch.setenv(k, v)
    from tools.serve_fleet import ServeFleet
    chaos_env = dict(os.environ)
    # probes: run1 entry, run2 entry, run2 flush 1 (passes), run2
    # flush 2 -> os._exit mid-op (same plan as the failover suite)
    chaos_env["YT_FAULT_PLAN"] = "fleet.kill_worker:worker_dead:1:3"
    fl = ServeFleet(n_workers=1, cache_dir=str(tmp_path / "cache"),
                    journal_dir=str(tmp_path),
                    worker_args=["--no-preflight", "--window_ms", "5"],
                    env=chaos_env)
    fl._base_env.pop("YT_FAULT_PLAN")   # replacements spawn clean
    try:
        o = fl.handle({"op": "open", "stencil": "iso3dfd", "radius": 1,
                       "g": 8, "wf": 2})
        assert o["ok"], o
        sid = o["sid"]
        assert fl.handle({"op": "init", "sid": sid})["ok"]
        r1 = fl.handle({"op": "run", "sid": sid, "first": 0, "last": 3})
        assert r1["ok"], r1
        gen0 = fl.workers[0]
        msg2 = {"op": "run", "sid": sid, "first": 4, "last": 9,
                "flush_every": 2}
        r2 = fl.handle(msg2, emit=lambda _ln: None)
        assert r2["ok"], r2
        tid = msg2["trace"]                    # front-stamped
        assert tid and r2["trace"] == tid
        assert fl.workers[0].gen == gen0.gen + 1   # failover happened

        # gen+1 evidence: the replacement finished the SAME trace —
        # the worker journal (shared path across gens) holds a
        # terminal ok for it, which only the replacement could write
        from yask_tpu.serve.journal import ServeJournal
        wrows = ServeJournal(
            str(tmp_path / "SERVE_JOURNAL.w0.jsonl")).rows()
        mine = [r for r in wrows if r.get("trace_id") == tid]
        assert any(r["event"] == "ok" for r in mine), mine
        # the front's retry row carries the id too
        frows = ServeJournal(
            str(tmp_path / "SERVE_JOURNAL.fleet.jsonl")).rows()
        retries = [r for r in frows if r["event"] == "retry"]
        assert retries and retries[0].get("trace_id") == tid

        # span file: front process + worker process(es), one trace
        spans = [r for r in tracer.read_spans(str(trace_path))
                 if r["trace"] == tid]
        names = {r["name"] for r in spans}
        assert "fleet.run" in names            # the front's span
        assert "serve.chunk" in names          # a worker's span
        assert len({r["pid"] for r in spans}) >= 2
    finally:
        fl.close()


# ----------------------------------------------------------- exporters

def _synthetic_rows():
    mk = lambda **kw: {"v": tracer.TRACE_SCHEMA, "trace": "tA",
                       "parent": "", "pid": 10, "tid": 1, "attrs": {},
                       **kw}
    return [
        mk(span="s1", name="run.supervised", phase="compute",
           ts=100.0, dur=1.0),
        mk(span="s2", parent="s1", name="serve.chunk", phase="compute",
           ts=100.1, dur=0.6),
        mk(span="s3", parent="s2", name="ckpt.save", phase="checkpoint",
           ts=100.5, dur=0.1),
        mk(span="s4", name="serve.queue_wait", phase="queue",
           ts=99.8, dur=0.2),
        mk(span="s5", name="compile.chunk", phase="compile", ts=99.0,
           dur=0.3, attrs={"kind": "shard_pallas", "k": 2}),
        mk(span="s6", parent="s2", name="run.launch", phase="compute",
           ts=100.2, dur=0.15,
           attrs={"k": 10, "xrounds": 5, "xslabs": 24, "xbytes": 138240}),
        # a second, older trace — the default must pick tA (newest)
        mk(span="s7", trace="tOLD", name="fleet.run", phase="front",
           ts=50.0, dur=0.5, pid=11),
    ]


@pytest.fixture()
def synthetic_trace(tmp_path):
    p = tmp_path / "T.jsonl"
    with open(p, "w") as f:
        for r in _synthetic_rows():
            f.write(json.dumps(r) + "\n")
    return p


def test_obs_report_phase_table_and_self_time(synthetic_trace):
    import importlib
    obs_report = importlib.import_module("tools.obs_report")
    rows = obs_report.pick_trace(
        tracer.read_spans(str(synthetic_trace)))
    assert {r["trace"] for r in rows} == {"tA"}     # latest trace wins
    selfs = obs_report.self_times(rows)
    assert selfs["s1"] == pytest.approx(0.4)        # 1.0 - child 0.6
    assert selfs["s2"] == pytest.approx(0.35)   # 0.6 - children 0.25
    bk = obs_report.phase_breakdown(rows)
    # every phase is its spans' self-times and nothing else: the
    # launch's 0.15 stays in compute, where it ran
    assert bk["compute"] == {"secs": pytest.approx(0.9), "count": 3}
    assert bk["queue"]["secs"] == pytest.approx(0.2)
    assert bk["compile"]["secs"] == pytest.approx(0.3)
    assert bk["checkpoint"]["secs"] == pytest.approx(0.1)
    assert "exchange" not in bk
    buf = io.StringIO()
    obs_report.report(rows, top=3, out=buf)
    text = buf.getvalue()
    for needle in ("compute", "queue", "compile", "checkpoint"):
        assert needle in text, text
    assert "halo" not in text and "exchange" not in text


def test_obs_report_perfetto_export_is_valid(synthetic_trace,
                                             tmp_path, capsys):
    import importlib
    obs_report = importlib.import_module("tools.obs_report")
    out = tmp_path / "perfetto.json"
    rc = obs_report.main(["--path", str(synthetic_trace),
                          "--trace", "all",
                          "--perfetto", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert len(xs) == len(_synthetic_rows())
    assert {e["pid"] for e in ms} == {10, 11}       # one lane per pid
    chunk = next(e for e in xs if e["name"] == "serve.chunk")
    assert chunk["ts"] == pytest.approx(100.1e6)    # µs wall clock
    assert chunk["dur"] == pytest.approx(0.6e6)
    assert chunk["cat"] == "compute"
    assert chunk["args"]["parent"] == "s1"
    capsys.readouterr()


def test_log_to_csv_traces_flattens(synthetic_trace):
    from yask_tpu.tools.log_to_csv import TRACE_COLS, traces_to_csv
    buf = io.StringIO()
    n = traces_to_csv(str(synthetic_trace), out=buf)
    assert n == len(_synthetic_rows())
    rows = list(csv.DictReader(io.StringIO(buf.getvalue())))
    assert len(rows) == n
    assert list(rows[0]) == TRACE_COLS
    launch = next(r for r in rows if r["name"] == "run.launch")
    assert json.loads(launch["attrs"])["xrounds"] == 5


# ------------------------- the second sink: the profiler's own clock

#: child span -> the span it must lie inside, for every span of the
#: taxonomy the cells cross (docs/observability.md)
TAXONOMY = {
    "yt.run.call": None,
    "yt.run.launch": "yt.run.call",
    "yt.run.wait": "yt.run.call",
    "yt.run.repad": None,             # strip: in a call; re-pad: lazy
    "yt.state.to_device": None,       # wherever host state goes back
    "yt.compile.chunk": "yt.run.call",
    "yt.cache.aot": "yt.run.call",
    "yt.serve.request": None,
    "yt.serve.collect": "yt.serve.request",
    "yt.serve.release": None,         # behind the answer, on the worker
    "yt.serve.snapshot": "yt.serve.request",
    "yt.serve.chunk": "yt.serve.request",
    "yt.serve.respond": "yt.serve.request",
    "yt.serve.sanity": "yt.serve.respond",
    "yt.serve.journal": "yt.serve.respond",
}


def _host_events(logdir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``yt.*`` host
    events in the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = [os.path.join(d, f) for d, _s, fs in os.walk(logdir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("yt."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session around a Pallas-interpreted 10-step call at
    K=4 (two fused groups + a fused group of the 2 steps left), two
    shard_pallas calls with a checkpoint between them, a checkpoint
    reload, and one served request; ``YT_TRACE`` unset."""
    import jax
    from yask_tpu.serve import StencilServer
    tmp = tmp_path_factory.mktemp("prof")
    saved = {k: os.environ.pop(k, None)
             for k in ("YT_TRACE", "YT_TRACE_EVENTS")}
    os.environ["YT_TRACE_EVENTS"] = str(tmp / "never.jsonl")
    ctx = _mk_iso("pallas", g=16, wf_steps=4)
    twin = _mk_iso("pallas", g=16, wf_steps=4)
    twin.run_solution(0, 9)                  # no session: the control
    shard = _mk_iso("shard_pallas", g=32, x_ranks=2, wf_steps=2)
    srv = StencilServer(journal_path=str(tmp / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=8,
                               mode="jit", wf=2)
        srv.init_vars(sid)
        jax.profiler.start_trace(str(tmp / "trace"))
        try:
            ctx.run_solution(0, 9)
            shard.run_solution(0, 3)
            # a checkpoint re-pads the resident interiors; the next
            # call strips them again
            shard.save_checkpoint(str(tmp / "sck"))
            shard.run_solution(4, 5)
            twin.save_checkpoint(str(tmp / "ck"))
            twin.load_checkpoint(str(tmp / "ck"))    # host -> device
            resp = srv.run(sid, 0, STEPS - 1)
            # the worker gives the snapshot back behind the answer:
            # joined, its last span is in the trace
            srv.shutdown()
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.shutdown()
        for k, v in saved.items():
            os.environ.pop(k, None)
            if v is not None:
                os.environ[k] = v
    return {"events": _host_events(str(tmp / "trace")), "ctx": ctx,
            "twin": twin, "resp": resp,
            "jsonl": tmp / "never.jsonl"}


@pytest.mark.parametrize("name", sorted(TAXONOMY))
def test_profiler_session_holds_every_span_inside_its_parent(
        profiled, name):
    mine = [e for e in profiled["events"] if e[0] == name]
    assert mine, f"no {name} event in the .xplane.pb"
    parent = TAXONOMY[name]
    if parent:
        outer = [e for e in profiled["events"] if e[0] == parent]
        for _n, a, b, _st in mine:
            assert any(pa <= a and b <= pb for _p, pa, pb, _s in outer), \
                f"{name} [{a}, {b}] lies inside no {parent}"


def test_profiled_spans_carry_scalar_attrs_and_one_rid(profiled):
    ev = profiled["events"]
    call = next(e for e in ev if e[0] == "yt.run.call"
                and e[3].get("mode") == "pallas")
    assert call[3]["n"] == 10 and call[3]["first"] == 0
    launches = [e[3] for e in ev if e[0] == "yt.run.launch"
                and call[1] <= e[1] <= call[2]]
    # every group a fused launch of its own length: no step leaves the
    # kernel, so no remainder span, and the call waits once, at its end
    assert [a["k"] for a in launches] == [4, 4, 2]
    # a launch returns the two pressure slots its kernel wrote and
    # carries ``vel`` over by reference: written + kept = the state's
    assert [(a["written"], a["kept"]) for a in launches] == [(2, 1)] * 3
    assert sum(len(r) for r in profiled["ctx"]._state.values()) == 3
    assert not [e for e in ev if e[0] == "yt.run.remainder"]
    waits = [e for e in ev if e[0] == "yt.run.wait"
             and call[1] <= e[1] <= call[2]]
    assert len(waits) == 1
    rid = profiled["resp"].rid
    for name in ("yt.serve.request", "yt.serve.collect",
                 "yt.serve.snapshot", "yt.serve.chunk",
                 "yt.serve.respond", "yt.serve.sanity",
                 "yt.serve.journal", "yt.serve.release"):
        assert [e[3].get("rid") for e in ev if e[0] == name] == [rid]
    # a batch's chunk names every member (here the one)
    chunk = next(e for e in ev if e[0] == "yt.serve.chunk")
    assert chunk[3]["rids"] == rid
    # the phases of the request follow one another on the one clock
    # (first the hand-over into the worker; last, behind the answer,
    # the rollback snapshot given back)
    order = [next(e for e in ev if e[0] == n) for n in (
        "yt.serve.collect", "yt.serve.snapshot", "yt.serve.chunk",
        "yt.serve.respond", "yt.serve.release")]
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))


def test_no_session_no_file_and_bit_identical_results(profiled):
    """The annotations are the only thing always on: with ``YT_TRACE``
    unset nothing is written, and a profiled run leaves the state a
    plain run leaves."""
    assert not profiled["jsonl"].exists()
    assert profiled["ctx"].compare_data(profiled["twin"],
                                        epsilon=0.0,
                                        abs_epsilon=0.0) == 0
    assert profiled["ctx"]._run.steps_done == 10


def test_built_chunks_carry_the_programs_names():
    """The device side is named from inside: the Pallas call by
    ``kernel_name``, each compiled module by its function, the XLA
    work beside the kernel by named scopes."""
    import jax
    from yask_tpu.ops import pallas_stencil as ps
    from yask_tpu.parallel import shard_step
    from yask_tpu.runtime.context import SCOPE_XLA_STEP
    ctx = _mk_iso("pallas", g=16, wf_steps=2)
    ctx._state_to_device()
    assert ps.kernel_name(ctx._program, 2) == "yt_iso3dfd_r2_k2"
    assert ps.kernel_name(ctx._program, 2, "shell") \
        == "yt_iso3dfd_r2_k2_shell"
    chunk, _ = ps.build_pallas_chunk(ctx._program, fuse_steps=2,
                                     interpret=True)
    assert "name=yt_iso3dfd_r2_k2" in str(
        jax.make_jaxpr(chunk)(ctx._state, 0))
    text = jax.jit(chunk).lower(ctx._state, 0).as_text(debug_info=True)
    assert ps.SCOPE_ZERO_PADS in text
    assert "module @jit_yt_iso3dfd_r2_k2 " in text
    # the executables' own text is what a trace reader joins scopes
    # from (a trace event prints no op_name)
    assert ctx.compiled_texts() == []
    ctx._get_compiled_chunk(2)
    xla, = ctx.compiled_texts()
    assert "HloModule jit_yt_xla_chunk," in xla
    assert f"/{SCOPE_XLA_STEP}/" in xla
    sh = _mk_iso("shard_pallas", g=32, x_ranks=2, wf_steps=2)
    sh.run_solution(0, 3)
    hlo = next(t for t in sh.compiled_texts()
               if "HloModule jit_yt_shard_pallas," in t)
    for scope in (shard_step.SCOPE_PACK, shard_step.SCOPE_UNPACK):
        assert scope in hlo, scope
    # the program starts at the exchange: the pad and the strip of a
    # shard state are programs of their own, each named like its scope
    for scope in (shard_step.SCOPE_PAD, shard_step.SCOPE_STRIP):
        assert scope not in hlo, scope
        assert any(f"HloModule jit_{scope}," in t and f"/{scope}/" in t
                   for t in sh.compiled_texts()), scope


def test_request_intervals_lie_end_to_end_inside_the_clients(
        tmp_path, trace_file):
    """A test of order, not of durations: queued, run (snapshot +
    chunk) and respond follow one another without overlap, inside the
    client's submit-to-answer interval; ``srv.metrics()`` covers all
    three."""
    import time
    from yask_tpu.serve import StencilServer
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=8,
                               mode="jit", wf=2)
        srv.init_vars(sid)
        t0 = time.perf_counter()
        resp = srv.run(sid, 0, STEPS - 1)
        client = time.perf_counter() - t0
        sample = srv.scheduler.samples()[-1]
        m = srv.metrics()
    finally:
        srv.shutdown()
    assert resp.ok
    parts = (resp.queue_secs, resp.run_secs, resp.respond_secs)
    assert all(p >= 0 for p in parts) and resp.respond_secs > 0
    assert sum(parts) <= client
    assert sample["respond_secs"] == resp.respond_secs
    assert m["p50_total_ms"] >= m["p50_run_ms"] + m["p50_respond_ms"] \
        - 0.002
    assert m["registry"]["counters"]["serve.d2h_bytes"] > 0
    rows = {r["name"]: r for r in tracer.read_spans(str(trace_file))
            if r["attrs"].get("rid") == resp.rid}
    order = ["serve.queue_wait", "serve.snapshot", "serve.chunk",
             "serve.respond"]
    slack = 0.005           # ts is the wall clock, dur perf_counter's
    for a, b in zip(order, order[1:]):
        assert rows[a]["ts"] + rows[a]["dur"] <= rows[b]["ts"] + slack, \
            (a, b)
    root = rows["serve.request"]
    assert root["parent"] == ""
    assert root["ts"] - slack <= rows["serve.queue_wait"]["ts"]
    assert rows["serve.respond"]["ts"] + rows["serve.respond"]["dur"] \
        <= root["ts"] + root["dur"] + slack
    assert len({r["trace"] for r in rows.values()}) == 1
    # bytes are known only after the pull: the JSONL row has them
    assert rows["serve.snapshot"]["attrs"]["bytes"] > 0
    assert rows["serve.respond"]["attrs"]["bytes"] > 0


def test_slo_latency_is_the_whole_request_and_rids_keep_journal_order(
        tmp_path, monkeypatch):
    """The latency the SLO monitor is fed covers queue + run + respond
    (its thresholds are compared with submit-to-answer time), and with
    concurrent clients a rid is drawn in the same lock hold as its
    ``received`` row, so rid order is journal order."""
    import threading
    from yask_tpu.serve import StencilServer
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    fed, resps = [], []
    real = srv.scheduler._slo_feed
    monkeypatch.setattr(
        srv.scheduler, "_slo_feed",
        lambda p, sid, **kw: (fed.append((p.rid, kw.get("total_ms"))),
                              real(p, sid, **kw))[1])
    try:
        sids = []
        for _ in range(3):
            sids.append(srv.open_session(stencil="iso3dfd", radius=1,
                                         g=8, mode="jit", wf=2))
            srv.init_vars(sids[-1])

        def client(sid):
            for i in range(3):
                resps.append(srv.run(sid, 2 * i, 2 * i + 1))
        threads = [threading.Thread(target=client, args=(s,))
                   for s in sids]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.shutdown()
    assert len(resps) == 9 and all(r.ok for r in resps)
    want = {r.rid: (r.queue_secs + r.run_secs + r.respond_secs) * 1e3
            for r in resps}
    assert {rid: ms for rid, ms in fed if rid in want} \
        == pytest.approx(want)
    with open(tmp_path / "SJ.jsonl") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    received = [r["rid"] for r in rows if r.get("event") == "received"]
    assert len(received) == 9 and received == sorted(received)
