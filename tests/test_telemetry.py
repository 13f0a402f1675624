"""The telemetry plane over the obs spine (yask_tpu/obs/telemetry.py,
slo.py + tools/obs_export.py, serve_fleet aggregation).

The contract under test, end to end:

* **Merge semantics** — fleet snapshots merge histogram windows by
  POOLING raw samples and re-ranking; percentiles are never averaged
  (the mean of two worker p99s is not the fleet p99).  Counters and
  gauges sum; per-worker blocks ride along without raw windows.
* **Name stability** — the ``STABLE_*`` registry names are the
  dashboard contract; renaming one fails here.  Prometheus exposition
  derives names mechanically (``serve.total_ms`` → ``yt_serve_total_ms``).
* **SLO burn rate** — multi-window burn over budget with per-SLI
  cooldown; a breach needs EVERY window burning.  OFF (None monitor)
  unless a ``YT_SLO_*`` knob is set; LOG-ONLY when on: a breach is a
  journaled ``slo_breach`` row joined to the offending trace id,
  never a blocked request.
* **Attribution** — a traced supervised run's per-phase span
  self-times sum to the root span's wall time (within 10%) and pick
  up the roofline model for the compute phase; halo-cal-unstable
  traces are excluded from the report.
* **Fleet acceptance** — a 2-worker fleet under an injected
  ``serve.run`` device_hang merges both workers' snapshots and banks
  at least one breach row per faulted worker.

Wired into ``make telemetrycheck`` (and ``make check``).
"""

import io
import json
import os

import numpy as np
import pytest

from yask_tpu.obs import metrics as obs_metrics
from yask_tpu.obs import tracer
from yask_tpu.obs.slo import SLO_SCHEMA, SloMonitor, slo_enabled
from yask_tpu.obs.telemetry import (STABLE_COUNTER_PREFIXES,
                                    STABLE_COUNTERS, STABLE_GAUGES,
                                    STABLE_HISTOGRAMS, TELEMETRY_SCHEMA,
                                    merge_snapshots, prom_name,
                                    to_prometheus)
from yask_tpu.resilience.faults import reset_faults

G = 8
STEPS = 4


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for k in list(os.environ):
        if k.startswith("YT_SLO_"):
            monkeypatch.delenv(k)
    monkeypatch.delenv("YT_FAULT_PLAN", raising=False)
    monkeypatch.delenv("YT_TRACE", raising=False)
    monkeypatch.delenv("YT_TRACE_EVENTS", raising=False)
    monkeypatch.setattr(tracer, "_compact_checked", False)
    reset_faults()
    yield
    reset_faults()


def _hist(samples):
    xs = [float(x) for x in samples]
    return {"count": len(xs),
            "mean": sum(xs) / len(xs) if xs else 0.0,
            "p50": obs_metrics.percentile(xs, 0.50),
            "p99": obs_metrics.percentile(xs, 0.99),
            "max": max(xs) if xs else 0.0,
            "window": len(xs),
            "samples": xs}


# ----------------------------------------------------- merge semantics

def test_merge_pools_samples_never_averages():
    """The one rule that matters: the fleet p99 is the percentile of
    the POOLED window, not the mean of per-worker p99s."""
    a = {"counters": {"serve.requests.ok": 150},
         "gauges": {"serve.queue_depth": 2},
         "histograms": {"serve.total_ms": _hist([1.0] * 150)}}
    b = {"counters": {"serve.requests.ok": 50},
         "gauges": {"serve.queue_depth": 3},
         "histograms": {"serve.total_ms": _hist([1000.0] * 50)}}
    out = merge_snapshots({"w0": a, "w1": b}, ts=123.5)
    assert out["v"] == TELEMETRY_SCHEMA
    assert out["ts"] == 123.5
    m = out["merged"]["histograms"]["serve.total_ms"]
    pooled = obs_metrics.percentile([1.0] * 150 + [1000.0] * 50, 0.99)
    averaged = (1.0 + 1000.0) / 2
    assert m["p99"] == pooled == 1000.0
    assert m["p99"] != averaged
    assert m["count"] == 200
    # count-weighted mean, max of maxes
    assert m["mean"] == pytest.approx((150 * 1.0 + 50 * 1000.0) / 200)
    assert m["max"] == 1000.0
    # counters and gauges sum
    assert out["merged"]["counters"]["serve.requests.ok"] == 200
    assert out["merged"]["gauges"]["serve.queue_depth"] == 5.0


def test_merge_keeps_worker_extras_without_raw_windows():
    a = {"counters": {"c": 1},
         "histograms": {"h": _hist([1.0, 2.0])},
         "occupancy": {"sessions": 3}, "slo": None}
    out = merge_snapshots({"w0": a, "w1": {"error": "EOFError: gone"}})
    w0 = out["workers"]["w0"]
    assert w0["occupancy"] == {"sessions": 3}       # extras ride along
    assert "samples" not in w0["histograms"]["h"]   # raw window dropped
    assert out["workers"]["w1"]["error"].startswith("EOFError")
    assert out["merged"]["counters"] == {"c": 1}    # dead worker = absent
    assert json.loads(json.dumps(out)) == out       # JSON-able


# ------------------------------------------------------ name stability

def test_stable_names_pinned():
    """The dashboard contract: renaming a registry metric fails here
    first, not in a grafana panel three weeks later."""
    assert STABLE_COUNTERS == ("serve.requests.ok",
                               "serve.requests.anomaly",
                               "serve.requests.rejected",
                               "serve.degraded",
                               "serve.preempted",
                               "serve.d2h_bytes",
                               "serve.sanity.exact",
                               "serve.snapshot.reused_bytes")
    assert STABLE_COUNTER_PREFIXES == ("serve.requests.",
                                       "serve.cache.",
                                       "serve.overload.")
    assert STABLE_GAUGES == ("serve.queue_depth",)
    assert STABLE_HISTOGRAMS == ("serve.queue_ms", "serve.run_ms",
                                 "serve.respond_ms",
                                 "serve.total_ms",
                                 "serve.batch_occupancy")
    assert prom_name("serve.total_ms") == "yt_serve_total_ms"
    assert prom_name("serve.requests.ok", prefix="x") \
        == "x_serve_requests_ok"


def test_prometheus_exposition_fleet_and_single():
    a = {"counters": {"serve.requests.ok": 3},
         "gauges": {"serve.queue_depth": 1},
         "histograms": {"serve.total_ms": _hist([2.0, 4.0])}}
    b = {"counters": {"serve.requests.ok": 1}}
    text = to_prometheus(merge_snapshots({"w0": a, "w1": b}))
    lines = text.splitlines()
    assert "# TYPE yt_serve_requests_ok counter" in lines
    assert "yt_serve_requests_ok 4" in lines
    assert 'yt_serve_requests_ok{worker="w0"} 3' in lines
    assert 'yt_serve_requests_ok{worker="w1"} 1' in lines
    assert "# TYPE yt_serve_queue_depth gauge" in lines
    assert "# TYPE yt_serve_total_ms summary" in lines
    assert 'yt_serve_total_ms{quantile="0.99"} 4' in lines
    assert "yt_serve_total_ms_count 2" in lines
    assert "yt_serve_total_ms_sum 6" in lines
    assert "yt_serve_total_ms_max 4" in lines
    # a single worker's snapshot exports unlabeled
    solo = to_prometheus(a)
    assert "yt_serve_requests_ok 3" in solo.splitlines()
    assert "worker=" not in solo


def test_obs_export_unwraps_all_reply_shapes():
    from tools.obs_export import export_snapshot
    snap = {"counters": {"serve.requests.ok": 2}}
    for doc in (snap, {"ok": True, "snapshot": snap},
                {"ok": True, "telemetry": merge_snapshots({"w0": snap})}):
        text = export_snapshot(doc)
        assert "yt_serve_requests_ok" in text


def test_registry_snapshot_full_merges_and_exports():
    """The real Registry → snapshot_full → merge → exposition path."""
    regs = []
    for vals in ([5.0, 5.0], [50.0]):
        r = obs_metrics.Registry()
        r.counter("serve.requests.ok").inc()
        for v in vals:
            r.histogram("serve.total_ms").observe(v)
        regs.append(r.snapshot_full())
    assert regs[0]["histograms"]["serve.total_ms"]["samples"] == [5.0, 5.0]
    out = merge_snapshots({"w0": regs[0], "w1": regs[1]})
    m = out["merged"]["histograms"]["serve.total_ms"]
    assert m["p99"] == obs_metrics.percentile([5.0, 5.0, 50.0], 0.99)
    assert "yt_serve_total_ms" in to_prometheus(out)


# ------------------------------------------------------- SLO burn rate

def test_slo_off_unless_knobs(monkeypatch):
    assert not slo_enabled({})
    assert SloMonitor.from_env({}) is None
    m = SloMonitor.from_env({"YT_SLO_P99_MS": "50"})
    assert m is not None and m.p99_ms == 50.0
    assert m.windows == (300.0, 3600.0)
    # bad values fall back to defaults, never raise
    m = SloMonitor.from_env({"YT_SLO_P99_MS": "50",
                             "YT_SLO_WINDOWS": "bogus",
                             "YT_SLO_BURN": "nan-ish?"})
    assert m.windows == (300.0, 3600.0)
    assert m.burn_threshold == 1.0
    m = SloMonitor.from_env({"YT_SLO_WINDOWS": "5,60"})
    assert m.windows == (5.0, 60.0)


def test_slo_breach_requires_every_window(monkeypatch):
    now = [1000.0]
    m = SloMonitor(windows=(10.0, 100.0), burn_threshold=1.0,
                   cooldown_secs=0.0, error_budget=0.5,
                   clock=lambda: now[0])
    m.record(ok=False, trace="t-bad-1")
    # 55s later the short window is empty: no breach even though the
    # long window burns (total>0 required in EVERY window)
    now[0] = 1055.0
    assert m.evaluate() == []
    m.record(ok=False, trace="t-bad-2")
    brs = m.evaluate()
    assert len(brs) == 1
    br = brs[0]
    assert br["v"] == SLO_SCHEMA
    assert br["signal"] == "error_rate"
    assert br["budget"] == 0.5 and br["threshold"] == 1.0
    assert set(br["windows"]) == {"10", "100"}
    for w in br["windows"].values():
        assert w["total"] > 0 and w["burn"] >= 1.0
        assert set(w) == {"burn", "bad", "total"}
    # joined to the worst offender's trace id
    assert br["trace"] == "t-bad-2"


def test_slo_good_traffic_dilutes_and_cooldown_suppresses():
    now = [0.0]
    m = SloMonitor(windows=(10.0,), burn_threshold=1.0,
                   cooldown_secs=30.0, error_budget=0.5,
                   clock=lambda: now[0])
    for _ in range(10):
        m.record(ok=True)
    m.record(ok=False)
    assert m.evaluate() == []          # 1/11 < 50% budget
    for _ in range(10):
        m.record(ok=False)
    assert len(m.evaluate()) == 1      # 11/21 burns past budget
    assert m.evaluate() == []          # cooldown holds
    now[0] = 31.0
    m.record(ok=False)                 # still burning after cooldown
    assert len(m.evaluate()) == 1
    s = m.summary()
    assert s["enabled"] and s["breaches"] == 2
    assert "error_rate" in s["burn"]


def test_slo_latency_and_occupancy_slis():
    m = SloMonitor(windows=(10.0,), p99_ms=100.0, latency_budget=0.5,
                   min_occupancy=2.0, occupancy_budget=0.5,
                   cooldown_secs=0.0, clock=lambda: 5.0)
    m.record(ok=True, total_ms=500.0, occupancy=1.0, trace="t-slow")
    rates = m.burn_rates(now=5.0)
    assert rates["latency"]["windows"]["10"]["bad"] == 1
    assert rates["occupancy"]["windows"]["10"]["bad"] == 1
    signals = {b["signal"] for b in m.evaluate(now=5.0)}
    assert {"latency", "occupancy"} <= signals
    # under the objective = good events
    m.record(ok=True, total_ms=50.0, occupancy=3.0)
    rates = m.burn_rates(now=5.0)
    assert rates["latency"]["windows"]["10"]["bad"] == 1
    assert rates["latency"]["windows"]["10"]["total"] == 2


def test_slo_breach_e2e_scheduler(tmp_path, monkeypatch):
    """In-process server: an injected serve.run device_hang on a jit
    session exhausts the ladder → rejected → the LOG-ONLY monitor
    journals an slo_breach row joined to the request's trace id, and
    metrics_snapshot surfaces monitor + breach count."""
    monkeypatch.setenv("YT_SLO_ERROR_BUDGET", "0.01")
    monkeypatch.setenv("YT_SLO_WINDOWS", "60,3600")
    monkeypatch.setenv("YT_SLO_COOLDOWN", "0")
    monkeypatch.setenv("YT_TRACE", "1")
    monkeypatch.setenv("YT_TRACE_EVENTS", str(tmp_path / "T.jsonl"))
    monkeypatch.setenv("YT_FAULT_PLAN", "serve.run:device_hang:1")
    reset_faults()
    from yask_tpu.serve import StencilServer
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=G,
                               mode="jit", wf=2)
        srv.init_vars(sid)
        r = srv.run(sid, 0, STEPS - 1, timeout=600)
        assert r.status == "rejected" and r.trace
        rows = srv.journal.rows()
        brs = [x for x in rows if x.get("event") == "slo_breach"]
        assert brs, [x.get("event") for x in rows]
        br = brs[0]
        d = br["detail"]
        assert d["slo_v"] == SLO_SCHEMA
        assert d["signal"] == "error_rate"
        assert set(d["windows"]) == {"60", "3600"}
        for w in d["windows"].values():
            assert w["total"] > 0 and w["burn"] >= 1.0
        # joined to the offending request's trace, which has spans
        assert br["trace_id"] == r.trace
        spans = tracer.read_spans(str(tmp_path / "T.jsonl"))
        assert any(s["trace"] == r.trace for s in spans)
        # LOG-ONLY: the next request is served normally
        r2 = srv.run(sid, 0, STEPS - 1, timeout=600)
        assert r2.ok, f"{r2.status}: {r2.error}"
        snap = srv.metrics_snapshot()
        assert snap["v"] == TELEMETRY_SCHEMA
        assert snap["journal"]["slo_breaches"] >= 1
        assert snap["slo"]["enabled"] is True
        assert "error_rate" in snap["slo"]["burn"]
        # the registry export stays inside the stable vocabulary
        for name in snap["counters"]:
            assert name in STABLE_COUNTERS or \
                any(name.startswith(p) for p in STABLE_COUNTER_PREFIXES)
        assert set(STABLE_HISTOGRAMS) <= set(snap["histograms"])
        assert set(STABLE_GAUGES) <= set(snap["gauges"])
        for s in snap["histograms"].values():
            assert "samples" in s      # the mergeable raw window
    finally:
        srv.shutdown()


# ---------------------------------------------------- fleet acceptance

def test_fleet_telemetry_merge_and_slo_breach(tmp_path):
    """2-worker fleet under injected serve.run device_hang: each
    worker's first run rejects (jit = bottom rung) and journals an
    slo_breach row joined to its trace id; the merged fleet snapshot
    carries both workers with pooled histograms; fleet_stats surfaces
    the breach counts."""
    from tools.serve_fleet import ServeFleet
    env = {
        "JAX_PLATFORMS": "cpu",
        "YT_TRACE": "1",
        "YT_TRACE_EVENTS": str(tmp_path / "trace.jsonl"),
        "YT_SLO_ERROR_BUDGET": "0.01",
        "YT_SLO_WINDOWS": "60,3600",
        "YT_SLO_COOLDOWN": "0",
        "YT_FAULT_PLAN": "serve.run:device_hang:1",
    }
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    reset_faults()
    fl = ServeFleet(n_workers=2, cache_dir=str(tmp_path / "cache"),
                    journal_dir=str(tmp_path),
                    worker_args=["--no-preflight", "--window_ms", "5"])
    try:
        sids = []
        for _ in range(2):
            s = fl.handle({"op": "open", "stencil": "iso3dfd",
                           "radius": 1, "g": G, "wf": 2})
            assert s["ok"], s
            assert fl.handle({"op": "init", "sid": s["sid"]})["ok"]
            sids.append(s)
        assert {s["worker"] for s in sids} == {0, 1}

        # each worker's first run hits its injected fault → rejected
        bad = [fl.handle({"op": "run", "sid": s["sid"],
                          "first": 0, "last": STEPS - 1,
                          "timeout": 600}) for s in sids]
        assert all(not r["ok"] for r in bad), bad
        # …then recovers: LOG-ONLY means serving continues
        good = [fl.handle({"op": "run", "sid": s["sid"],
                           "first": 0, "last": STEPS - 1,
                           "timeout": 600}) for s in sids]
        assert all(r["ok"] for r in good), good

        # each worker journal has a breach row joined to the trace of
        # its rejected request (which has spans in the shared file)
        spans = tracer.read_spans(env["YT_TRACE_EVENTS"])
        traced = {s["trace"] for s in spans}
        for w in fl.workers:
            rows = []
            with open(w.journal_path) as f:
                for ln in f:
                    rows.append(json.loads(ln))
            brs = [r for r in rows if r.get("event") == "slo_breach"]
            assert brs, f"worker {w.idx} journaled no slo_breach"
            br = brs[0]
            d = br["detail"]
            assert d["signal"] == "error_rate"
            assert set(d["windows"]) == {"60", "3600"}
            assert all(x["burn"] >= 1.0 and x["total"] > 0
                       for x in d["windows"].values())
            rej = next(r for r in rows
                       if r.get("event") == "rejected")
            assert br["trace_id"] == rej["trace_id"] != ""
            assert br["trace_id"] in traced

        # the merged fleet snapshot: both workers, pooled histograms
        tel = fl.handle({"op": "metrics_snapshot"})
        assert tel["ok"], tel
        t = tel["telemetry"]
        assert t["v"] == TELEMETRY_SCHEMA
        assert set(t["workers"]) == {"w0", "w1"}
        merged = t["merged"]
        assert merged["counters"]["serve.requests.ok"] == 2
        assert merged["counters"]["serve.requests.rejected"] == 2
        assert merged["histograms"]["serve.total_ms"]["count"] == 2
        for wsnap in t["workers"].values():
            assert wsnap["slo"]["enabled"] is True
            for s in wsnap["histograms"].values():
                assert "samples" not in s

        # exposition renders from the merged reply shape
        from tools.obs_export import export_snapshot
        text = export_snapshot(tel)
        assert "yt_serve_requests_rejected 2" in text.splitlines()
        assert 'yt_serve_requests_ok{worker="w0"} 1' \
            in text.splitlines()

        # fleet_stats surfaces per-worker SLO state + breach totals
        fs = fl.handle({"op": "fleet_stats"})
        assert fs["ok"] and fs["slo_breaches"] >= 2
        assert all(row["slo_breaches"] >= 1 and row["slo"]["enabled"]
                   for row in fs["workers"])

        # the heartbeat path banks the same merged shape
        fl.supervise_tick()
        fs = fl.handle({"op": "fleet_stats"})
        assert fs.get("telemetry_ts") is not None
    finally:
        fl.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        reset_faults()
