"""Elastic fleet: SLO-driven autoscaling, overload control, and the
load harness (ISSUE: elastic fleet under fire).

Layers under test:

* the pure :class:`AutoscalePolicy` decision loop (triggers, cooldown,
  bounds, dead-data refusal) with an injected clock — no fleet;
* telemetry snapshot staleness: ``merge_snapshots`` excludes flagged
  blocks, ``signals_from_snapshot`` never reads them, and the fleet
  front carries banked blocks forward honestly aged and surfaces
  ``stale_workers`` in ``fleet_stats``;
* worker-level overload control on an in-process ``StencilServer``:
  queue-wait deadline fast-fail (terminal ``rejected`` /
  ``deadline_in_queue``), brownout tier 1 (shed streaming flushes)
  and tier 2 (structured ``Overloaded`` + Retry-After on new
  sessions) — in-flight work never abandoned;
* fleet-level admission saturation (``YT_FLEET_MAX_QUEUE``):
  structured ``overloaded`` answer + journal row, and admission
  recovery once queues drain;
* the drain path: ``_scale_down`` migrates every session through the
  checkpoint/restore/replay machinery — zero lost, zero duplicated,
  contiguous steps after migration;
* the ``SERVE-AUTOSCALE-BOUNDS`` checker rule;
* (slow) the chaos soak and trace-replay tenant-mix reproduction via
  ``tools/load_harness.py``.

The closed-loop acceptance (burn spike -> journaled scale_up -> warm
spawn with zero lowerings -> idle drain scale_down) is
``make loadcheck`` (tools/load_harness.py --check), wired into
``make check``.
"""

import json
import os
import threading
import time

import pytest

from yask_tpu.resilience.faults import reset_faults
from yask_tpu.serve.autoscale import (AutoscalePolicy, ScaleSignals,
                                      signals_from_snapshot)

G = 8
PROFILE = {"stencil": "iso3dfd", "radius": 1, "g": G, "wf": 2}


@pytest.fixture(autouse=True)
def _clean_fault_plan(monkeypatch):
    monkeypatch.delenv("YT_FAULT_PLAN", raising=False)
    reset_faults()
    yield
    reset_faults()


# ---------------------------------------------------- policy units


def mk_policy(**kw):
    kw.setdefault("min_workers", 1)
    kw.setdefault("max_workers", 4)
    kw.setdefault("cooldown", 0.0)
    kw.setdefault("up_queue", 8)
    kw.setdefault("up_burn", 1.0)
    kw.setdefault("down_idle", 3)
    return AutoscalePolicy(**kw)


def sig(n=2, fresh=None, queue=0, burn=0.0, draining=0, stale=()):
    return ScaleSignals(n_workers=n, n_draining=draining,
                        fresh_workers=n if fresh is None else fresh,
                        stale_workers=list(stale),
                        queue_depth=queue, max_burn=burn)


def test_policy_refuses_dead_data():
    p = mk_policy(down_idle=1)
    # every worker stale: no decision, and the tick is NOT idle —
    # an unobserved fleet is not a quiet one
    for _ in range(5):
        assert p.decide(sig(fresh=0, stale=["w0", "w1"])) is None
    # the idle counter was held at zero throughout
    assert p._idle_ticks == 0


def test_policy_queue_trigger_and_max_bound():
    p = mk_policy(up_queue=8)
    d = p.decide(sig(n=2, queue=16))  # 8 per fresh worker
    assert d is not None and d.action == "up"
    assert d.reason == "queue_depth"
    assert d.signal["queue_depth"] == 16
    # at the ceiling the same signal decides nothing
    p2 = mk_policy(up_queue=8, max_workers=2)
    assert p2.decide(sig(n=2, queue=64)) is None


def test_policy_burn_trigger():
    p = mk_policy(up_burn=1.0)
    d = p.decide(sig(n=1, fresh=1, burn=2.5))
    assert d is not None and d.action == "up"
    assert d.reason == "burn_rate"
    assert d.signal["max_burn"] == 2.5
    # 0 disables the burn trigger entirely
    p2 = mk_policy(up_burn=0.0)
    assert p2.decide(sig(n=1, fresh=1, burn=99.0)) is None


def test_policy_cooldown_damps_flapping():
    now = [100.0]
    p = mk_policy(cooldown=30.0, clock=lambda: now[0])
    assert p.decide(sig(n=1, fresh=1, burn=5.0)).action == "up"
    # hot again inside the cooldown window: hold
    now[0] += 10.0
    assert p.decide(sig(n=2, burn=5.0)) is None
    # window elapsed: fires again
    now[0] += 25.0
    assert p.decide(sig(n=2, burn=5.0)).action == "up"
    # a decision in EITHER direction opens the window: idle ticks
    # accumulated during cooldown must not fire a down inside it
    now[0] += 1.0
    for _ in range(5):
        assert p.decide(sig(n=3)) is None
    now[0] += 40.0
    d = p.decide(sig(n=3))
    assert d is not None and d.action == "down"


def test_policy_idle_scale_down_and_min_floor():
    p = mk_policy(down_idle=3, min_workers=1)
    assert p.decide(sig(n=2)) is None
    assert p.decide(sig(n=2)) is None
    d = p.decide(sig(n=2))
    assert d is not None and d.action == "down" and d.reason == "idle"
    # at the floor, idleness decides nothing
    p2 = mk_policy(down_idle=1, min_workers=1)
    assert p2.decide(sig(n=1, fresh=1)) is None
    # a draining worker is excluded from the headroom
    p3 = mk_policy(down_idle=1, min_workers=1)
    assert p3.decide(sig(n=2, draining=1)) is None
    # queued work resets the idle streak
    p4 = mk_policy(down_idle=2)
    assert p4.decide(sig(n=2)) is None
    assert p4.decide(sig(n=2, queue=1)) is None
    assert p4.decide(sig(n=2)) is None


def test_signals_from_snapshot_skips_stale_and_errors():
    merged = {
        "workers": {
            "w0": {"occupancy": {"queue_depth": 3},
                   "slo": {"burn": {"latency_p99_ms": {
                       "budget": 0.01,
                       "windows": {"2": {"burn": 7.5, "bad": 3,
                                         "total": 4},
                                   "60": {"burn": 0.2, "bad": 3,
                                          "total": 90}}}}}},
            "w1": {"occupancy": {"queue_depth": 100},
                   "slo": {"burn": {"latency_p99_ms": {
                       "windows": {"2": {"burn": 50.0,
                                         "total": 10}}}}}},
            "w2": {"error": "ServeClientError: boom"},
        },
        "stale_workers": ["w1"],
    }
    s = signals_from_snapshot(merged, n_workers=3, n_draining=1)
    assert s.fresh_workers == 1          # w1 stale, w2 errored
    assert s.queue_depth == 3            # w1's 100 never counted
    assert s.max_burn == 7.5             # SHORTEST populated window
    assert s.stale_workers == ["w1"]
    assert s.n_draining == 1
    # no snapshot at all: zero fresh workers, policy will refuse
    s2 = signals_from_snapshot(None, n_workers=2)
    assert s2.fresh_workers == 0


def test_merge_snapshots_excludes_stale_blocks():
    from yask_tpu.obs.telemetry import merge_snapshots
    fresh = {"counters": {"serve.requests.completed": 5},
             "gauges": {}, "histograms": {}, "poll_age_secs": 0.0}
    stale = {"counters": {"serve.requests.completed": 100},
             "gauges": {}, "histograms": {},
             "poll_age_secs": 99.0, "stale": True}
    m = merge_snapshots({"w0": fresh, "w1": stale})
    assert m["stale_workers"] == ["w1"]
    # the stale worker's counters never entered the fold...
    assert m["merged"]["counters"]["serve.requests.completed"] == 5
    # ...but its block (honestly aged) is still visible per-worker
    assert m["workers"]["w1"]["poll_age_secs"] == 99.0


# ------------------------------------------- worker overload control


@pytest.fixture()
def server(tmp_path):
    from yask_tpu.serve import StencilServer
    srv = StencilServer(journal_path=str(tmp_path / "SERVE.jsonl"),
                        window_secs=0.01, preflight=False)
    yield srv
    srv.shutdown()


def _rows(path):
    out = []
    with open(path) as f:
        for ln in f:
            out.append(json.loads(ln))
    return out


def test_queue_deadline_fast_fail(server, tmp_path, monkeypatch):
    """A request whose deadline expires while QUEUED is rejected with
    reason deadline_in_queue before it ever reaches the device."""
    from yask_tpu.serve import ServeRequest
    sid = server.open_session(**PROFILE)
    server.init_vars(sid)
    # The head request holds the worker behind a gate this test owns;
    # the second queues behind it on the same session with a deadline
    # of 0.02 s, and the gate opens only after that has passed.  (The
    # head used to be a first run whose lazy compile was hoped to be
    # slow: with a warm compile cache it was over before the deadline
    # and the second request ran, ROADMAP D14.)
    gate, held = threading.Event(), threading.Event()
    execute = server.scheduler._execute

    def gated(batch):
        held.set()
        assert gate.wait(timeout=120)
        return execute(batch)

    monkeypatch.setattr(server.scheduler, "_execute", gated)
    # 20 steps stays finite (the undamped profile grows nonfinite
    # past ~40)
    h1 = server.submit(ServeRequest(session=sid, first_step=0,
                                    last_step=19))
    assert held.wait(timeout=120)       # the worker has taken the head
    h2 = server.submit(ServeRequest(session=sid, first_step=20,
                                    last_step=20, deadline_secs=0.02))
    time.sleep(0.05)                    # at least: h2's deadline is past
    gate.set()
    r1, r2 = server.wait(h1), server.wait(h2)
    assert r1.status == "ok", r1.error
    assert r2.status == "rejected", r2.status
    assert "deadline" in (r2.error or ""), r2.error
    rej = [r for r in _rows(str(tmp_path / "SERVE.jsonl"))
           if r["event"] == "rejected" and r["rid"] == r2.rid]
    assert rej and rej[-1]["detail"]["reason"] == "deadline_in_queue", rej
    snap = server.obs.snapshot()
    assert snap["counters"]["serve.overload.deadline_in_queue"] >= 1


@pytest.fixture()
def hot_slo_env(monkeypatch):
    """Every request breaches a 1 us p99 target on a short window —
    the burn rate saturates immediately and deterministically."""
    monkeypatch.setenv("YT_SLO_P99_MS", "0.001")
    monkeypatch.setenv("YT_SLO_WINDOWS", "60")
    yield


def test_brownout_tier1_sheds_flushes(hot_slo_env, monkeypatch,
                                      server, tmp_path):
    from yask_tpu.serve import ServeRequest
    sid = server.open_session(**PROFILE)
    server.init_vars(sid)
    h = server.submit(ServeRequest(session=sid, first_step=0,
                                   last_step=3, flush_every=1))
    assert server.wait(h).status == "ok"      # burn is now >> 2
    monkeypatch.setenv("YT_SERVE_SHED_BURN", "2.0")
    time.sleep(0.3)                           # tier cache ~250 ms
    assert server.scheduler.overload_tier() == 1
    h2 = server.submit(ServeRequest(session=sid, first_step=4,
                                    last_step=7, flush_every=1))
    r2 = server.wait(h2)
    # the run itself (and its final answer) is untouched...
    assert r2.status == "ok", r2.error
    rows = _rows(str(tmp_path / "SERVE.jsonl"))
    shed = [r for r in rows if r["event"] == "shed"
            and r["rid"] == r2.rid]
    streams = [r for r in rows if r["event"] == "stream"
               and r["rid"] == r2.rid]
    # ...but every progress beacon was shed, journaled with the tier
    assert shed and not streams, (shed, streams)
    assert all(r["detail"]["tier"] >= 1 for r in shed)
    snap = server.obs.snapshot()
    assert snap["counters"]["serve.overload.shed_flush"] >= len(shed)


def test_brownout_tier2_rejects_new_sessions(hot_slo_env, monkeypatch,
                                             server, tmp_path):
    from yask_tpu.serve import ServeRequest
    from yask_tpu.serve.api import Overloaded
    sid = server.open_session(**PROFILE)
    server.init_vars(sid)
    h = server.submit(ServeRequest(session=sid, first_step=0,
                                   last_step=1))
    assert server.wait(h).status == "ok"
    monkeypatch.setenv("YT_SERVE_SHED_BURN", "2.0")
    monkeypatch.setenv("YT_SERVE_REJECT_BURN", "4.0")
    monkeypatch.setenv("YT_SERVE_RETRY_AFTER", "2.5")
    time.sleep(0.3)
    assert server.scheduler.overload_tier() == 2
    with pytest.raises(Overloaded) as ei:
        server.open_session(**PROFILE)
    assert ei.value.retry_after == 2.5
    rows = [r for r in _rows(str(tmp_path / "SERVE.jsonl"))
            if r["event"] == "overloaded"]
    assert rows and rows[-1]["detail"]["tier"] == 2, rows
    snap = server.obs.snapshot()
    assert snap["counters"]["serve.overload.rejected_sessions"] >= 1
    assert snap["gauges"]["serve.overload.tier"] == 2
    # in-flight / established tenants are never abandoned: the
    # existing session still serves under tier 2
    h2 = server.submit(ServeRequest(session=sid, first_step=2,
                                    last_step=2))
    assert server.wait(h2).status == "ok"
    # burnout over: admission recovers
    monkeypatch.delenv("YT_SERVE_SHED_BURN")
    monkeypatch.delenv("YT_SERVE_REJECT_BURN")
    time.sleep(0.3)
    assert server.scheduler.overload_tier() == 0
    sid2 = server.open_session(**PROFILE)
    assert sid2


# ----------------------------------------------------- checker rule


@pytest.fixture()
def env():
    from yask_tpu import yk_factory
    return yk_factory().new_env()


def _serve_ctx(env):
    from yask_tpu import yk_factory
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=1)
    ctx.apply_command_line_options(f"-g {G} -wf_steps 2 -serve")
    return ctx


def _autoscale_diags(env):
    from yask_tpu.checker import run_checks
    report = run_checks(_serve_ctx(env), passes=("serve",))
    return [d for d in report.diagnostics
            if d.rule == "SERVE-AUTOSCALE-BOUNDS"]


def test_checker_autoscale_bounds(env, monkeypatch):
    # autoscale off: the rule never fires
    monkeypatch.delenv("YT_FLEET_AUTOSCALE", raising=False)
    assert not _autoscale_diags(env)
    # coherent knobs: info
    monkeypatch.setenv("YT_FLEET_AUTOSCALE", "1")
    d = _autoscale_diags(env)
    assert [x.severity for x in d] == ["info"], d
    # min above raw max: error (the policy clamps, the checker warns
    # the operator they asked for an impossible fleet)
    monkeypatch.setenv("YT_FLEET_MIN_WORKERS", "8")
    monkeypatch.setenv("YT_FLEET_MAX_WORKERS", "2")
    d = _autoscale_diags(env)
    assert [x.severity for x in d] == ["error"], d
    monkeypatch.delenv("YT_FLEET_MIN_WORKERS")
    monkeypatch.delenv("YT_FLEET_MAX_WORKERS")
    # zero cooldown: warn
    monkeypatch.setenv("YT_FLEET_SCALE_COOLDOWN", "0")
    d = _autoscale_diags(env)
    assert [x.severity for x in d] == ["warn"], d
    monkeypatch.delenv("YT_FLEET_SCALE_COOLDOWN")
    # both up-triggers disabled: warn (the fleet can only shrink)
    monkeypatch.setenv("YT_FLEET_SCALE_UP_QUEUE", "0")
    monkeypatch.setenv("YT_FLEET_SCALE_UP_BURN", "0")
    d = _autoscale_diags(env)
    assert [x.severity for x in d] == ["warn"], d


# ------------------------------------------------------ fleet level


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    from tools.serve_fleet import ServeFleet
    tmp = tmp_path_factory.mktemp("elastic")
    saved = {}
    env = {"JAX_PLATFORMS": "cpu"}
    for k, v in env.items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    fl = ServeFleet(n_workers=2, cache_dir=str(tmp / "cache"),
                    journal_dir=str(tmp),
                    worker_args=["--no-preflight", "--window_ms", "5"])
    fl._tmpdir = str(tmp)
    try:
        yield fl
    finally:
        fl.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _fleet_rows(fleet):
    return _rows(fleet.journal.path)


def test_saturation_rejects_structured_then_recovers(fleet,
                                                     monkeypatch):
    """Satellite: YT_FLEET_MAX_QUEUE saturation answers a structured
    overloaded rejection (journaled), and admission recovers once the
    queues drain."""
    from tools.serve_fleet import FleetWorker
    monkeypatch.setenv("YT_FLEET_MAX_QUEUE", "4")
    monkeypatch.setattr(
        FleetWorker, "occupancy",
        lambda self: {"queue_depth": 4, "sessions": 0, "completed": 0})
    out = fleet.handle({"op": "open", **PROFILE})
    assert not out.get("ok") and out.get("overloaded") is True, out
    assert float(out.get("retry_after", 0)) > 0, out
    assert "YT_FLEET_MAX_QUEUE" in out.get("error", ""), out
    rows = [r for r in _fleet_rows(fleet)
            if r.get("event") == "overloaded"]
    assert rows and rows[-1]["detail"]["queue_bound"] == 4, rows
    # queues drained (the monkeypatch expires): admission recovers
    monkeypatch.undo()
    monkeypatch.setenv("YT_FLEET_MAX_QUEUE", "4")
    s = fleet.handle({"op": "open", **PROFILE})
    assert s.get("ok"), s
    assert fleet.handle({"op": "init", "sid": s["sid"]})["ok"]
    r = fleet.handle({"op": "run", "sid": s["sid"],
                      "first": 0, "last": 1})
    assert r.get("ok"), r
    fleet._saturation_sid = s["sid"]          # reused by the drain test


def test_stale_worker_excluded_and_surfaced(fleet, monkeypatch):
    """Satellite: a worker whose snapshot aged past 3 heartbeat
    intervals is excluded from the merged fold and listed in
    fleet_stats.stale_workers."""
    from tools.serve_fleet import FleetWorker
    m = fleet.collect_telemetry(block=True)    # banks fresh blocks
    assert m["stale_workers"] == []
    assert m["workers"]["w0"]["poll_age_secs"] == 0.0
    # age worker 1's bank past the horizon and make its poll fail
    with fleet._lock:
        fleet._snap_bank[1]["ts"] -= fleet._stale_after() + 60.0
    real_call = FleetWorker.call

    def flaky(self, op, on_stream=None, **kw):
        if op == "metrics_snapshot" and self.idx == 1:
            raise RuntimeError("injected poll failure")
        return real_call(self, op, on_stream=on_stream, **kw)

    monkeypatch.setattr(FleetWorker, "call", flaky)
    m2 = fleet.collect_telemetry(block=True)
    assert m2["stale_workers"] == ["w1"], m2["stale_workers"]
    assert m2["workers"]["w1"]["poll_age_secs"] > fleet._stale_after()
    monkeypatch.undo()
    fs = fleet.handle({"op": "fleet_stats"})
    assert fs["ok"] and fs["stale_workers"] == ["w1"], fs
    # the autoscaler sees one fresh worker only
    s = signals_from_snapshot(m2, n_workers=2)
    assert s.fresh_workers == 1 and s.stale_workers == ["w1"]
    # a fresh poll un-stales it
    m3 = fleet.collect_telemetry(block=True)
    assert m3["stale_workers"] == []


def test_scale_down_drains_and_migrates(fleet):
    """The drain path end-to-end: sessions on the retiring tail
    worker are checkpointed and migrated (zero lost), the journal
    carries drain + scale_down rows, and migrated sessions keep
    serving contiguous steps."""
    from yask_tpu.serve.autoscale import Decision
    # place a session on the tail worker (least-loaded admission;
    # worker 0 already owns the saturation test's session)
    s = fleet.handle({"op": "open", **PROFILE})
    assert s.get("ok"), s
    assert fleet.handle({"op": "init", "sid": s["sid"]})["ok"]
    r = fleet.handle({"op": "run", "sid": s["sid"],
                      "first": 0, "last": 1})
    assert r.get("ok"), r
    tail = fleet.workers[-1]
    victims = sorted(tail.sessions)
    assert victims, "expected at least one session on the tail worker"
    fleet._scale_down(Decision("down", "idle", {"test": True}))
    assert len(fleet.workers) == 1
    rows = _fleet_rows(fleet)
    drains = [r for r in rows if r.get("event") == "drain"]
    downs = [r for r in rows if r.get("event") == "scale_down"]
    assert drains and downs, (drains, downs)
    det = downs[-1]["detail"]
    assert sorted(det["migrated"]) == victims, det
    assert det["lost"] == [], det
    assert det["reason"] == "idle"
    # every migrated session keeps serving contiguous steps on the
    # survivor
    for sid in victims:
        nxt = 2 if sid == s["sid"] else 0
        rr = fleet.handle({"op": "run", "sid": sid,
                           "first": nxt, "last": nxt})
        assert rr.get("ok"), (sid, rr)
    fs = fleet.handle({"op": "fleet_stats"})
    assert fs["ok"] and len(fs["workers"]) == 1


def test_drain_chaos_aborts_without_losing_sessions(fleet,
                                                    monkeypatch):
    """An injected fleet.drain fault aborts the scale-down: the
    worker is un-marked, nothing migrates, nothing is lost."""
    from yask_tpu.serve.autoscale import Decision
    # grow back to 2 workers first (manual mechanism call)
    fleet._scale_up(Decision("up", "queue_depth", {"test": True}))
    assert len(fleet.workers) == 2
    ups = [r for r in _fleet_rows(fleet)
           if r.get("event") == "scale_up"]
    assert ups and ups[-1]["detail"]["reason"] == "queue_depth"
    monkeypatch.setenv("YT_FAULT_PLAN", "fleet.drain:backend_unavailable:1")
    reset_faults()
    before = {w.idx for w in fleet.workers}
    fleet._scale_down(Decision("down", "idle", {"test": True}))
    assert {w.idx for w in fleet.workers} == before
    assert not any(w.draining for w in fleet.workers)
    faults = [r for r in _fleet_rows(fleet)
              if r.get("event") == "fault"
              and r.get("detail", {}).get("site") == "fleet.drain"]
    assert faults, "aborted drain must journal a fault row"
    monkeypatch.delenv("YT_FAULT_PLAN")
    reset_faults()


# ------------------------------------------------------ load harness


def test_arrival_schedules_are_seeded_and_shaped():
    import random

    from tools.load_harness import arrivals
    a1 = arrivals("spike", 10.0, 1.0, random.Random(1))
    a2 = arrivals("spike", 10.0, 1.0, random.Random(1))
    assert a1 == a2 and len(a1) > 10
    p1 = arrivals("poisson", 20.0, 1.0, random.Random(2))
    assert all(0.0 <= t <= 1.0 for t in p1)
    s1 = arrivals("step", 10.0, 2.0, random.Random(3))
    first_half = sum(1 for t in s1 if t < 1.0)
    assert len(s1) - first_half > first_half  # rate doubles mid-run


def test_replay_reproduces_tenant_mix(fleet):
    """Replay derives (offset, tenant) pairs from recorded journal
    `received` rows — same tenants, same per-tenant request counts,
    order preserved."""
    from collections import Counter

    from tools.load_harness import replay_arrivals
    mix = Counter()
    paths = [w.journal_path for w in fleet.workers]
    for p in paths:
        for row in _rows(p):
            if row.get("event") == "received":
                mix[row["session"]] += 1
    assert mix, "fleet tests above should have recorded traffic"
    pairs = []
    for p in paths:
        pairs.extend(replay_arrivals(p))
    assert Counter(t for _off, t in pairs) == mix
    assert all(off >= 0.0 for off, _t in pairs)


@pytest.mark.slow
def test_soak_chaos_audit(tmp_path, capsys):
    """The composed chaos soak: spike + worker kill + hang + zero
    output under one seeded plan, gated on exactly-once + oracle
    bit-identity + quarantine-only anomaly release."""
    import argparse

    from tools.load_harness import run_soak
    args = argparse.Namespace(
        rate=8.0, duration=1.5, spike_mult=4.0, tenants=2, steps=2,
        flush_every=0, deadline=0.0, workers=2, seed=11,
        no_oracle=False)
    rc = run_soak(args, str(tmp_path))
    assert rc == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert 0.0 < out["summary"]["goodput"] <= 1.0


@pytest.mark.slow
def test_load_run_prints_latency_and_goodput(tmp_path, capsys):
    """A clean open-loop run audits against the oracle and prints its
    p50/p99/goodput summary as one JSON line."""
    import argparse

    from tools.load_harness import run_load
    args = argparse.Namespace(
        arrivals="poisson", rate=8.0, duration=1.0, spike_mult=4.0,
        tenants=2, steps=2, flush_every=0, deadline=0.0, workers=2,
        seed=7, replay="", replay_speed=1.0, no_oracle=False)
    rc = run_load(args, str(tmp_path))
    assert rc == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    s = out["summary"]
    assert {"p50_ms", "p99_ms", "goodput"} <= set(s)
    assert s["goodput"] >= 0.9 and s["p99_ms"] >= s["p50_ms"] > 0
