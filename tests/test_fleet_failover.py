"""Fleet supervision acceptance (tools/serve_fleet.py): worker health,
crash-restart, and checkpoint-backed session failover.

The chaos story, driven end-to-end on the CPU mesh:

* ``fleet.kill_worker`` (``YT_FAULT_PLAN`` in the worker's env)
  hard-exits the worker at the SECOND chunk-boundary flush of a
  streaming run — a mid-op crash with one stream line already
  delivered;
* the front detects the EOF, SIGKILLs the worker group, spawns a
  replacement warm-started from the shared compile cache, re-opens +
  restores the session from the last banked checkpoint, replays the
  committed ops past that boundary, and re-issues the in-flight run
  EXACTLY ONCE under its idempotency key;
* every response is bit-identical to an uninterrupted single-worker
  twin, and ``SERVE_JOURNAL.fleet.jsonl`` carries the ``worker_dead``
  → ``failover`` (dead worker id, snapshot step, replayed ranges) →
  ``retry`` trail;
* front-side ``fleet.heartbeat`` drops drive the miss-threshold
  unhealthy path into the same failover without any crash.

One module-scoped scenario amortizes the four worker-interpreter
spawns (the chaos worker, its two replacements, the twin) across every
assertion here.  Also wired into ``make faultcheck``.
"""

import os

import numpy as np
import pytest

from tools.serve_fleet import (ServeFleet, fleet_ckpt_every,
                               fleet_hb_deadline, fleet_hb_misses)
from yask_tpu.resilience.faults import reset_faults


@pytest.fixture(autouse=True)
def _fresh_faults():
    reset_faults()
    yield
    reset_faults()


def _run(fleet, sid, first, last, **extra):
    lines = []
    msg = {"op": "run", "sid": sid, "first": first, "last": last,
           **extra}
    r = fleet.handle(msg, emit=lines.append)
    return r, lines, msg


def _evs(rows, event):
    return [r for r in rows if r["event"] == event]


@pytest.fixture(scope="module")
def scenario(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("failover")
    (tmp / "A").mkdir()
    (tmp / "B").mkdir()
    saved = {}
    env = {"JAX_PLATFORMS": "cpu"}
    for k, v in env.items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    reset_faults()

    # Worker-side kill plan — hits in the chaos worker's process:
    # run1 entry (1), run2 entry (2), run2 flush 1 (3, passes — its
    # stream line escapes: the at-least-once evidence), run2 flush 2
    # (4) → os._exit(17) mid-op.
    chaos_env = dict(os.environ)
    chaos_env["YT_FAULT_PLAN"] = "fleet.kill_worker:worker_dead:1:3"
    wargs = ["--no-preflight", "--window_ms", "5"]
    art = {}
    fl = ServeFleet(n_workers=1, cache_dir=str(tmp / "cache"),
                    journal_dir=str(tmp / "A"), worker_args=wargs,
                    env=chaos_env)
    # replacements must spawn WITHOUT the kill plan (a fresh process
    # would re-fire it and the single retry could never land)
    fl._base_env.pop("YT_FAULT_PLAN")
    tw = ServeFleet(n_workers=1, cache_dir=str(tmp / "cache"),
                    journal_dir=str(tmp / "B"), worker_args=wargs)
    try:
        sids = {}
        for key, f in (("a", fl), ("b", tw)):
            o = f.handle({"op": "open", "stencil": "iso3dfd",
                          "radius": 1, "g": 8, "wf": 2})
            assert o["ok"], o
            assert f.handle({"op": "init", "sid": o["sid"]})["ok"]
            sids[key] = o["sid"]
        art["sid"] = sids["a"]
        art["gen0"] = fl.workers[0]

        # run 1 (steps 0..3): committed via the pre-run snapshot @0
        for key, f in (("a", fl), ("b", tw)):
            r, _, _ = _run(f, sids[key], 0, 3)
            assert r["ok"], r

        # run 2 (steps 4..9, streaming): the chaos worker dies at the
        # second flush; the front must fail over and answer anyway
        art["r2a"], art["streams_a"], msg2 = _run(
            fl, sids["a"], 4, 9, flush_every=2)
        art["idem2"] = msg2.get("idem")
        art["gen1"] = fl.workers[0]
        art["r2b"], art["streams_b"], _ = _run(
            tw, sids["b"], 4, 9, flush_every=2)

        # run 3 (steps 10..11): service continues on the replacement
        art["r3a"], _, _ = _run(fl, sids["a"], 10, 11)
        art["r3b"], _, _ = _run(tw, sids["b"], 10, 11)

        # heartbeat drops (front-side site) → unhealthy → replaced
        os.environ["YT_FAULT_PLAN"] = "fleet.heartbeat:backend_unavailable:2"
        reset_faults()
        try:
            fl.supervise_tick()
            art["after_tick1"] = (fl.workers[0],
                                  fl.workers[0].hb_misses)
            fl.supervise_tick()
            art["after_tick2"] = fl.workers[0]
        finally:
            del os.environ["YT_FAULT_PLAN"]
            reset_faults()

        # run 4 (steps 12..13): service continues on the 2nd repl
        art["r4a"], _, _ = _run(fl, sids["a"], 12, 13)
        art["r4b"], _, _ = _run(tw, sids["b"], 12, 13)

        art["cache0"] = fl.handle({"op": "cache_stats"})["stats"]["0"]
        art["jrows"] = fl.journal.rows()
        art["twin_jrows"] = tw.journal.rows()
        yield art
    finally:
        fl.close()
        tw.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        reset_faults()


# ------------------------------------------------- failover acceptance

def test_crash_failover_is_bit_identical_to_twin(scenario):
    a, b = scenario["r2a"], scenario["r2b"]
    assert a["ok"], a
    assert b["ok"], b
    assert a["outputs"], "run answered without outputs"
    for name in b["outputs"]:
        x = np.asarray(a["outputs"][name]["data"])
        y = np.asarray(b["outputs"][name]["data"])
        assert np.array_equal(x, y), \
            f"{name}: failed-over run diverged from uninterrupted twin"
    # and the sessions stay bit-identical through later steps on BOTH
    # replacements (post-crash and post-heartbeat-failover)
    for ra, rb in ((scenario["r3a"], scenario["r3b"]),
                   (scenario["r4a"], scenario["r4b"])):
        assert ra["ok"] and rb["ok"], (ra, rb)
        for name in rb["outputs"]:
            assert np.array_equal(
                np.asarray(ra["outputs"][name]["data"]),
                np.asarray(rb["outputs"][name]["data"])), name
    # the twin never failed over
    twin_events = {r["event"] for r in scenario["twin_jrows"]}
    assert not twin_events & {"worker_dead", "failover", "retry"}


def test_failover_journal_trail(scenario):
    sid = scenario["sid"]
    rows = scenario["jrows"]
    dead = _evs(rows, "worker_dead")
    assert len(dead) == 2, dead
    assert dead[0]["rid"] == "w0.g0"
    assert dead[0]["detail"]["worker"] == 0
    assert dead[0]["detail"]["sessions"] == [sid]
    assert dead[1]["rid"] == "w0.g1"
    assert "missed 2 heartbeats" in dead[1]["detail"]["cause"]

    fo = _evs(rows, "failover")
    assert len(fo) == 2, fo
    assert all(r["rid"] == sid for r in fo)
    # crash failover: restored from the pre-run snapshot @0, replayed
    # the committed run 1 (0..3); the in-flight run 2 is NOT replay —
    # it is the exactly-once retry
    assert fo[0]["detail"]["dead_worker"] == 0
    assert fo[0]["detail"]["dead_gen"] == 0
    assert fo[0]["detail"]["to_gen"] == 1
    assert fo[0]["detail"]["snapshot_step"] == 0
    assert fo[0]["detail"]["replayed"] == [[0, 3]]
    # heartbeat failover: the cadence snapshot @10 (banked once run 2
    # pushed the session past YT_FLEET_CKPT_EVERY=8 steps) bounds the
    # replay to run 3 alone
    assert fo[1]["detail"]["dead_gen"] == 1
    assert fo[1]["detail"]["to_gen"] == 2
    assert fo[1]["detail"]["snapshot_step"] == 10
    assert fo[1]["detail"]["replayed"] == [[10, 11]]

    snaps = _evs(rows, "snapshot")
    assert {r["detail"]["step"] for r in snaps} >= {0, 10}, snaps


def test_inflight_retry_exactly_once(scenario):
    rows = scenario["jrows"]
    retries = _evs(rows, "retry")
    assert len(retries) == 1, retries     # re-issued exactly once
    d = retries[0]["detail"]
    assert d["op"] == "run"
    assert d["idem"] == scenario["idem2"]  # the SAME stamped key
    assert d["worker"] == 0 and d["gen"] == 1
    # streams are at-least-once across the failover: the flush line
    # that escaped before the kill repeats when the retry re-runs the
    # chunk; the step SET still matches the twin exactly
    steps_a = [ln["step"] for ln in scenario["streams_a"]]
    steps_b = [ln["step"] for ln in scenario["streams_b"]]
    assert sorted(set(steps_a)) == sorted(set(steps_b))
    assert len(set(steps_b)) == len(steps_b)   # twin: each step once
    assert len(steps_a) == len(steps_b) + 1    # one duplicated line
    assert steps_a.count(steps_b[0]) == 2      # ... the pre-kill flush


def test_heartbeat_miss_threshold_replaces_worker(scenario):
    w1, misses1 = scenario["after_tick1"]
    assert w1 is scenario["gen1"]          # first miss: counted only
    assert misses1 == 1
    w2 = scenario["after_tick2"]
    assert w2 is not scenario["gen1"]      # threshold: replaced
    assert w2.gen == 2


def test_replacement_warm_starts_from_shared_cache(scenario):
    # the gen-2 replacement replayed run 3 and served run 4 entirely
    # off the shared disk cache — zero fresh lowerings
    cs = scenario["cache0"]
    assert cs["lowerings"] == 0, cs
    assert cs["disk_hits"] > 0, cs


# ------------------------------------------------------ cheap units

def test_worker_fault_kinds(monkeypatch):
    from yask_tpu.resilience.faults import (FAULT_KINDS, WorkerDead,
                                            WorkerUnhealthy,
                                            fault_point)
    assert "worker_dead" in FAULT_KINDS
    assert "worker_unhealthy" in FAULT_KINDS
    monkeypatch.setenv("YT_FAULT_PLAN",
                       "k:worker_dead; u:worker_unhealthy")
    reset_faults()
    with pytest.raises(WorkerDead) as ei:
        fault_point("k")
    assert ei.value.kind == "worker_dead" and ei.value.site == "k"
    with pytest.raises(WorkerUnhealthy):
        fault_point("u")


def test_fleet_env_knobs(monkeypatch):
    monkeypatch.setenv("YT_FLEET_CKPT_EVERY", "3")
    assert fleet_ckpt_every() == 3
    monkeypatch.setenv("YT_FLEET_CKPT_EVERY", "junk")
    assert fleet_ckpt_every() == 8                 # bad value: default
    monkeypatch.setenv("YT_FLEET_HB_DEADLINE", "0.01")
    assert fleet_hb_deadline() == 0.1              # floored
    monkeypatch.setenv("YT_FLEET_HB_MISSES", "0")
    assert fleet_hb_misses() == 1                  # floored
