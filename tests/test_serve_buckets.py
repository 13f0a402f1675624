"""Serving v2: shape-bucket co-batching, chunked streaming responses,
and the scheduler edge cases around them.

The bucketing contract under test is BIT-identity, not tolerance: a
tenant opened at g rides a bucket profile at the next ladder rung as a
masked sub-domain, and every response (and every mid-run stream
snapshot) must equal the solo ``run_solution`` oracle at the tenant's
own geometry exactly.  The masked ensemble chunk keeps the step's
arithmetic behind an optimization barrier precisely so this holds —
see ``EnsembleRun._batched_chunk_fn``.

Everything runs on the CPU mesh; geometries are tiny (rung 16).
"""

import os
import threading
import time

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.resilience.faults import reset_faults
from yask_tpu.serve import (ServeJournal, ServeRequest, StencilServer,
                            bucket_cobatch_feasible, bucket_for,
                            bucket_ladder, plan_bucket)
from yask_tpu.serve.buckets import DEFAULT_LADDER
from yask_tpu.serve.scheduler import extract_outputs

STEPS = 4   # two wf=2 chunks


@pytest.fixture(autouse=True)
def _clean_fault_plan(monkeypatch):
    monkeypatch.delenv("YT_FAULT_PLAN", raising=False)
    reset_faults()
    yield
    reset_faults()


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


def mk_server(tmp_path, **kw):
    kw.setdefault("window_secs", 0.05)
    kw.setdefault("max_batch", 16)
    kw.setdefault("preflight", False)
    return StencilServer(journal_path=str(tmp_path / "SERVE.jsonl"),
                         **kw)


def solo_oracle(env, g, first, last, radius=1, stencil="iso3dfd"):
    """Lone run_solution at the tenant's exact geometry, standard
    init — the bit-identity target for bucket-hosted sessions."""
    from yask_tpu.runtime.init_utils import init_solution_vars
    ctx = yk_factory().new_solution(env, stencil=stencil, radius=radius)
    ctx.apply_command_line_options(f"-g {g} -wf_steps 2")
    ctx.get_settings().mode = "jit"
    ctx.prepare_solution()
    init_solution_vars(ctx)
    ctx.run_solution(first, last)
    return extract_outputs(ctx)


# ------------------------------------------------------------- planner

def test_ladder_default_and_override(monkeypatch):
    monkeypatch.delenv("YT_SERVE_BUCKETS", raising=False)
    assert bucket_ladder() == DEFAULT_LADDER
    assert bucket_for(12) == 16
    assert bucket_for(16) == 16
    assert bucket_for(17) == 24
    assert bucket_for(DEFAULT_LADDER[-1] + 1) is None
    monkeypatch.setenv("YT_SERVE_BUCKETS", "64, 8,32")
    assert bucket_ladder() == (8, 32, 64)
    assert bucket_for(9) == 32
    monkeypatch.setenv("YT_SERVE_BUCKETS", "not,numbers")
    assert bucket_ladder() == DEFAULT_LADDER


def test_plan_bucket_decisions(env):
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=1)
    ctx.apply_command_line_options("-g 12 -wf_steps 2")
    ctx.get_settings().mode = "jit"
    # feasibility works on the UNPREPARED probe (open_session decides
    # before paying a prepare at the wrong geometry)
    ok, why = bucket_cobatch_feasible(ctx)
    assert ok and why == ""

    d = plan_bucket(ctx, 12, requested=False)
    assert d.decision == "exact" and "not requested" in d.reason
    d = plan_bucket(ctx, 12, requested=True)
    assert d.decision == "bucketed" and d.bucket == 16 and d.g == 12
    d = plan_bucket(ctx, 16, requested=True)
    assert d.decision == "bucketed" and d.bucket == 16
    assert d.reason == "exact rung"
    d = plan_bucket(ctx, DEFAULT_LADDER[-1] + 8, requested=True)
    assert d.decision == "exact" and "overtops" in d.reason

    sh = yk_factory().new_solution(env, stencil="iso3dfd", radius=1)
    sh.apply_command_line_options("-g 12")
    sh.get_settings().mode = "sharded"
    d = plan_bucket(sh, 12, requested=True)
    assert d.decision == "declined" and d.reason

    swe = yk_factory().new_solution(env, stencil="swe2d", radius=None)
    swe.apply_command_line_options("-g 12")
    swe.get_settings().mode = "jit"
    d = plan_bucket(swe, 12, requested=True)
    assert d.decision == "declined" and "IF_DOMAIN" in d.reason


# --------------------------------------------------- bucketed serving

def test_bucketed_bit_identity_mixed_geometries(tmp_path, env):
    """Three tenants at three DISTINCT geometries on one rung ride ONE
    vmapped execution, each bit-identical to its solo oracle."""
    srv = mk_server(tmp_path)
    try:
        gs = (10, 12, 16)
        sids = []
        for g in gs:
            sid = srv.open_session(stencil="iso3dfd", radius=1, g=g,
                                   mode="jit", wf=2, bucket=True)
            b = srv.session_bucket(sid)
            assert b["decision"] == "bucketed" and b["bucket"] == 16, b
            srv.init_vars(sid)
            sids.append(sid)
        handles = [srv.submit_run(sid, 0, STEPS - 1) for sid in sids]
        resps = [srv.wait(h, timeout=240) for h in handles]
        assert all(r.ok for r in resps), [(r.status, r.error)
                                          for r in resps]
        assert max(r.batch for r in resps) == len(gs), \
            "mixed-geometry tenants did not co-batch"
        # batched= proves the vmapped executable ran (batch= alone is
        # only the intended width; a degrade must not pass silently)
        assert all(r.batched for r in resps if r.batch > 1), \
            "co-batched run degraded to sequential members"
        for g, r in zip(gs, resps):
            want = solo_oracle(env, g, 0, STEPS - 1)
            for name, a in want.items():
                assert r.outputs[name].shape == a.shape
                assert np.array_equal(r.outputs[name], a), \
                    f"g={g} var {name} not bit-identical to solo"
        # the bucketing verdict rides the journal's batched row
        rows = ServeJournal(str(tmp_path / "SERVE.jsonl")).rows()
        batched = [r for r in rows if r["event"] == "batched"]
        assert any(r["detail"].get("bucket", {}).get("decision")
                   == "bucketed" for r in batched)
    finally:
        srv.shutdown()


def test_bucket_decline_serves_exact(tmp_path, env):
    """swe2d carries IF_DOMAIN conditions: bucketing is DECLINED with a
    structured reason and the session still answers, hosted exact."""
    srv = mk_server(tmp_path)
    try:
        sid = srv.open_session(stencil="swe2d", radius=None, g=12,
                               mode="jit", wf=2, bucket=True)
        b = srv.session_bucket(sid)
        assert b["decision"] == "declined"
        assert "IF_DOMAIN" in b["reason"]
        srv.init_vars(sid)
        r = srv.run(sid, 0, STEPS - 1, timeout=240)
        assert r.ok
        want = solo_oracle(env, 12, 0, STEPS - 1, radius=None,
                           stencil="swe2d")
        for name, a in want.items():
            assert np.array_equal(r.outputs[name], a)
    finally:
        srv.shutdown()


def test_set_var_and_read_on_bucketed_session(tmp_path, env):
    """User fills against a bucket-hosted session address the tenant's
    interior coordinates (low-corner anchoring) and round-trip."""
    g = 12
    srv = mk_server(tmp_path)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=g,
                               mode="jit", wf=2, bucket=True)
        srv.init_vars(sid)
        rng = np.random.RandomState(7)
        seed = (rng.rand(1, g, g, g).astype(np.float32) - 0.5) * 0.1
        with srv.scheduler.session_ctx(sid) as ctx:
            ctx.get_var("pressure").set_elements_in_slice(
                seed, [0, 0, 0, 0], [0, g - 1, g - 1, g - 1])
            back = np.asarray(ctx.get_var("pressure")
                              .get_elements_in_slice(
                                  [0, 0, 0, 0], [0, g - 1, g - 1, g - 1]))
        assert np.array_equal(back, seed[0])

        from yask_tpu.runtime.init_utils import init_solution_vars
        ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=1)
        ctx.apply_command_line_options(f"-g {g} -wf_steps 2")
        ctx.get_settings().mode = "jit"
        ctx.prepare_solution()
        init_solution_vars(ctx)
        ctx.get_var("pressure").set_elements_in_slice(
            seed, [0, 0, 0, 0], [0, g - 1, g - 1, g - 1])
        ctx.run_solution(0, STEPS - 1)
        want = extract_outputs(ctx)

        r = srv.run(sid, 0, STEPS - 1, timeout=240)
        assert r.ok
        for name, a in want.items():
            assert np.array_equal(r.outputs[name], a), name
    finally:
        srv.shutdown()


@pytest.mark.parametrize("g", [12, 16])
def test_a_sub_domain_answer_is_not_recorded(g, tmp_path):
    """A bucketed tenant's low-corner cut is not the interior of the
    array it was cut from: the run state's record of pulls takes an
    answer only where the tenant's domain is the bucket's whole one,
    and the next rollback snapshot of a sub-domain tenant pulls its
    newest slot like the others."""
    srv = mk_server(tmp_path, window_secs=0.0)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=g,
                               mode="jit", wf=2, bucket=True)
        assert srv.session_bucket(sid)["bucket"] == 16
        srv.init_vars(sid)
        r = srv.run(sid, 0, STEPS - 1, timeout=240)
        assert r.ok and r.outputs["pressure"].shape == (g, g, g)
        with srv.scheduler.session_ctx(sid) as ctx:
            ring = ctx._state["pressure"]
            held = ctx.get_run_state().recall_pull(
                "pressure", len(ring) - 1, ring[-1])
        if g == 16:
            assert held is r.outputs["pressure"]
        else:
            assert held is None
        snap = srv.snapshot(sid)
        newest = 16 ** 3 * 4
        assert snap["reused_bytes"] == (newest if g == 16 else 0)
    finally:
        srv.shutdown()


def test_a_streamed_flush_is_the_next_chunks_rollback_target(tmp_path,
                                                             env):
    """The field a flush pulled whole is the newest slot of the
    snapshot the next chunk takes: it crosses once."""
    srv = mk_server(tmp_path, window_secs=0.0)
    interior = 16 ** 3 * 4
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=16,
                               mode="jit", wf=2)
        srv.init_vars(sid)
        r = srv.wait(srv.submit(ServeRequest(
            session=sid, first_step=0, last_step=5, flush_every=2,
            stream_outputs=True)), timeout=240)
        c = srv.metrics()["registry"]["counters"]
    finally:
        srv.shutdown()
    assert r.ok and [ev["step"] for ev in r.streams] == [1, 3]
    # three chunks: the first snapshot pulls the ring (3), the other
    # two the older slot and vel (the jit chunk hands vel back a new
    # object); two flushes and the answer pull the newest slot
    assert c["serve.snapshot.reused_bytes"] == 2 * interior
    assert c["serve.d2h_bytes"] == (3 + 2 + 2 + 3) * interior
    want = solo_oracle(env, 16, 0, 5)
    assert np.array_equal(r.outputs["pressure"], want["pressure"])
    mid = solo_oracle(env, 16, 0, 3)
    assert np.array_equal(r.streams[1]["outputs"]["pressure"],
                          mid["pressure"])


# ------------------------------------------------ streaming/preemption

def test_streaming_flush_and_preemption_bit_identity(tmp_path, env):
    """A long streamed run flushes partial results at chunk boundaries,
    yields to a short request between chunks, and still finishes
    bit-identical to the uninterrupted solo oracle — including every
    mid-run snapshot."""
    srv = mk_server(tmp_path)
    try:
        long_sid = srv.open_session(stencil="iso3dfd", radius=1, g=16,
                                    mode="jit", wf=2)
        short_sid = srv.open_session(stencil="iso3dfd", radius=1, g=10,
                                     mode="jit", wf=2)
        for s in (long_sid, short_sid):
            srv.init_vars(s)
        seen = []
        h_long = srv.submit(
            ServeRequest(session=long_sid, first_step=0,
                         last_step=7, flush_every=2,
                         stream_outputs=True),
            on_stream=lambda ev: seen.append(ev))
        h_short = srv.submit_run(short_sid, 0, 0)
        r_long = srv.wait(h_long, timeout=240)
        r_short = srv.wait(h_short, timeout=240)
        assert r_long.ok and r_short.ok
        assert r_long.preempted >= 1, "long run never yielded"
        steps_flushed = [ev["step"] for ev in r_long.streams]
        assert steps_flushed == [1, 3, 5]
        assert [ev["step"] for ev in seen] == steps_flushed, \
            "on_stream hook missed flushes"

        want = solo_oracle(env, 16, 0, 7)
        for name, a in want.items():
            assert np.array_equal(r_long.outputs[name], a), \
                f"{name} diverged after chunking + preemption"
        mid = solo_oracle(env, 16, 0, 3)
        for name, a in mid.items():
            assert np.array_equal(r_long.streams[1]["outputs"][name],
                                  a), f"mid-run snapshot {name} diverged"

        rows = ServeJournal(str(tmp_path / "SERVE.jsonl")).rows()
        events = {r["event"] for r in rows}
        assert "stream" in events and "preempted" in events
    finally:
        srv.shutdown()


def test_flush_fault_is_nonfatal(tmp_path, monkeypatch):
    """An injected fault at serve.flush costs the beacon, not the run."""
    monkeypatch.setenv("YT_FAULT_PLAN", "serve.flush:backend_unavailable:1")
    reset_faults()
    srv = mk_server(tmp_path)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=10,
                               mode="jit", wf=2)
        srv.init_vars(sid)
        r = srv.run(sid, 0, 7, flush_every=2, stream_outputs=False,
                    timeout=240)
        assert r.ok, (r.status, r.error)
        # one flush was eaten by the fault, the rest arrived
        assert len(r.streams) < 3
        rows = ServeJournal(str(tmp_path / "SERVE.jsonl")).rows()
        faults = [x for x in rows if x["event"] == "fault"
                  and x["detail"].get("nonfatal")]
        assert faults and faults[0]["detail"]["site"] == "serve.flush"
    finally:
        srv.shutdown()


# ---------------------------------------------- scheduler edge cases

def test_window_zero_runs_solo_without_waiting(tmp_path):
    """window=0 (YT_SERVE_WINDOW_MS=0): no co-batching wait — the head
    request launches immediately as an occupancy-1 run."""
    srv = mk_server(tmp_path, window_secs=0.0)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=10,
                               mode="jit", wf=2)
        srv.init_vars(sid)
        t0 = time.perf_counter()
        r = srv.run(sid, 0, STEPS - 1, timeout=240)
        assert r.ok and r.batch == 1 and not r.batched
        assert time.perf_counter() - t0 < 60
    finally:
        srv.shutdown()


def test_batch_cap_overflow_splits(tmp_path, env):
    """More compatible tenants than max_batch: the scheduler splits
    into capped batches and every request still answers exactly."""
    srv = mk_server(tmp_path, max_batch=2, window_secs=0.2)
    try:
        sids = []
        for _ in range(5):
            sid = srv.open_session(stencil="iso3dfd", radius=1, g=10,
                                   mode="jit", wf=2)
            srv.init_vars(sid)
            sids.append(sid)
        handles = [srv.submit_run(sid, 0, STEPS - 1) for sid in sids]
        resps = [srv.wait(h, timeout=240) for h in handles]
        assert all(r.ok for r in resps)
        assert max(r.batch for r in resps) <= 2
        assert any(r.batch == 2 for r in resps), \
            "cap never filled — splitting untested"
        want = solo_oracle(env, 10, 0, STEPS - 1)
        for r in resps:
            for name, a in want.items():
                assert np.array_equal(r.outputs[name], a)
    finally:
        srv.shutdown()


def test_shutdown_with_queued_requests_rejects_terminal(tmp_path):
    """Shutdown with a queue: every pending request resolves to a
    terminal rejected response — wait() never hangs."""
    srv = mk_server(tmp_path, window_secs=5.0)
    sid = srv.open_session(stencil="iso3dfd", radius=1, g=10,
                           mode="jit", wf=2)
    srv.init_vars(sid)
    handles = [srv.submit_run(sid, i, i) for i in range(3)]
    # shut down from a side thread while they sit in the window
    t = threading.Thread(target=srv.shutdown)
    t.start()
    resps = [srv.wait(h, timeout=60) for h in handles]
    t.join(timeout=60)
    assert not t.is_alive()
    for r in resps:
        assert r.status in ("rejected", "ok"), r.status
        if r.status == "rejected":
            assert "shut down" in r.error
    assert any(r.status == "rejected" for r in resps)
    # journal rows are terminal for every request
    from yask_tpu.serve import SERVE_TERMINAL
    rows = ServeJournal(str(tmp_path / "SERVE.jsonl")).rows()
    terminal = {r["rid"] for r in rows if r["event"] in SERVE_TERMINAL}
    assert {p.rid for p in handles} <= terminal

    # post-shutdown submits reject immediately (no hang either)
    h = srv.submit_run(sid, 10, 10)
    r = srv.wait(h, timeout=10)
    assert r.status == "rejected" and "shut down" in r.error


def test_bucket_hosted_session_does_not_degrade(tmp_path, monkeypatch):
    """A fault on a masked sub-domain run REJECTS instead of degrading:
    mode degradation would silently abandon the bucket geometry."""
    monkeypatch.setenv("YT_FAULT_PLAN", "serve.run:compile_failed:9")
    reset_faults()
    srv = mk_server(tmp_path)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=1, g=10,
                               mode="jit", wf=2, bucket=True)
        assert srv.session_bucket(sid)["decision"] == "bucketed"
        srv.init_vars(sid)
        r = srv.run(sid, 0, STEPS - 1, timeout=240)
        assert r.status == "rejected"
        assert "bucket-hosted" in r.error
        assert not r.degraded
    finally:
        srv.shutdown()


# ------------------------------------------------------------- checker

def test_checker_serve_bucket_rule(env):
    from yask_tpu.checker import run_checks
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=1)
    ctx.apply_command_line_options("-g 16 -wf_steps 2 -serve")
    ctx.get_settings().mode = "jit"
    rep = run_checks(ctx, passes=("serve",))
    found = [d for d in rep.diagnostics
             if d.rule == "SERVE-BUCKET-INELIGIBLE"]
    assert found and found[0].severity == "info"
    assert found[0].detail["rung"] == {"x": 16, "y": 16, "z": 16}

    swe = yk_factory().new_solution(env, stencil="swe2d", radius=None)
    swe.apply_command_line_options("-g 16 -wf_steps 2 -serve")
    swe.get_settings().mode = "jit"
    rep = run_checks(swe, passes=("serve",))
    found = [d for d in rep.diagnostics
             if d.rule == "SERVE-BUCKET-INELIGIBLE"]
    assert found and "IF_DOMAIN" in found[0].message
