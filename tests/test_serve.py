"""The serving layer (yask_tpu/serve/): multi-tenant correctness,
dynamic micro-batching, fault degradation, sanity quarantine, warm
restart, and the journal/checker/wire plumbing around them.

The acceptance contract (tier-1 on purpose, like the resilience
acceptance tests): a server hosting two DISTINCT prepared stencils
answers 8+ concurrent tenant requests where (a) every response is
bit-identical to a solo ``run_solution`` oracle, (b) the journal
shows batch occupancy > 1, and (c) a warm-restarted server's first
request costs zero lowerings.  Everything runs on the CPU mesh; the
faults are injected (``YT_FAULT_PLAN``), so the machinery that keeps
tenants alive on flaky hardware is tested without hardware.
"""

import os
import subprocess
import threading

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.resilience.faults import reset_faults
from yask_tpu.serve import (SERVE_SCHEMA, SERVE_TERMINAL, ServeJournal,
                            ServeRequest, StencilServer)
from yask_tpu.serve.scheduler import extract_outputs
from yask_tpu.utils.exceptions import YaskException

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G = 16        # iso3dfd domain edge
G2 = 32       # wave2d domain edge
STEPS = 4     # two wf=2 chunks


@pytest.fixture(autouse=True)
def _clean_fault_plan(monkeypatch):
    monkeypatch.delenv("YT_FAULT_PLAN", raising=False)
    reset_faults()
    yield
    reset_faults()


@pytest.fixture()
def server(tmp_path):
    srv = StencilServer(journal_path=str(tmp_path / "SERVE.jsonl"),
                        window_secs=0.05, max_batch=16,
                        preflight=False)
    yield srv
    srv.shutdown()


def iso_seed(i):
    rng = np.random.RandomState(100 + i)
    return (rng.rand(1, G, G, G).astype(np.float32) - 0.5) * 0.1


def wave_seed(i):
    rng = np.random.RandomState(200 + i)
    return (rng.rand(1, G2, G2).astype(np.float32) - 0.5) * 0.1


def fill_iso(fill_var, fill_slice, i):
    fill_var("vel", 0.5)
    fill_slice("pressure", iso_seed(i),
               [0, 0, 0, 0], [0, G - 1, G - 1, G - 1])


def fill_wave(fill_var, fill_slice, i):
    fill_var("c2", 0.2)
    fill_slice("u", wave_seed(i), [0, 0, 0], [0, G2 - 1, G2 - 1])


PROFILES = {
    "iso3dfd": dict(stencil="iso3dfd", radius=2, g=G, filler=fill_iso),
    "wave2d": dict(stencil="wave2d", radius=2, g=G2, filler=fill_wave),
}


def open_and_fill(srv, name, i, mode="jit"):
    p = PROFILES[name]
    sid = srv.open_session(stencil=p["stencil"], radius=p["radius"],
                           g=p["g"], mode=mode, wf=2)
    with srv.scheduler.session_ctx(sid) as ctx:
        p["filler"](
            lambda v, x: ctx.get_var(v).set_all_elements_same(x),
            lambda v, a, f, l: ctx.get_var(v).set_elements_in_slice(
                a, f, l),
            i)
    return sid


def solo_oracle(env, name, i, first=0, last=STEPS - 1, mode="jit"):
    """What a lone run_solution produces for the same fills."""
    p = PROFILES[name]
    ctx = yk_factory().new_solution(env, stencil=p["stencil"],
                                    radius=p["radius"])
    ctx.apply_command_line_options(f"-g {p['g']} -wf_steps 2")
    ctx.get_settings().mode = mode
    ctx.prepare_solution()
    p["filler"](
        lambda v, x: ctx.get_var(v).set_all_elements_same(x),
        lambda v, a, f, l: ctx.get_var(v).set_elements_in_slice(a, f, l),
        i)
    ctx.run_solution(first, last)
    return extract_outputs(ctx)


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


# ------------------------------------------------------------ acceptance

def test_acceptance_concurrent_two_stencils(server, env):
    """Two distinct prepared stencils, 8 concurrent tenant threads,
    every answer bit-identical to solo run_solution, occupancy > 1."""
    tenants = [("iso3dfd", i) for i in range(4)] + \
              [("wave2d", i) for i in range(4)]
    sids = [open_and_fill(server, name, i) for name, i in tenants]

    resps = {}

    def go(sid):
        resps[sid] = server.request(
            ServeRequest(session=sid, first_step=0,
                         last_step=STEPS - 1), timeout=600)

    threads = [threading.Thread(target=go, args=(sid,))
               for sid in sids]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for (name, i), sid in zip(tenants, sids):
        r = resps[sid]
        assert r.ok, f"{name}#{i}: {r.status} {r.error}"
        want = solo_oracle(env, name, i)
        assert set(want) == set(r.outputs)
        for var in want:
            assert np.array_equal(want[var], r.outputs[var]), \
                f"{name}#{i} var {var} not bit-identical to solo oracle"

    # the journal must prove requests actually co-batched
    assert server.journal.max_occupancy() > 1
    m = server.metrics()
    assert m["completed"] == 8 and m["ok"] == 8
    assert m["batch_occupancy_max"] > 1
    assert m["profiles"] == 2 and m["sessions"] == 8


def test_acceptance_warm_restart_zero_lowerings(tmp_path, monkeypatch):
    """A restarted server answers its first request without lowering
    anything: the AOT disk cache is the warm-start story."""
    from yask_tpu.cache import clear_memo, reset_stats, stats
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path / "cache"))

    def one_round():
        srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                            window_secs=0.0, preflight=False)
        sid = open_and_fill(srv, "iso3dfd", 0)
        r = srv.run(sid, 0, STEPS - 1, timeout=600)
        srv.shutdown()
        return r

    clear_memo()            # cold start: no memo leakage from other
    reset_stats()           # tests, so round 1 populates the disk
    r1 = one_round()
    assert r1.ok
    clear_memo()            # simulate process restart: memo gone,
    reset_stats()           # disk cache stays
    r2 = one_round()
    assert r2.ok
    assert stats()["lowerings"] == 0, \
        "warm-restarted server lowered something on its first request"
    assert r2.cache_hit == "disk"
    for var in r1.outputs:
        assert np.array_equal(r1.outputs[var], r2.outputs[var])


def test_threads_vs_sequential_bit_identity(server, env):
    """N tenant threads against ONE registry produce exactly the bits
    of N sequential solo runs — concurrency must be invisible."""
    n = 5
    sids = [open_and_fill(server, "iso3dfd", i) for i in range(n)]
    resps = {}

    def go(sid):
        resps[sid] = server.run(sid, 0, STEPS - 1, timeout=600)

    threads = [threading.Thread(target=go, args=(s,)) for s in sids]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, sid in enumerate(sids):
        want = solo_oracle(env, "iso3dfd", i)
        assert resps[sid].ok
        for var in want:
            assert np.array_equal(want[var], resps[sid].outputs[var])


# ------------------------------------------------------- fault handling

def test_injected_fault_degrades_session(tmp_path, monkeypatch, env):
    """A classified device fault at serve.run walks the tenant down
    the PR 9 degradation ladder: the tenant gets a degraded-mode
    ANSWER (bit-identical to the rung's solo oracle), not an error,
    and the journal records the fault + the rung."""
    monkeypatch.setenv("YT_FAULT_PLAN", "serve.run:device_hang:1")
    reset_faults()
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = open_and_fill(srv, "iso3dfd", 0, mode="pallas")
        r = srv.run(sid, 0, STEPS - 1, timeout=600)
        assert r.ok, f"{r.status}: {r.error}"
        assert r.degraded and r.mode == "jit"
        assert srv.session_mode(sid) == "jit"
        events = [e["event"] for e in srv.journal.events(r.rid)]
        assert events == ["received", "batched", "fault", "degraded",
                          "ok"]
        want = solo_oracle(env, "iso3dfd", 0, mode="jit")
        for var in want:
            assert np.array_equal(want[var], r.outputs[var])
    finally:
        srv.shutdown()


def test_fault_every_rung_rejects_with_exhausted_ladder(tmp_path,
                                                        monkeypatch):
    """When every rung faults too, the tenant gets a structured
    rejection (never a hang, never an unclassified traceback)."""
    monkeypatch.setenv("YT_FAULT_PLAN", "serve.run:device_hang:99")
    reset_faults()
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = open_and_fill(srv, "iso3dfd", 0, mode="pallas")
        r = srv.run(sid, 0, STEPS - 1, timeout=600)
        assert r.status == "rejected"
        assert "device_hang" in r.error
        assert srv.journal.terminal(r.rid) == "rejected"
    finally:
        srv.shutdown()


def test_sanity_quarantine_on_corrupt_output(tmp_path, monkeypatch):
    """An all-zero answer is released FLAGGED (status anomaly), never
    banked clean — the round-3 incident, applied to serving."""
    monkeypatch.setenv("YT_FAULT_PLAN", "serve.respond:zero_output:1")
    reset_faults()
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = open_and_fill(srv, "iso3dfd", 0)
        r = srv.run(sid, 0, STEPS - 1, timeout=600)
        assert r.status == "anomaly" and not r.ok
        assert "all_zero" in r.anomaly["anomalies"]
        assert float(np.abs(r.outputs["pressure"]).max()) == 0.0
        assert srv.journal.terminal(r.rid) == "anomaly"
        assert srv.metrics()["anomalies"] == 1
    finally:
        srv.shutdown()


def _poison(value):
    def corrupt(site, outs):
        assert site == "serve.respond"
        bad = {k: np.array(a, copy=True) for k, a in outs.items()}
        bad["pressure"][1, 2, 3] = value
        return bad
    return corrupt


_RESPOND_CASES = {
    # id: (fault plan, stand-in for maybe_corrupt, anomalies, exact)
    "clean": ("", None, [], 0),
    "zero": ("serve.respond:zero_output:1", None, ["all_zero"], 0),
    "nan": ("serve.respond:nan_output:1", None, ["nonfinite"], 1),
    "pinf": ("", _poison(np.inf), ["nonfinite"], 1),
    "ninf": ("", _poison(-np.inf), ["nonfinite"], 1),
}


@pytest.mark.parametrize("case", sorted(_RESPOND_CASES))
def test_released_verdict_is_of_the_host_bytes_and_says_its_path(
        case, tmp_path, monkeypatch):
    """The verdict is of the host bytes the tenant receives, after
    ``maybe_corrupt("serve.respond")``: a NaN, an Inf or an all-zero
    field injected there is released as ``anomaly`` with the fields a
    float64 recomputation of those bytes gives.  ``serve.sanity.exact``
    and the span's ``exact`` say which requests took the exact scan:
    the non-finite ones only."""
    from yask_tpu.obs import tracer
    from yask_tpu.resilience import faults
    plan, stand_in, anomalies, exact = _RESPOND_CASES[case]
    trace_file = tmp_path / "TRACE_EVENTS.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(trace_file))
    monkeypatch.setenv("YT_TRACE", "1")
    if plan:
        monkeypatch.setenv("YT_FAULT_PLAN", plan)
        reset_faults()
    if stand_in:
        monkeypatch.setattr(faults, "maybe_corrupt", stand_in)
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = open_and_fill(srv, "iso3dfd", 0)
        r = srv.run(sid, 0, STEPS - 1, timeout=600)
        counters = srv.metrics()["registry"]["counters"]
    finally:
        srv.shutdown()
    assert r.status == ("anomaly" if anomalies else "ok")
    assert counters["serve.sanity.exact"] == exact
    assert counters[f"serve.requests.{r.status}"] == 1
    span, = [s for s in tracer.read_spans(str(trace_file))
             if s["name"] == "serve.sanity"]
    assert span["attrs"]["exact"] == exact
    if not anomalies:
        assert r.anomaly == {}
        return
    got = np.asarray(r.outputs["pressure"], dtype=np.float64)
    finite = np.isfinite(got)
    assert r.anomaly == {
        "classification": "ANOMALY", "anomalies": anomalies,
        "zero_frac": round(float((got == 0).mean()), 6),
        "nonfinite_frac": round(float(1 - finite.mean()), 6),
        "max_abs": round(float(np.abs(got[finite]).max())
                         if finite.any() else 0.0, 6)}


# ------------------------------------------- what a request pulls

def _pull_counters(srv):
    c = srv.metrics()["registry"]["counters"]
    return (c.get("serve.d2h_bytes", 0),
            c.get("serve.snapshot.reused_bytes", 0))


# interiors a request of the second and later ones pulls, and reuses
# in its snapshot: the answer the last request returned is the newest
# slot of the next snapshot in both modes; ``vel`` stays one object
# under ``pallas`` only (the jitted XLA chunk hands every array back
# as a new object)
_PULLS = {"pallas": (2, 2), "jit": (3, 1)}


@pytest.mark.parametrize("mode", sorted(_PULLS))
def test_later_requests_pull_each_interior_once(mode, tmp_path,
                                                monkeypatch, env):
    from yask_tpu.obs import tracer
    trace_file = tmp_path / "TRACE_EVENTS.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(trace_file))
    monkeypatch.setenv("YT_TRACE", "1")
    interior = G * G * G * 4
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = open_and_fill(srv, "iso3dfd", 0, mode=mode)
        seen, resps = [_pull_counters(srv)], []
        for k in range(3):
            resps.append(srv.run(sid, k * STEPS, (k + 1) * STEPS - 1,
                                 timeout=600))
            seen.append(_pull_counters(srv))
    finally:
        srv.shutdown()
    assert all(r.ok and r.mode == mode for r in resps)
    steps = [(b[0] - a[0], b[1] - a[1]) for a, b in zip(seen, seen[1:])]
    pulled, reused = _PULLS[mode]
    # the session's first request: the whole ring and the answer
    assert steps[0] == (4 * interior, 0)
    assert steps[1:] == [(pulled * interior, reused * interior)] * 2
    spans = [s["attrs"] for s in tracer.read_spans(str(trace_file))
             if s["name"] == "serve.snapshot"]
    assert [(s["bytes"], s["reused_bytes"]) for s in spans] \
        == [(3 * interior, 0)] + [((pulled - 1) * interior,
                                   reused * interior)] * 2
    # what was reused is what a pull would have returned
    want = solo_oracle(env, "iso3dfd", 0, last=3 * STEPS - 1, mode=mode)
    assert np.array_equal(want["pressure"], resps[-1].outputs["pressure"])
    assert not resps[-1].outputs["pressure"].flags.writeable


def test_a_write_through_the_server_is_pulled_anew(tmp_path):
    """``set_var_slice`` puts another array into ``vel``'s slot: the
    next snapshot pulls it and never serves the copy it held."""
    interior = G * G * G * 4
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    try:
        sid = open_and_fill(srv, "iso3dfd", 0, mode="pallas")
        assert srv.run(sid, 0, STEPS - 1, timeout=600).ok
        new_vel = np.full((G, G, G), 0.25, np.float32)
        srv.set_var_slice(sid, "vel", new_vel, [0, 0, 0],
                          [G - 1, G - 1, G - 1])
        before = _pull_counters(srv)
        assert srv.run(sid, STEPS, 2 * STEPS - 1, timeout=600).ok
        after = _pull_counters(srv)
        # older slot, vel, the answer; the last answer's slot reused
        assert (after[0] - before[0], after[1] - before[1]) \
            == (3 * interior, interior)
        snap = srv.snapshot(sid)
        assert np.array_equal(snap["state"]["vel"][0], new_vel)
        # ... and from there on vel is held again
        assert (snap["d2h_bytes"], snap["reused_bytes"]) \
            == (interior, 2 * interior)
    finally:
        srv.shutdown()


# ---------------------------------------------------------- scheduling

def test_same_session_requests_serialize_in_order(server, env):
    """Two requests on ONE session never co-batch (state-dependent);
    they run in submit order and land the same state as one longer
    solo run."""
    sid = open_and_fill(server, "iso3dfd", 0)
    h1 = server.submit(ServeRequest(session=sid, first_step=0,
                                    last_step=STEPS - 1))
    h2 = server.submit(ServeRequest(session=sid, first_step=STEPS,
                                    last_step=2 * STEPS - 1))
    r1 = server.wait(h1, timeout=600)
    r2 = server.wait(h2, timeout=600)
    assert r1.ok and r2.ok
    assert r1.batch == 1 and r2.batch == 1
    want = solo_oracle(env, "iso3dfd", 0, first=0, last=2 * STEPS - 1)
    for var in want:
        assert np.array_equal(want[var], r2.outputs[var])


def test_incompatible_step_ranges_do_not_cobatch(server):
    """Different step ranges → different batch keys → separate
    executions, both correct."""
    s1 = open_and_fill(server, "iso3dfd", 0)
    s2 = open_and_fill(server, "iso3dfd", 1)
    h1 = server.submit(ServeRequest(session=s1, first_step=0,
                                    last_step=STEPS - 1))
    h2 = server.submit(ServeRequest(session=s2, first_step=0,
                                    last_step=2 * STEPS - 1))
    r1 = server.wait(h1, timeout=600)
    r2 = server.wait(h2, timeout=600)
    assert r1.ok and r2.ok
    assert r1.batch == 1 and r2.batch == 1


def test_unknown_session_rejected(server):
    r = server.request(ServeRequest(session="nope", first_step=0),
                       timeout=60)
    assert r.status == "rejected" and "unknown serve session" in r.error


def test_requested_outputs_subset_and_missing(server):
    sid = open_and_fill(server, "iso3dfd", 0)
    r = server.run(sid, 0, STEPS - 1, outputs=("pressure",),
                   timeout=600)
    assert set(r.outputs) == {"pressure"}
    r2 = server.run(sid, STEPS, STEPS, outputs=("no_such_var",),
                    timeout=600)
    assert r2.status == "rejected" and "no_such_var" in r2.error


def test_profile_shared_across_tenants(server):
    """Two tenants on the same configuration share ONE prepared
    context (the one-compile-many-tenants contract)."""
    s1 = open_and_fill(server, "iso3dfd", 0)
    s2 = open_and_fill(server, "iso3dfd", 1)
    sess1 = server.registry.session(s1)
    sess2 = server.registry.session(s2)
    assert sess1.profile is sess2.profile
    assert sess1.ctx is sess2.ctx
    assert sess1.run_state is not sess2.run_state


def test_duplicate_session_id_raises(server):
    open_and_fill(server, "iso3dfd", 0)
    server.open_session(stencil="iso3dfd", radius=2, g=G,
                        session="twin")
    with pytest.raises(YaskException, match="already open"):
        server.open_session(stencil="iso3dfd", radius=2, g=G,
                            session="twin")


def test_prewarm_counts_chunks(server):
    sid = open_and_fill(server, "iso3dfd", 0)
    # 5 steps at wf=2 → chunk sizes {2, 1}
    assert server.prewarm(sid, 5) == 2


# ------------------------------------------------------------- journal

def test_journal_schema_and_terminal(tmp_path):
    j = ServeJournal(str(tmp_path / "J.jsonl"))
    j.record("r1", "s1", "received")
    j.record("r1", "s1", "batched", batch=3)
    j.record("r1", "s1", "ok")
    rows = j.rows()
    assert all(r["v"] == SERVE_SCHEMA for r in rows)
    assert j.terminal("r1") == "ok"
    assert j.terminal("r2") is None
    assert j.max_occupancy() == 3
    with pytest.raises(ValueError):
        j.record("r1", "s1", "not-an-event")
    assert set(SERVE_TERMINAL) == {"ok", "anomaly", "rejected"}


def test_journal_compact_keeps_one_row_per_request(tmp_path):
    p = str(tmp_path / "J.jsonl")
    j = ServeJournal(p)
    for rid, term in (("r1", "ok"), ("r2", "rejected")):
        j.record(rid, "s", "received")
        j.record(rid, "s", term)
    j.record("r3", "s", "received")     # still in flight
    with open(p, "a") as f:
        f.write("not json\n")           # malformed lines are skipped
    dropped = j.compact()   # 5 parsed rows -> 3 kept (the malformed
    assert dropped == 2     # line never parsed, so it isn't counted)
    rows = j.rows()
    assert [r["rid"] for r in rows] == ["r1", "r2", "r3"]
    assert [r["event"] for r in rows] == ["ok", "rejected", "received"]


def test_journal_compact_preserves_occupancy_evidence(tmp_path):
    # the co-batching acceptance probe reads max_occupancy() from
    # batched rows — compaction must keep the best one per rid even
    # after the terminal row lands
    j = ServeJournal(str(tmp_path / "J.jsonl"))
    j.record("r1", "s", "received")
    j.record("r1", "s", "batched", batch=2)
    j.record("r1", "s", "batched", batch=5)    # the high-water mark
    j.record("r1", "s", "batched", batch=3)
    j.record("r1", "s", "ok")
    j.record("r2", "s", "received")
    j.record("r2", "s", "ok")
    before = j.max_occupancy()
    assert before == 5
    j.compact()
    rows = j.rows()
    assert j.max_occupancy() == before         # evidence survived
    assert [r["event"] for r in rows] == ["batched", "ok", "ok"]
    assert rows[0]["detail"]["batch"] == 5
    j.compact()                                # idempotent
    assert j.max_occupancy() == before


def test_journal_compact_if_large_threshold(tmp_path, monkeypatch):
    from yask_tpu.serve.journal import serve_journal_max_bytes
    p = str(tmp_path / "J.jsonl")
    j = ServeJournal(p)
    for i in range(50):
        j.record("r1", "s", "received", pad="x" * 64)
    j.record("r1", "s", "ok")
    size = os.path.getsize(p)
    assert not j.compact_if_large(max_bytes=size + 1)   # under: no-op
    assert os.path.getsize(p) == size
    assert j.compact_if_large(max_bytes=size - 1)       # over: compacts
    assert os.path.getsize(p) < size
    assert j.terminal("r1") == "ok"
    # the env knob parses MB (bad values fall back to 64)
    monkeypatch.setenv("YT_JOURNAL_MAX_MB", "2")
    assert serve_journal_max_bytes() == 2 * (1 << 20)
    monkeypatch.setenv("YT_JOURNAL_MAX_MB", "not-a-number")
    assert serve_journal_max_bytes() == 64 * (1 << 20)
    monkeypatch.delenv("YT_JOURNAL_MAX_MB")
    assert serve_journal_max_bytes() == 64 * (1 << 20)
    # missing file: False, never raises
    assert not ServeJournal(str(tmp_path / "nope.jsonl")) \
        .compact_if_large()


def test_journal_never_raises_on_unwritable_path(tmp_path):
    j = ServeJournal(str(tmp_path / "no_such_dir" / "J.jsonl"))
    row = j.record("r1", "s1", "received")   # must not raise
    assert row["rid"] == "r1"
    assert j.rows() == []


# ------------------------------------------------------------- checker

def test_checker_serve_pass_gated_on_knob(env):
    from yask_tpu.checker import run_checks
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(f"-g {G} -wf_steps 2")
    report = run_checks(ctx, passes=("serve",))
    assert "serve" in report.passes
    assert not [d for d in report.diagnostics
                if d.rule.startswith("SERVE-")]


def test_checker_serve_cache_cold_and_batchable(env, monkeypatch):
    from yask_tpu.checker import run_checks
    monkeypatch.delenv("YT_COMPILE_CACHE", raising=False)
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(f"-g {G} -wf_steps 2 -serve")
    report = run_checks(ctx, passes=("serve",))
    rules = {d.rule: d.severity for d in report.diagnostics}
    assert rules.get("SERVE-CACHE-COLD") == "warn"
    assert rules.get("SERVE-BATCH-INCOMPAT") == "info"  # jit batches
    monkeypatch.setenv("YT_COMPILE_CACHE", "/tmp")
    report2 = run_checks(ctx, passes=("serve",))
    assert not [d for d in report2.diagnostics
                if d.rule == "SERVE-CACHE-COLD"]


def test_checker_serve_batch_incompat_warns_for_sharded(env):
    from yask_tpu.checker import run_checks
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(f"-g {G} -wf_steps 2 -serve")
    ctx.get_settings().mode = "sharded"
    report = run_checks(ctx, passes=("serve",))
    inc = [d for d in report.diagnostics
           if d.rule == "SERVE-BATCH-INCOMPAT"]
    assert inc and inc[0].severity == "warn"


# ------------------------------------------------------------- ensemble

def test_ensemble_members_param(env):
    from yask_tpu.runtime.ensemble import EnsembleRun
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(f"-g {G} -wf_steps 2")
    ctx.prepare_solution()
    members = [ctx.get_run_state(), ctx.new_run_state()]
    ens = EnsembleRun(ctx, members=members)
    assert ens.n == 2
    with pytest.raises(YaskException, match="disagrees"):
        EnsembleRun(ctx, n=3, members=members)


# ------------------------------------------------------------- metrics

def _checkout_state():
    """What a run of the program must leave as it found it: git's view
    of the tree (None where the checkout is no repository) and the
    bytes of the driver's perf record."""
    st = subprocess.run(["git", "status", "--porcelain"], cwd=ROOT,
                        capture_output=True, text=True)
    led = os.path.join(ROOT, "PERF_LEDGER.jsonl")
    return (st.stdout if st.returncode == 0 else None,
            open(led, "rb").read() if os.path.exists(led) else None)


def test_serve_tool_start_and_stop_leaves_the_checkout_clean(tmp_path):
    """``tools/serve.py`` started in the checkout, one request served,
    then stopped: the server emits ``metrics`` and writes no perf
    record — ``PERF_LEDGER.jsonl`` has one writer, the driver."""
    from tools.serve_client import ServeClient
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               YT_SERVE_JOURNAL=str(tmp_path / "SJ.jsonl"))
    env.pop("YT_FAULT_PLAN", None)
    before = _checkout_state()
    with ServeClient.spawn(["--no-preflight"], env=env) as c:
        sid = c.open(stencil="iso3dfd", radius=1, g=G, mode="jit")
        c.init_vars(sid)
        assert c.run(sid, 0, STEPS - 1)["status"] == "ok"
        assert c.metrics()["completed"] == 1
    assert c._proc.returncode == 0
    assert _checkout_state() == before
