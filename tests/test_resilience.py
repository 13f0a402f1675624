"""yask_tpu.resilience: fault classes / guards / journal / sanity
units, plus the two end-to-end acceptance paths (also the
``make faultcheck`` target): an injected backend drop mid-session whose
rerun resumes from the journal, and an injected all-zero output that
can only ever produce a quarantined ANOMALY row.

Everything runs on CPU: the injection plan (``YT_FAULT_PLAN``) drives
the faults, so the machinery that guards hardware runs is tested
without hardware.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from yask_tpu.resilience import (CKPT_SCHEMA, Breaker, CompileFailed,
                                 CompilerOOM, DeviceHang, Fault,
                                 BackendUnavailable, ResultAnomaly,
                                 SessionJournal, TERMINAL_OUTCOMES,
                                 anomaly_fields, array_stats,
                                 check_output, classify,
                                 classify_message, deadline,
                                 default_breaker_path,
                                 degradation_ladder, extract_snapshot,
                                 fault_point, guarded_call,
                                 max_journal_bytes, maybe_corrupt,
                                 peek_checkpoint, python_cmd,
                                 reset_faults, restore_checkpoint,
                                 run_deadlined, save_checkpoint,
                                 snapshot_mismatches)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fault_plan(monkeypatch):
    monkeypatch.delenv("YT_FAULT_PLAN", raising=False)
    reset_faults()
    yield
    reset_faults()


# ------------------------------------------------------------ fault classes

def test_classify_messages():
    assert classify_message("INTERNAL: stream terminated by RST_STREAM") \
        is BackendUnavailable
    assert classify_message("UNAVAILABLE: failed to connect") \
        is BackendUnavailable
    assert classify_message("Mosaic lowering failed") is CompileFailed
    assert classify_message("some totally unrelated KeyError") is None


def test_classify_oom_wins_over_compile_signs():
    # a Mosaic OOM message also carries INTERNAL/Mosaic signatures;
    # the OOM test must win (the round-3 tuner postmortem ordering)
    msg = ("INTERNAL: Mosaic failed: RESOURCE_EXHAUSTED: Ran out of "
           "memory in memory space vmem")
    assert classify_message(msg) is CompilerOOM
    # what libtpu 0.0.34 printed on a v5e (PR 21): VMEM at compile,
    # HBM at program load
    for msg in ("JaxRuntimeError: RESOURCE_EXHAUSTED: XLA:TPU compile "
                "permanent error. Ran out of memory in memory space "
                "vmem. Used 136.76M of 128.00M vmem. Exceeded vmem "
                "capacity by 8.76M.",
                "JaxRuntimeError: RESOURCE_EXHAUSTED: Error loading "
                "program 'jit_body': Attempting to reserve 11.21G at "
                "the bottom of memory. That was not possible. There "
                "are 10.92G free, 0B reserved, and 10.92G reservable."):
        assert classify_message(msg) is CompilerOOM


def test_classify_wraps_and_passes_through():
    f = classify(RuntimeError("Connection reset by peer"), site="s")
    assert isinstance(f, BackendUnavailable) and f.site == "s"
    assert isinstance(f.cause, RuntimeError)
    inj = BackendUnavailable("injected", site="x")
    assert classify(inj) is inj          # Fault instances pass through
    assert classify(KeyError("bug")) is None   # our bugs stay ours


def test_breaker():
    b = Breaker(threshold=2)
    assert not b.record(BackendUnavailable("one"))
    assert not b.tripped
    assert b.record(BackendUnavailable("two")) and b.tripped
    assert b.last.kind == "backend_unavailable"
    b.reset()
    assert not b.tripped and b.consecutive == 0


# ---------------------------------------------------------------- injection

def test_fault_plan_compact_parse(monkeypatch):
    monkeypatch.setenv("YT_FAULT_PLAN",
                       "a.*:backend_unavailable:2:1; b:zero_output")
    from yask_tpu.resilience.faults import active_plan
    plan = active_plan()
    assert plan[0]["site"] == "a.*" and plan[0]["times"] == 2 \
        and plan[0]["after"] == 1
    assert plan[1] == {"site": "b", "kind": "zero_output", "times": 1,
                       "after": 0, "secs": 3600.0, "_seen": 0}


def test_fault_plan_rejects_unknown_kind(monkeypatch):
    monkeypatch.setenv("YT_FAULT_PLAN", "a:frobnicate")
    from yask_tpu.resilience.faults import active_plan
    with pytest.raises(ValueError):
        active_plan()


def test_fault_point_fires_by_glob_and_window(monkeypatch):
    monkeypatch.setenv("YT_FAULT_PLAN", "s.*:backend_unavailable:1:1")
    fault_point("s.one")                 # hit 1 <= after: no fire
    with pytest.raises(BackendUnavailable):
        fault_point("s.two")             # hit 2: fires
    fault_point("s.three")               # window exhausted
    fault_point("other")                 # never matched the glob


def test_injected_faults_carry_classifiable_signatures(monkeypatch):
    # injected messages must round-trip through classify_message, so
    # code that sniffs messages (not isinstance) behaves identically
    # under injection and under the real backend
    for kind, cls in (("backend_unavailable", BackendUnavailable),
                      ("compiler_oom", CompilerOOM)):
        monkeypatch.setenv("YT_FAULT_PLAN", f"p.{kind}:{kind}")
        reset_faults()
        with pytest.raises(cls) as ei:
            fault_point(f"p.{kind}")
        assert classify_message(str(ei.value)) is cls


def test_maybe_corrupt(monkeypatch):
    import numpy as np
    monkeypatch.setenv("YT_FAULT_PLAN",
                       "z:zero_output; n:nan_output")
    a = np.ones((3, 3), np.float32)
    z = maybe_corrupt("z", a)
    assert (z == 0).all() and (a == 1).all()   # copy, not in-place
    state = {"v": [np.ones(4)]}
    n = maybe_corrupt("n", state)
    assert np.isnan(n["v"][0]).all()
    assert maybe_corrupt("unmatched", a) is a


# ---------------------------------------------------------------- guards

def test_guarded_call_classifies_and_keeps_own_bugs(monkeypatch):
    def boom():
        raise RuntimeError("UNAVAILABLE: failed to connect")
    with pytest.raises(BackendUnavailable):
        guarded_call(boom, site="t.backend")

    def bug():
        raise KeyError("ours")
    with pytest.raises(KeyError):        # unclassified: untouched
        guarded_call(bug, site="t.bug")


def test_guarded_call_retries_then_succeeds(monkeypatch):
    monkeypatch.setenv("YT_FAULT_PLAN", "t.retry:backend_unavailable:1")
    calls = []
    out = guarded_call(lambda: calls.append(1) or "ok", site="t.retry",
                       retries=2, backoff=0.01, max_backoff=0.01,
                       jitter=0.0)
    assert out == "ok" and calls == [1]


def test_guarded_call_breaker_suppresses_retry(monkeypatch):
    monkeypatch.setenv("YT_FAULT_PLAN", "t.brk:backend_unavailable:9")
    b = Breaker(threshold=1)
    t0 = time.perf_counter()
    with pytest.raises(BackendUnavailable):
        guarded_call(lambda: "never", site="t.brk", retries=5,
                     backoff=5.0, breaker=b)
    assert time.perf_counter() - t0 < 2.0   # no backoff sleeps happened
    assert b.tripped


def test_guarded_call_breaker_resets_on_success():
    b = Breaker(threshold=3)
    b.record(BackendUnavailable("x"))
    assert guarded_call(lambda: 7, site="t.ok", breaker=b) == 7
    assert b.consecutive == 0


def test_deadline_converts_hang(monkeypatch):
    monkeypatch.setenv("YT_FAULT_PLAN", "t.hang:hang")
    from yask_tpu.resilience.faults import _entries
    _entries()[0]["secs"] = 5.0          # shorten the injected stall
    with pytest.raises(DeviceHang):
        guarded_call(lambda: None, site="t.hang", deadline_secs=0.3)


def test_deadline_noop_when_off():
    with deadline(None, site="x"):
        pass
    with deadline(0.2, site="x"):
        time.sleep(0.01)                 # finishes before the alarm


def test_run_deadlined_ok_and_kill():
    rc, out = run_deadlined(python_cmd("print('hello')"), 30,
                            site="t.sub")
    assert rc == 0 and out.strip() == "hello"
    with pytest.raises(DeviceHang) as ei:
        run_deadlined(python_cmd(
            "import sys, time; print('partial', flush=True); "
            "time.sleep(60)"), 1.0, site="t.sub")
    assert "partial" in (ei.value.partial_stdout or "")


def test_guarded_call_backoff_jitter_bounds(monkeypatch):
    # the sleep schedule is the fleet's anti-lockstep contract:
    # delay = min(backoff * 2^attempt, max_backoff) * (1 + jitter*U)
    # with U in [0, 1) — verify both the exact formula at a pinned U
    # and the [base, base*(1+jitter)) envelope.
    from yask_tpu.resilience import guard as guard_mod
    backoff, max_backoff, jitter, retries = 0.5, 2.0, 0.25, 4
    for u in (0.0, 0.5, 0.999):
        monkeypatch.setenv("YT_FAULT_PLAN", "t.jit:backend_unavailable:99")
        reset_faults()
        sleeps = []
        monkeypatch.setattr(guard_mod.time, "sleep", sleeps.append)
        monkeypatch.setattr(guard_mod.random, "random", lambda: u)
        with pytest.raises(BackendUnavailable):
            guarded_call(lambda: "never", site="t.jit",
                         retries=retries, backoff=backoff,
                         max_backoff=max_backoff, jitter=jitter)
        assert len(sleeps) == retries      # one sleep per retry
        for attempt, got in enumerate(sleeps):
            base = min(backoff * (2 ** attempt), max_backoff)
            assert got == pytest.approx(base * (1.0 + jitter * u))
            assert base <= got < base * (1.0 + jitter)
        # exponential then capped: 0.5, 1.0, 2.0, 2.0 (scaled by jitter)
        bases = [s / (1.0 + jitter * u) for s in sleeps]
        assert bases == pytest.approx([0.5, 1.0, 2.0, 2.0])


def test_run_deadlined_partial_stdout_drains_only_pre_kill():
    # everything flushed before the SIGKILL survives in
    # .partial_stdout; output the child never reached is absent — the
    # drain is the real pipe contents, not a re-run.
    with pytest.raises(DeviceHang) as ei:
        run_deadlined(python_cmd(
            "import time\n"
            "print('line-one', flush=True)\n"
            "print('line-two', flush=True)\n"
            "time.sleep(60)\n"
            "print('never-happens', flush=True)\n"), 1.0,
            site="t.drain")
    got = ei.value.partial_stdout or ""
    assert "line-one" in got and "line-two" in got
    assert "never-happens" not in got
    assert ei.value.site == "t.drain"
    assert ei.value.kind == "device_hang"


# ---------------------------------------------------------------- journal

def test_journal_roundtrip_and_resume(tmp_path):
    j = SessionJournal(str(tmp_path / "J.jsonl"))
    j.record("validate", "a", "started", attempt=1)
    j.record("validate", "a", "ok", attempt=1, mismatches=0)
    j.record("validate", "b", "started", attempt=1)
    j.record("validate", "b", "fault", attempt=1, kind="backend_unavailable")
    j.record("validate", "c", "anomaly", anomalies=["all_zero"])
    assert j.completed("validate", "a")
    assert not j.completed("validate", "b")
    assert j.completed("validate", "c")   # anomaly is terminal
    assert j.pending("validate", ["a", "b", "c", "d"]) == ["b", "d"]
    assert j.attempts("validate", "b") == 1
    assert j.last_outcomes()[("validate", "b")]["outcome"] == "fault"


def test_journal_skips_malformed_lines(tmp_path):
    p = tmp_path / "J.jsonl"
    j = SessionJournal(str(p))
    j.record("s", "c", "ok")
    with open(p, "a") as f:
        f.write("{truncated mid-wri\n")   # kill mid-write
    assert len(j.rows()) == 1


def test_journal_compact(tmp_path):
    j = SessionJournal(str(tmp_path / "J.jsonl"))
    j.record("session", "", "started")
    j.record("validate", "a", "started")
    j.record("validate", "a", "ok")
    j.record("session", "", "ok")
    dropped = j.compact()
    assert dropped == 2
    rows = j.rows()
    assert [(r["stage"], r["case"], r["outcome"]) for r in rows] == [
        ("session", "", "ok"), ("validate", "a", "ok")]
    assert j.completed("validate", "a")


# ---------------------------------------------------------------- sanity

def test_check_output_verdicts():
    import numpy as np
    ok = check_output(np.linspace(1, 2, 64))
    assert ok["ok"] and ok["anomalies"] == []
    z = check_output(np.zeros(64))
    assert not z["ok"] and "all_zero" in z["anomalies"]
    nf = check_output(np.array([1.0, np.nan]))
    assert "nonfinite" in nf["anomalies"]
    m = check_output(np.ones(8), oracle=np.full(8, 2.0))
    assert "oracle_mismatch" in m["anomalies"]
    assert m["oracle_rel_err"] > 0.4
    shp = check_output(np.ones(8), oracle=np.ones(9))
    assert "oracle_shape_mismatch" in shp["anomalies"]
    good = check_output(np.ones(8), oracle=np.ones(8) * 1.001)
    assert good["ok"]


def _ref_stats(data):
    """The verdict's numbers by the plain float64 recomputation
    ``array_stats`` replaced (kept here as the reference): a float64
    copy, a finite mask, a masked gather."""
    import numpy as np
    from yask_tpu.resilience.sanity import _as_arrays
    n = zeros = nonfinite = 0
    max_abs = 0.0
    for a in _as_arrays(data):
        if a.size == 0:
            continue
        a = np.asarray(a, dtype=np.float64)
        n += a.size
        finite = np.isfinite(a)
        nonfinite += int(a.size - int(finite.sum()))
        zeros += int((a == 0.0).sum())
        if finite.any():
            max_abs = max(max_abs, float(np.abs(a[finite]).max()))
    return {"n": n,
            "zero_frac": (zeros / n) if n else 0.0,
            "nonfinite_frac": (nonfinite / n) if n else 0.0,
            "max_abs": max_abs}


def _sanity_field(dtype="float32", shape=(12, 32, 32)):
    import numpy as np
    rng = np.random.RandomState(5)
    return ((rng.rand(*shape) - 0.5) * 8).astype(dtype)


def _with(a, **at):
    """``a`` with a few flat positions set: ``_with(a, nan=[7])``."""
    import numpy as np
    vals = {"nan": np.nan, "pinf": np.inf, "ninf": -np.inf}
    a = a.copy()
    for k, where in at.items():
        a.reshape(-1)[where] = vals[k]
    return a


def _zero_share(nonzero, n=10000):
    import numpy as np
    a = np.zeros(n, np.float32)
    a[:nonzero] = 1.5
    return a


def _sanity_cases():
    import ml_dtypes
    import numpy as np
    f = _sanity_field()
    last = f.size - 1
    # (id, data, anomalies, takes the exact scan)
    return [
        ("clean_fp32", f, [], False),
        ("one_nan", _with(f, nan=[4097]), ["nonfinite"], True),
        ("one_pinf", _with(f, pinf=[0]), ["nonfinite"], True),
        ("one_ninf", _with(f, ninf=[last]), ["nonfinite"], True),
        ("nan_and_inf", _with(f, nan=[3, 9000], pinf=[5000], ninf=[11]),
         ["nonfinite"], True),
        ("all_nan", np.full((4, 8), np.nan, np.float32),
         ["nonfinite"], True),
        ("all_zero", np.zeros((12, 32, 32), np.float32),
         ["all_zero"], False),
        ("zeros_under_max", _zero_share(11), [], False),
        ("zeros_at_max", _zero_share(10), ["all_zero"], False),
        ("zeros_over_max", _zero_share(9), ["all_zero"], False),
        ("empty", np.zeros((0, 4), np.float32), [], False),
        ("scalar", np.float32(-2.5), [], False),
        ("list", [f, _with(f, nan=[1]), np.zeros(0, np.float32)],
         ["nonfinite"], True),
        ("ring_dict", {"p": [f, -2 * f], "v": [np.zeros((3, 3))]},
         [], False),
        ("float64", _sanity_field("float64") * 1e200, [], False),
        ("bfloat16", _sanity_field(ml_dtypes.bfloat16), [], False),
        ("bfloat16_nan", _with(_sanity_field(ml_dtypes.bfloat16),
                               nan=[77], ninf=[5000]),
         ["nonfinite"], True),
        ("int32", np.arange(-5, 5000, dtype=np.int32), [], False),
        ("noncontiguous", _with(f, pinf=[32])[::2, 1:, ::3],
         ["nonfinite"], True),
        ("noncontiguous_clean", f.T[:, ::2], [], False),
    ]


@pytest.mark.parametrize("case", _sanity_cases(),
                         ids=lambda c: c[0])
def test_verdict_equals_float64_recomputation(case, monkeypatch):
    """The one-pass verdict is, key for key and value for value, the
    float64 recomputation's; the exact scan runs for the non-finite
    cases only, and ``took_exact_scan`` says whether it did."""
    from yask_tpu.resilience import sanity
    _, data, anomalies, exact = case
    # several blocks per array, so block seams are walked too
    monkeypatch.setattr(sanity, "_BLOCK_ELEMS", 2048)
    calls = []
    real = sanity._exact_scan
    monkeypatch.setattr(sanity, "_exact_scan",
                        lambda blk: calls.append(1) or real(blk))
    got = check_output(data)
    want = _ref_stats(data)
    want = {"anomalies": anomalies, **want, "ok": not anomalies}
    assert got == want
    assert list(got) == list(want)
    assert array_stats(data) == _ref_stats(data)
    assert bool(calls) == exact == sanity.took_exact_scan(got)



def test_array_stats_over_state_dict():
    import numpy as np
    st = array_stats({"v": [np.zeros(4), np.array([1.0, -3.0])]})
    assert st["n"] == 6 and st["max_abs"] == 3.0
    assert abs(st["zero_frac"] - 4 / 6) < 1e-12


def test_anomaly_fields_shape():
    v = check_output(__import__("numpy").zeros(16))
    af = anomaly_fields(v)
    assert af["quarantined"] is True
    assert af["anomaly"]["classification"] == "ANOMALY"
    assert af["anomaly"]["anomalies"] == ["all_zero"]


# ------------------------------------------------------------- acceptance

def test_acceptance_all_zero_output_quarantined(tmp_path):
    """Injected all-zero outputs must never be released or journaled
    as clean (the all-zero incident from real hardware, replayed):
    through the documented server, ``tools/serve.py`` in a process of
    its own, the answer on the wire is an ``anomaly`` with its verdict
    and so is the journal's terminal row."""
    from tools.serve_client import ServeClient, ServeClientError
    from yask_tpu.serve import ServeJournal
    jpath = str(tmp_path / "SJ.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu", YT_SERVE_JOURNAL=jpath,
               YT_FAULT_PLAN="serve.respond:zero_output:99")
    with ServeClient.spawn(["--no-preflight"], env=env) as c:
        sid = c.open(stencil="iso3dfd", radius=1, g=16, mode="jit")
        c.init_vars(sid)
        with pytest.raises(ServeClientError) as ei:
            c.run(sid, 0, 3)
        resp = ei.value.response
        assert resp["status"] == "anomaly" and not resp["ok"]
        assert resp["anomaly"]["classification"] == "ANOMALY"
        assert "all_zero" in resp["anomaly"]["anomalies"]
        m = c.metrics()
        assert m["anomalies"] == 1 and m["ok"] == 0
    j = ServeJournal(jpath)
    assert j.terminal(resp["rid"]) == "anomaly"
    rows = j.events(resp["rid"])
    assert not any(r["event"] == "ok" for r in rows)
    assert "all_zero" in rows[-1]["detail"]["anomalies"]


# ------------------------------------------------------------ checkpoints

def _make_iso(mode, g=16, wf=0, ranks=(), **knobs):
    """A small prepared iso3dfd context with deterministic interiors —
    the checkpoint/supervision tests' shared subject (every call with
    the same ``g`` starts from identical state, whatever the mode)."""
    import numpy as np
    from yask_tpu import yk_factory
    fac = yk_factory()
    env = fac.new_env()
    ctx = fac.new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(f"-g {g} -wf_steps {wf}")
    o = ctx.get_settings()
    o.mode = mode
    for k, v in knobs.items():
        setattr(o, k, v)
    for d, n in ranks:
        ctx.set_num_ranks(d, n)
    ctx.prepare_solution()
    _fill_iso(ctx, g)
    return ctx


def _fill_iso(ctx, g=16):
    import numpy as np
    rng = np.random.RandomState(11)
    for vn in ctx.get_var_names():
        v = ctx.get_var(vn)
        if vn == "vel":
            v.set_all_elements_same(0.05)
        else:
            arr = rng.rand(g, g, g).astype(np.float32)
            v.set_elements_in_slice(arr, [0, 0, 0, 0],
                                    [0, g - 1, g - 1, g - 1])


def test_ckpt_roundtrip_and_peek(tmp_path):
    ctx = _make_iso("jit")
    ctx.run_solution(0, 3)
    snap = extract_snapshot(ctx)
    assert snap["meta"]["schema"] == CKPT_SCHEMA
    assert snap["meta"]["cur_step"] == 4
    path = str(tmp_path / "c.ckpt.npz")
    save_checkpoint(ctx, path)
    meta = peek_checkpoint(path)
    assert meta and meta["cur_step"] == 4 \
        and meta["solution"] == "iso3dfd"
    fresh = _make_iso("jit")                  # different initial state
    assert restore_checkpoint(fresh, path)
    assert fresh._cur_step == 4 and fresh._steps_done == 4
    assert snapshot_mismatches(extract_snapshot(fresh), snap) == 0


def _ref_snapshot(ctx):
    """``extract_snapshot`` by the route it replaced (kept here as the
    reference): every padded ring array pulled whole, the interior cut
    by a strided host copy."""
    import numpy as np
    from yask_tpu.resilience.checkpoint import _interior_index
    ctx._materialize_state()
    gsz = ctx._opts.global_domain_sizes
    state, padded = {}, 0
    for name, ring in ctx._state.items():
        idx = _interior_index(ctx._program.geoms[name], gsz)
        state[name] = [np.ascontiguousarray(np.asarray(a)[idx])
                       for a in ring]
        padded += sum(int(a.nbytes) for a in ring)
    return state, padded


def _assert_same_state(got, want):
    import numpy as np
    assert list(got) == list(want)
    for name in want:
        assert len(got[name]) == len(want[name])
        for g, w in zip(got[name], want[name]):
            assert isinstance(g, np.ndarray)
            assert g.dtype == w.dtype and g.shape == w.shape
            assert g.flags.c_contiguous
            assert g.tobytes() == w.tobytes()


_SNAP_MODES = {
    "jit": dict(mode="jit"),
    "pallas": dict(mode="pallas", wf=2),
    "shard_map": dict(mode="shard_map", ranks=(("x", 2),)),
}


@pytest.mark.parametrize("on_device", [True, False],
                         ids=["device", "host"])
@pytest.mark.parametrize("mode", sorted(_SNAP_MODES))
def test_snapshot_cut_on_device_equals_host_cut(mode, on_device,
                                                tmp_path):
    """The snapshot whose interiors are cut on the device is, bit for
    bit and with equal meta, the whole-pull-and-host-cut one; what it
    counts as crossed is the interiors' bytes (nothing for
    host-resident state); the ``.npz`` payload is the same bytes."""
    import numpy as np
    ctx = _make_iso(**_SNAP_MODES[mode])
    ctx.run_solution(0, 3)
    if not on_device:
        ctx._state_to_host()
    want, padded = _ref_snapshot(ctx)
    assert ctx._state_on_device == on_device
    snap = extract_snapshot(ctx)
    _assert_same_state(snap["state"], want)
    interiors = sum(a.nbytes for ring in want.values() for a in ring)
    assert interiors < padded
    assert snap["d2h_bytes"] == (interiors if on_device else 0)
    assert snap["meta"] == {
        "schema": CKPT_SCHEMA, "solution": "iso3dfd",
        "dtype": "float32", "domain": {"x": 16, "y": 16, "z": 16},
        "rings": {n: len(r) for n, r in want.items()},
        "axes": {"pressure": ["x", "y", "z"], "vel": ["x", "y", "z"]},
        "cur_step": 4, "steps_done": 4}
    path = str(tmp_path / "c.ckpt.npz")
    save_checkpoint(ctx, path)
    with np.load(path) as data:
        for name, ring in want.items():
            for i, w in enumerate(ring):
                assert data[f"{name}__slot{i}"].tobytes() == w.tobytes()


def _no_room(a, idx):
    import jax
    raise jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: "
        "Attempting to allocate 216.00M. That was not possible.")


def _broken(a, idx):
    import jax
    raise jax.errors.JaxRuntimeError("FAILED_PRECONDITION: boom")


# id: (mode, a snapshot taken before the cut fails?, steps run between,
#      the stand-in for the device cut, what crosses then)
_NO_ROOM_CASES = {
    "fresh_context": ("jit", False, 0, _no_room, "padded"),
    "a_run_between": ("jit", True, 2, _no_room, "padded"),
    "pallas_run_between": ("pallas", True, 2, _no_room, "written"),
    "untouched_state": ("jit", True, 0, _no_room, "nothing"),
    "another_error": ("jit", False, 0, _broken, "raises"),
    "another_error_after_a_run": ("jit", True, 2, _broken, "raises"),
}


@pytest.mark.parametrize("case", sorted(_NO_ROOM_CASES))
def test_snapshot_falls_back_when_device_has_no_room(case, monkeypatch):
    """Where the device cannot hold the interior-sized temporary (an
    allocation failure at the cut) the slots that have to be pulled
    cross padded, as before, and the snapshot is the same; any other
    device error is not swallowed.  A slot the run state remembers a
    pull of needs no cut, so no room either: an untouched state's
    second snapshot crosses nothing, and after a ``pallas`` run only
    the slots the kernel wrote cross (``vel`` is the array it was)."""
    import jax
    from yask_tpu.resilience import checkpoint
    mode, before, steps, cut, crosses = _NO_ROOM_CASES[case]
    ctx = _make_iso(mode, wf=2 if mode == "pallas" else 0)
    ctx.run_solution(0, 1)
    if before:
        extract_snapshot(ctx)
    if steps:
        ctx.run_solution(2, 1 + steps)
    want, padded = _ref_snapshot(ctx)
    monkeypatch.setattr(checkpoint, "_device_cut", cut)
    if crosses == "raises":
        with pytest.raises(jax.errors.JaxRuntimeError, match="boom"):
            extract_snapshot(ctx)
        return
    snap = extract_snapshot(ctx)
    _assert_same_state(snap["state"], want)
    assert snap["d2h_bytes"] == {
        "padded": padded, "nothing": 0,
        "written": sum(int(a.nbytes) for a in ctx._state["pressure"]),
    }[crosses]
    assert not any(a.flags.writeable
                   for ring in snap["state"].values() for a in ring)


# ------------------------------------- the record of pulls (RunState.pulled)

def _interior_bytes(snap, *names):
    return sum(int(a.nbytes) for n in names for a in snap["state"][n])


@pytest.mark.parametrize("mode", sorted(_SNAP_MODES))
def test_second_snapshot_of_an_untouched_state_crosses_nothing(mode):
    """A device array is immutable: while the ring holds the OBJECT an
    interior was pulled from, the host copy is what another pull would
    return, so the second snapshot crosses 0 bytes and equals the
    first, and the whole-pull reference, byte for byte.  The arrays
    are shared between the two and not writable."""
    ctx = _make_iso(**_SNAP_MODES[mode])
    ctx.run_solution(0, 3)
    first = extract_snapshot(ctx)
    second = extract_snapshot(ctx)
    want, _ = _ref_snapshot(ctx)
    _assert_same_state(second["state"], want)
    assert snapshot_mismatches(first, second, epsilon=0,
                               abs_epsilon=0) == 0
    whole = _interior_bytes(first, "pressure", "vel")
    assert (first["d2h_bytes"], first["reused_bytes"]) == (whole, 0)
    assert (second["d2h_bytes"], second["reused_bytes"]) == (0, whole)
    for name, ring in first["state"].items():
        for a, b in zip(ring, second["state"][name]):
            assert a is b
            with pytest.raises(ValueError, match="read-only"):
                a[0, 0, 0] = 1.0


# what a run leaves the record: the launch of a ``pallas`` chunk
# returns only what its kernel wrote, ``vel`` stays the array it was;
# the XLA chunk is donated and hands every array back as a new
# object; a shard mode re-pads its resting interiors at each access
_REUSED_AFTER_RUN = {"jit": (), "pallas": ("vel",), "shard_map": ()}


@pytest.mark.parametrize("mode", sorted(_SNAP_MODES))
def test_after_a_run_the_written_slots_cross_again(mode):
    import gc
    import weakref
    ctx = _make_iso(**_SNAP_MODES[mode])
    ctx.run_solution(0, 3)
    extract_snapshot(ctx)
    before = [weakref.ref(a) for ring in ctx._state.values()
              for a in ring]
    ctx.run_solution(4, 7)
    snap = extract_snapshot(ctx)
    want, _ = _ref_snapshot(ctx)
    _assert_same_state(snap["state"], want)
    reused = _interior_bytes(snap, *_REUSED_AFTER_RUN[mode])
    assert snap["reused_bytes"] == reused
    assert snap["d2h_bytes"] \
        == _interior_bytes(snap, "pressure", "vel") - reused
    # the record holds no device array the state has dropped: the
    # arrays of before the run that the state let go of are gone, and
    # every entry is of an object a slot holds now
    gc.collect()
    now = [a for ring in ctx._state.values() for a in ring]
    assert all(r() is None or any(r() is a for a in now)
               for r in before)
    run = ctx.get_run_state()
    assert sorted(run.pulled) == [("pressure", 0), ("pressure", 1),
                                  ("vel", 0)]
    for (name, slot), (ref, _host) in run.pulled.items():
        assert ref() is ctx._state[name][slot]


@pytest.mark.parametrize("write", ["slice", "same", "element"])
def test_a_public_write_is_pulled_anew(write):
    """A public fill puts another array object into the slot, so the
    copy the record holds of the old one is never served."""
    import numpy as np
    ctx = _make_iso("pallas", wf=2)
    ctx.run_solution(0, 1)
    old = extract_snapshot(ctx)
    vel = ctx.get_var("vel")
    if write == "slice":
        vel.set_elements_in_slice(
            np.full((16, 16, 16), 0.25, np.float32),
            [0, 0, 0], [15, 15, 15])
    elif write == "same":
        vel.set_all_elements_same(0.25)
    else:
        vel.set_element(0.25, [3, 4, 5])
    snap = extract_snapshot(ctx)
    want, _ = _ref_snapshot(ctx)
    _assert_same_state(snap["state"], want)
    assert snap["state"]["vel"][0][3, 4, 5] == np.float32(0.25)
    assert old["state"]["vel"][0][3, 4, 5] == np.float32(0.05)
    assert snap["d2h_bytes"] == _interior_bytes(snap, "vel")
    assert snap["reused_bytes"] == _interior_bytes(snap, "pressure")


def test_a_deleted_array_is_never_served_from_the_record():
    """A run that fails after its input was donated leaves deleted
    arrays in the state: a snapshot of them raises, as before, and
    does not hand back the bytes they held."""
    ctx = _make_iso("jit")
    ctx.run_solution(0, 1)
    extract_snapshot(ctx)
    ctx._state["pressure"][0].delete()
    with pytest.raises(RuntimeError, match="deleted"):
        extract_snapshot(ctx)
    assert ("pressure", 0) not in ctx.get_run_state().pulled


def test_host_resident_state_is_not_recorded():
    """A host array can be written in place: only a device array's
    identity says its bytes are the ones that were pulled.  Host state
    is cut by a copy each time, and nothing is remembered."""
    import numpy as np
    ctx = _make_iso("jit")
    ctx.run_solution(0, 1)
    ctx._state_to_host()
    first = extract_snapshot(ctx)
    assert ctx.get_run_state().pulled == {}
    ctx._state["vel"][0] = np.full_like(ctx._state["vel"][0], 0.5)
    second = extract_snapshot(ctx)
    assert ctx.get_run_state().pulled == {}
    assert (first["reused_bytes"], second["reused_bytes"]) == (0, 0)
    assert float(first["state"]["vel"][0].max()) == np.float32(0.05)
    assert float(second["state"]["vel"][0].max()) == 0.5
    assert first["state"]["vel"][0] is not second["state"]["vel"][0]
    assert not second["state"]["vel"][0].flags.writeable


# ------------------- the served rollback target, from a reusing snapshot

@pytest.fixture()
def served_iso(tmp_path, monkeypatch):
    """A ``pallas`` session of an in-process server, filled like
    ``_make_iso``, and ``taken``: for every rollback snapshot the
    scheduler takes, the state by the parent's route
    (``_ref_snapshot``) at that moment and the snapshot it took."""
    from yask_tpu.resilience import checkpoint
    from yask_tpu.serve import StencilServer
    taken = []
    real = checkpoint.extract_snapshot

    def spy(ctx):
        want, _ = _ref_snapshot(ctx)
        snap = real(ctx)
        taken.append((want, snap))
        return snap
    monkeypatch.setattr(checkpoint, "extract_snapshot", spy)
    srv = StencilServer(journal_path=str(tmp_path / "SJ.jsonl"),
                        window_secs=0.0, preflight=False)
    sid = srv.open_session(stencil="iso3dfd", radius=2, g=16,
                           mode="pallas", wf=2, bucket=False)
    with srv.scheduler.session_ctx(sid) as ctx:
        _fill_iso(ctx)
    yield srv, sid, taken
    srv.shutdown()


def _padded_bytes(ctx):
    import numpy as np
    return {name: [np.asarray(a).tobytes() for a in ring]
            for name, ring in ctx._state.items()}


@pytest.mark.parametrize("to_mode", ["pallas", "jit"])
def test_a_rollback_from_a_reusing_snapshot_is_the_parents(
        to_mode, served_iso, monkeypatch):
    """A fault at ``serve.run`` on the third request rolls the session
    back to a snapshot two thirds of which never crossed: it is, bit
    for bit, the whole pull; restored into the same mode and down the
    ladder it gives the same padded state and the same run as the
    whole pull restored, and the tenant's degraded answer is that
    run's."""
    import numpy as np
    from yask_tpu.resilience import apply_snapshot
    from yask_tpu.serve.scheduler import extract_outputs
    srv, sid, taken = served_iso
    for k in range(2):
        assert srv.run(sid, 4 * k, 4 * k + 3, timeout=600).ok
    monkeypatch.setenv("YT_FAULT_PLAN", "serve.run:device_hang:1")
    reset_faults()
    r = srv.run(sid, 8, 11, timeout=600)
    assert r.ok and r.degraded and r.mode == "jit"
    want, snap = taken[2]
    interior = 16 ** 3 * 4
    assert (snap["d2h_bytes"], snap["reused_bytes"]) \
        == (interior, 2 * interior)
    _assert_same_state(snap["state"], want)
    ref = {"meta": snap["meta"], "state": want}
    assert snapshot_mismatches(snap, ref, epsilon=0, abs_epsilon=0) == 0
    wf = 2 if to_mode == "pallas" else 0
    ours, theirs = _make_iso(to_mode, wf=wf), _make_iso(to_mode, wf=wf)
    assert apply_snapshot(ours, snap) and apply_snapshot(theirs, ref)
    assert ours._cur_step == theirs._cur_step == 8
    assert _padded_bytes(ours) == _padded_bytes(theirs)
    ours.run_solution(8, 11)
    theirs.run_solution(8, 11)
    assert _padded_bytes(ours) == _padded_bytes(theirs)
    if to_mode == "jit":
        assert np.array_equal(extract_outputs(theirs)["pressure"],
                              r.outputs["pressure"])


@pytest.mark.parametrize("kind", ["zero_output", "nan_output"])
def test_a_corrupted_answer_never_becomes_the_rollback_target(
        kind, served_iso, monkeypatch):
    """The record takes the array that was pulled, before
    ``maybe_corrupt("serve.respond")``: the next request's snapshot
    reuses the field the device holds, not what the tenant was
    sent."""
    import numpy as np
    srv, sid, taken = served_iso
    assert srv.run(sid, 0, 3, timeout=600).ok
    monkeypatch.setenv("YT_FAULT_PLAN", f"serve.respond:{kind}:1")
    reset_faults()
    bad = srv.run(sid, 4, 7, timeout=600)
    assert bad.status == "anomaly"
    good = srv.run(sid, 8, 11, timeout=600)
    assert good.ok
    want, snap = taken[2]
    interior = 16 ** 3 * 4
    assert snap["reused_bytes"] == 2 * interior
    _assert_same_state(snap["state"], want)
    newest = snap["state"]["pressure"][-1]
    assert np.isfinite(newest).all() and np.abs(newest).max() > 0
    assert newest.tobytes() != np.asarray(
        bad.outputs["pressure"]).tobytes()
    twin = _make_iso("pallas", wf=2)
    twin.run_solution(0, 11)
    from yask_tpu.serve.scheduler import extract_outputs
    assert np.array_equal(extract_outputs(twin)["pressure"],
                          good.outputs["pressure"])


def test_ckpt_restore_never_raises(tmp_path):
    """Missing / torn / corrupt / stale-schema / wrong-geometry files
    all answer False — the caller's fallback is a fresh run, never a
    crash (the ISSUE's torn-write criterion)."""
    import numpy as np
    ctx = _make_iso("jit")
    ctx.run_solution(0, 1)
    path = str(tmp_path / "c.ckpt.npz")
    save_checkpoint(ctx, path)

    assert not restore_checkpoint(ctx, str(tmp_path / "missing.npz"))

    blob = open(path, "rb").read()
    torn = str(tmp_path / "torn.npz")
    with open(torn, "wb") as f:
        f.write(blob[:len(blob) // 2])        # killed mid-write
    assert not restore_checkpoint(ctx, torn)

    garbage = str(tmp_path / "garbage.npz")
    with open(garbage, "wb") as f:
        f.write(b"this is not an npz archive")
    assert not restore_checkpoint(ctx, garbage)

    stale = str(tmp_path / "stale.npz")
    data = dict(np.load(path))
    meta = json.loads(bytes(data["__meta__"]).decode())
    meta["schema"] = "yask_tpu.checkpoint/0"
    data["__meta__"] = np.frombuffer(json.dumps(meta).encode(),
                                     np.uint8)
    np.savez(stale, **data)
    assert peek_checkpoint(stale) is None
    assert not restore_checkpoint(ctx, stale)

    other = _make_iso("jit", g=24)            # wrong domain geometry
    assert not restore_checkpoint(other, path)
    assert restore_checkpoint(ctx, path)      # the original still loads


def test_ckpt_fault_sites_and_atomicity(monkeypatch, tmp_path):
    ctx = _make_iso("jit")
    ctx.run_solution(0, 1)
    path = str(tmp_path / "c.ckpt.npz")
    save_checkpoint(ctx, path)
    good = open(path, "rb").read()
    monkeypatch.setenv(
        "YT_FAULT_PLAN",
        "ckpt.save:backend_unavailable:1; ckpt.restore:device_hang:1")
    reset_faults()
    with pytest.raises(BackendUnavailable):
        save_checkpoint(ctx, path)
    # the failed save never touched the previous complete checkpoint
    assert open(path, "rb").read() == good
    with pytest.raises(DeviceHang):
        restore_checkpoint(ctx, path)
    assert restore_checkpoint(ctx, path)      # window exhausted


def test_degradation_ladder_table():
    assert degradation_ladder("shard_pallas") == ["shard_map", "jit"]
    assert degradation_ladder("shard_map") == ["jit"]
    assert degradation_ladder("pallas") == ["jit"]
    assert degradation_ladder("jit") == []
    assert degradation_ladder("ref") == []    # oracle never degrades


# ----------------------------------------------------- breaker sidecar

def test_breaker_persists_across_restarts(tmp_path):
    path = str(tmp_path / "BREAKER_STATE.json")
    b = Breaker(threshold=3, path=path)
    b.record(BackendUnavailable("one"))
    b.record(BackendUnavailable("two"))
    b2 = Breaker(threshold=3, path=path)      # a session restart
    assert b2.consecutive == 2 and not b2.tripped
    assert b2.record(BackendUnavailable("three")) and b2.tripped
    b3 = Breaker(threshold=3, path=path)      # restart with it open
    assert b3.tripped and b3.last.kind == "backend_unavailable"
    b3.reset()                                # a fresh successful probe
    assert not Breaker(threshold=3, path=path).tripped


def test_breaker_sidecar_failures_swallowed(tmp_path):
    bad = str(tmp_path / "nodir" / "B.json")  # unwritable location
    b = Breaker(threshold=2, path=bad)        # load failure: silent
    assert b.record(BackendUnavailable("x")) is False  # persist failure: silent
    assert b.consecutive == 1


def test_default_breaker_path_env(monkeypatch, tmp_path):
    monkeypatch.setenv("YT_BREAKER_STATE", str(tmp_path / "B.json"))
    assert default_breaker_path() == str(tmp_path / "B.json")


# ------------------------------------------------- journal growth bound

def test_journal_compact_if_large(tmp_path):
    j = SessionJournal(str(tmp_path / "J.jsonl"))
    for _ in range(10):
        j.record("validate", "a", "started")
        j.record("validate", "a", "ok")
    assert j.compact_if_large(max_bytes=1 << 20) == 0   # under the bound
    assert len(j.rows()) == 20
    dropped = j.compact_if_large(max_bytes=64)
    assert dropped == 19
    assert [r["outcome"] for r in j.rows()] == ["ok"]
    # a missing journal is trivially under any bound
    assert SessionJournal(
        str(tmp_path / "none.jsonl")).compact_if_large(max_bytes=1) == 0


def test_max_journal_bytes_env(monkeypatch):
    assert max_journal_bytes() == 8 * 2 ** 20
    monkeypatch.setenv("YT_JOURNAL_MAX_BYTES", "123")
    assert max_journal_bytes() == 123
    monkeypatch.setenv("YT_JOURNAL_MAX_BYTES", "bogus")
    assert max_journal_bytes() == 8 * 2 ** 20


# --------------------------------------------- supervised runs / ladder

def test_supervised_run_matches_plain(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_SESSION_JOURNAL", str(tmp_path / "J.jsonl"))
    plain = _make_iso("jit")
    plain.run_solution(0, 7)
    sup = _make_iso("jit", ckpt_every=3, watchdog_every=4,
                    ckpt_dir=str(tmp_path))
    sup.run_solution(0, 7)
    assert sup.compare_data(plain) == 0
    meta = peek_checkpoint(str(tmp_path / "iso3dfd.ckpt.npz"))
    assert meta and meta["cur_step"] == 8 and meta["steps_done"] == 8


def test_watchdog_flags_corrupt_state(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_SESSION_JOURNAL", str(tmp_path / "J.jsonl"))
    monkeypatch.setenv("YT_FAULT_PLAN", "run.scan:nan_output:1")
    reset_faults()
    ctx = _make_iso("jit", watchdog_every=2)
    with pytest.raises(ResultAnomaly):        # jit has no rung below it
        ctx.run_solution(0, 3)
    rows = SessionJournal(str(tmp_path / "J.jsonl")).rows()
    flt = [r for r in rows
           if r["stage"] == "run" and r["outcome"] == "fault"]
    assert flt and flt[-1]["detail"]["site"] == "run.scan"
    assert flt[-1]["detail"]["kind"] == "result_anomaly"


def test_acceptance_pallas_degrades_to_jit_ladder(tmp_path, monkeypatch):
    """Injected device hang mid-run under pallas: the supervisor rolls
    back to the last snapshot, degrades pallas → jit, and finishes with
    output identical to an uninterrupted jit run (the ISSUE acceptance
    criterion), with rollback step / ladder path / attempts journaled."""
    monkeypatch.setenv("YT_SESSION_JOURNAL", str(tmp_path / "J.jsonl"))
    monkeypatch.setenv("YT_FAULT_PLAN", "run.chunk:device_hang:1:1")
    reset_faults()
    ref = _make_iso("jit")
    ref.run_solution(0, 7)
    ctx = _make_iso("pallas", wf=2, ckpt_every=2)
    ctx.run_solution(0, 7)
    assert ctx._mode == "jit"
    assert ctx.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    rows = SessionJournal(str(tmp_path / "J.jsonl")).rows()
    flt = [r for r in rows
           if r["stage"] == "run" and r["outcome"] == "fault"]
    assert len(flt) == 1
    d = flt[0]["detail"]
    assert d["kind"] == "device_hang" and d["site"] == "run.chunk"
    assert d["rollback_step"] == 2 and d["from_mode"] == "pallas"
    ok = [r for r in rows
          if r["stage"] == "run" and r["outcome"] == "ok"]
    assert ok and ok[-1]["detail"] == {
        "from_mode": "pallas", "final_mode": "jit",
        "ladder_path": ["jit"], "attempts": 2}


_CHILD = """\
import os, sys
sys.path.insert(0, os.environ["YT_REPO_ROOT"])
import numpy as np
from yask_tpu import yk_factory
from yask_tpu.resilience import restore_checkpoint, save_checkpoint

mode, out_npz = sys.argv[1], sys.argv[2]
fac = yk_factory()
env = fac.new_env()
ctx = fac.new_solution(env, stencil="iso3dfd", radius=2)
ctx.apply_command_line_options("-g 16")
o = ctx.get_settings()
o.mode = mode
o.ckpt_every = 2
o.ckpt_dir = os.environ["YT_CKPT_DIR"]
if mode == "shard_map":
    ctx.set_num_ranks("x", 4)
ctx.prepare_solution()
# identical to _make_iso(g=16): resumes and twins start from one state
rng = np.random.RandomState(11)
for vn in ctx.get_var_names():
    v = ctx.get_var(vn)
    if vn == "vel":
        v.set_all_elements_same(0.05)
    else:
        arr = rng.rand(16, 16, 16).astype(np.float32)
        v.set_elements_in_slice(arr, [0, 0, 0, 0], [0, 15, 15, 15])
first = 0
path = os.path.join(o.ckpt_dir, "iso3dfd.ckpt.npz")
if restore_checkpoint(ctx, path):
    first = ctx._cur_step
    print("resumed-at", first, flush=True)
if first <= 7:
    ctx.run_solution(first, 7)
save_checkpoint(ctx, out_npz)
print("child-done", flush=True)
"""


def test_acceptance_sigkill_resume_bit_identical(tmp_path):
    """SIGKILL a checkpointing run mid-span; fresh processes restore
    from the surviving checkpoint and finish bit-identical to an
    uninterrupted twin — same-mode (jit → jit) AND cross-mode (the
    checkpoint was written under jit, resumed under shard_map): the
    ISSUE's kill-resume acceptance criterion."""
    import shutil
    import signal
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    kill_dir = tmp_path / "ckpt_kill"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "YT_REPO_ROOT": ROOT,
        "YT_CKPT_DIR": str(kill_dir),
        "YT_SESSION_JOURNAL": str(tmp_path / "J.jsonl"),
        "YT_BREAKER_STATE": str(tmp_path / "B.json"),
        # hang the 3rd chunk (after the step-4 cadence save) for 600 s:
        # the child CANNOT finish on its own — only the SIGKILL ends it
        "YT_FAULT_PLAN": json.dumps(
            [{"site": "run.chunk", "kind": "hang", "times": 1,
              "after": 2, "secs": 600}]),
    })
    proc = subprocess.Popen(
        [sys.executable, str(script), "jit",
         str(tmp_path / "unused.npz")],
        env=env, cwd=ROOT, start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    ckpt = str(kill_dir / "iso3dfd.ckpt.npz")
    try:
        deadline_t = time.time() + 240
        while time.time() < deadline_t:
            meta = peek_checkpoint(ckpt)
            if meta and meta["cur_step"] >= 4:
                break
            assert proc.poll() is None, \
                f"child exited early (rc={proc.returncode})"
            time.sleep(0.2)
        else:
            pytest.fail("child never banked the step-4 checkpoint")
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)
    meta = peek_checkpoint(ckpt)
    assert meta and meta["cur_step"] == 4     # mid-run state survived

    twin = _make_iso("jit")
    twin.run_solution(0, 7)
    want = extract_snapshot(twin)

    env.pop("YT_FAULT_PLAN")
    for mode in ("jit", "shard_map"):
        d = tmp_path / f"ckpt_{mode}"
        shutil.copytree(kill_dir, d)          # each resume gets its own
        out = tmp_path / f"final_{mode}.npz"
        e = dict(env)
        e["YT_CKPT_DIR"] = str(d)
        r = subprocess.run(
            [sys.executable, str(script), mode, str(out)],
            env=e, cwd=ROOT, capture_output=True, text=True,
            timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "resumed-at 4" in r.stdout
        fresh = _make_iso("jit")
        assert restore_checkpoint(fresh, str(out))
        assert fresh._cur_step == 8
        assert snapshot_mismatches(extract_snapshot(fresh), want) == 0


