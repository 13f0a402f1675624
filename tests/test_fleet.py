"""Fleet front (tools/serve_fleet.py): N server workers behind one
JSON-lines front.  Acceptance contract: admission spreads fresh
sessions across workers; a shared YT_COMPILE_CACHE means worker 2's
first run is WARM (lowerings == 0, disk hits > 0) off worker 1's cold
compile, with bit-identical outputs; session affinity pins every sid
to exactly one worker journal; an injected ``fleet.route`` fault is
answered (ok=False), never crashes the front.

One module-scoped fleet amortizes the two worker-interpreter spawns
(each imports jax) across every test here."""

import json
import os

import numpy as np
import pytest

from tools.serve_fleet import ServeFleet
from yask_tpu.resilience.faults import reset_faults

STEPS = 4


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fleet")
    saved = {}
    env = {"JAX_PLATFORMS": "cpu"}
    for k, v in env.items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    fl = ServeFleet(n_workers=2, cache_dir=str(tmp / "cache"),
                    journal_dir=str(tmp),
                    worker_args=["--no-preflight", "--window_ms", "5"])
    try:
        yield fl
    finally:
        fl.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.fixture(scope="module")
def sessions(fleet):
    """Two identical-profile sessions; admission must spread them."""
    out = []
    for _ in range(2):
        s = fleet.handle({"op": "open", "stencil": "iso3dfd",
                          "radius": 1, "g": 8, "wf": 2})
        assert s["ok"], s
        assert fleet.handle({"op": "init", "sid": s["sid"]})["ok"]
        out.append(s)
    return out


def test_admission_spreads_across_workers(sessions):
    assert sessions[0]["worker"] != sessions[1]["worker"], \
        "least-loaded admission put both sessions on one worker"


def test_shared_cache_warm_start_and_bit_identity(fleet, sessions):
    s1, s2 = sessions
    r1 = fleet.handle({"op": "run", "sid": s1["sid"],
                       "first": 0, "last": STEPS - 1})
    assert r1["ok"], r1
    cs = fleet.handle({"op": "cache_stats"})["stats"]
    assert cs[str(s1["worker"])]["lowerings"] > 0, \
        "worker 1's first run should be the cold compile"

    r2 = fleet.handle({"op": "run", "sid": s2["sid"],
                       "first": 0, "last": STEPS - 1})
    assert r2["ok"], r2
    cs = fleet.handle({"op": "cache_stats"})["stats"]
    w2 = cs[str(s2["worker"])]
    assert w2["lowerings"] == 0, \
        f"worker 2 re-lowered instead of warm-starting: {w2}"
    assert w2["disk_hits"] > 0, w2

    for name in r1["outputs"]:
        a = np.asarray(r1["outputs"][name]["data"])
        b = np.asarray(r2["outputs"][name]["data"])
        assert np.array_equal(a, b), \
            f"{name}: warm-cache run diverged from cold run"


def test_session_affinity_via_worker_journals(fleet, sessions):
    for s in sessions:
        assert fleet.handle({"op": "run", "sid": s["sid"],
                             "first": STEPS, "last": 2 * STEPS - 1})["ok"]
    placed = {}
    for w in fleet.workers:
        with open(w.journal_path) as f:
            for ln in f:
                placed.setdefault(json.loads(ln)["session"],
                                  set()).add(w.idx)
    for s in sessions:
        assert placed.get(s["sid"]) == {s["worker"]}, \
            f"session {s['sid']} left worker {s['worker']}: " \
            f"{placed.get(s['sid'])}"


def test_fleet_stats_and_metrics_aggregate(fleet, sessions):
    fs = fleet.handle({"op": "fleet_stats"})
    assert fs["ok"] and len(fs["workers"]) == 2
    m = fleet.handle({"op": "metrics"})["metrics"]
    assert m["sessions"] == 2
    assert m["completed"] >= 4


def test_route_fault_is_answered_not_fatal(fleet, sessions):
    os.environ["YT_FAULT_PLAN"] = "fleet.route:backend_unavailable:1"
    reset_faults()
    try:
        r = fleet.handle({"op": "run", "sid": sessions[0]["sid"],
                          "first": 2 * STEPS, "last": 2 * STEPS})
        assert not r["ok"] and "error" in r, r
    finally:
        del os.environ["YT_FAULT_PLAN"]
        reset_faults()
    # the front survives and the session keeps serving
    r = fleet.handle({"op": "run", "sid": sessions[0]["sid"],
                      "first": 2 * STEPS, "last": 2 * STEPS})
    assert r["ok"], r
