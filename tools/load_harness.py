#!/usr/bin/env python
"""Chaos traffic-replay load harness for the serving fleet.

Drives an in-process :class:`tools.serve_fleet.ServeFleet` with an
OPEN-LOOP arrival process (arrivals fire on the wall clock whether or
not earlier requests answered — the shape that actually builds queues)
and prints the latency/goodput summary as one JSON line:

* **Arrival processes** (``--arrivals``): seeded ``poisson`` /
  ``uniform`` (deterministic gaps) / ``step`` (rate doubles at the
  midpoint) / ``spike`` (a ``--spike-mult`` burst through the middle
  third).  Across tenants the loop is open; PER tenant it is closed
  (one in-flight request per session — the scheduler serializes a
  session's requests anyway, and step ranges must stay contiguous).
* **Replay** (``--replay PATH``): re-drives a recorded
  ``SERVE_JOURNAL`` — the ``received`` rows' original tenant mix and
  inter-arrival gaps (scaled by ``--replay-speed``) become the
  schedule, so a production trace reproduces under test.
* **Chaos soak** (``--soak``): one seeded ``YT_FAULT_PLAN`` composes a
  ``load.arrival`` load spike with worker-side ``fleet.kill_worker``,
  ``fleet.hang_worker`` and ``serve.respond`` zero-output corruption,
  all concurrent with the offered load.  The acceptance gate is NOT
  throughput: every completed (``ok``) response must be bit-identical
  to a solo in-process ``StencilServer`` oracle at the same chunk
  boundary, corrupted outputs may only surface quarantined
  (``status == "anomaly"``), every applied step range is applied
  exactly once (contiguous per-tenant coverage + at most one
  journaled ``retry`` per idempotency key).
* **Loadcheck** (``--check``): the seeded, deterministic CPU-mesh
  scenario ``make loadcheck`` gates on — a latency-SLO burn spike
  trips the autoscaler (journaled ``scale_up`` joined to the breach
  trace, warm spawn with zero lowerings), the queue drains, admission
  recovers, idle ticks drain + retire the extra worker with zero lost
  sessions.

The summary line carries ``p50_ms`` / ``p99_ms`` and ``goodput``
(ok/offered); under the soak, injected kills are SUPPOSED to dent
goodput.

The harness performs no device work itself: every request is a fleet
``handle()`` call (guarded sites live in the workers), and the oracle
runs through the serve package's own guarded scheduler.
"""

from __future__ import annotations

import argparse
import calendar
import json
import os
import random
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_PROFILE = {"stencil": "iso3dfd", "radius": 1, "g": 8, "wf": 2}


# ---------------------------------------------------------- schedules

def arrivals(kind: str, rate: float, duration: float,
             rng: random.Random, spike_mult: float = 4.0) -> List[float]:
    """Arrival offsets (seconds from t0) for one open-loop process."""
    rate = max(rate, 1e-9)
    if kind == "uniform":
        gap = 1.0 / rate
        n = int(duration * rate)
        return [i * gap for i in range(n)]
    if kind == "poisson":
        out, t = [], 0.0
        while True:
            t += rng.expovariate(rate)
            if t >= duration:
                return out
            out.append(t)
    if kind == "step":
        half = duration / 2.0
        lo = arrivals("poisson", rate, half, rng)
        hi = arrivals("poisson", rate * spike_mult,
                      duration - half, rng)
        return lo + [half + t for t in hi]
    if kind == "spike":
        third = duration / 3.0
        base = arrivals("poisson", rate, duration, rng)
        burst = arrivals("poisson", rate * spike_mult, third, rng)
        return sorted(base + [third + t for t in burst])
    raise ValueError(f"unknown arrival process {kind!r}")


def replay_arrivals(journal_path: str, speed: float = 1.0) \
        -> List[Tuple[float, str]]:
    """(offset, tenant) pairs from a recorded serve journal's
    ``received`` rows — the original tenant mix and gaps (journal ts
    resolution is 1 s; ``speed`` > 1 compresses the gaps)."""
    speed = max(speed, 1e-9)
    rows: List[Tuple[float, str]] = []
    t0: Optional[float] = None
    with open(journal_path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line or '"received"' not in line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if row.get("event") != "received":
                continue
            try:
                ts = calendar.timegm(time.strptime(
                    row.get("ts", ""), "%Y-%m-%dT%H:%M:%SZ"))
            except ValueError:
                continue
            if t0 is None:
                t0 = float(ts)
            rows.append(((ts - t0) / speed,
                         str(row.get("session", "tenant-0"))))
    return rows


# ------------------------------------------------------------ harness

class LoadHarness:
    """Open-loop driver over an in-process fleet front."""

    def __init__(self, fleet, tenants: int = 2, steps: int = 2,
                 flush_every: int = 0, deadline: float = 0.0,
                 spike_burst: int = 8, profile: Optional[Dict] = None,
                 rng: Optional[random.Random] = None):
        self.fleet = fleet
        self.steps = max(1, int(steps))
        self.flush_every = int(flush_every)
        self.deadline = float(deadline)
        self.spike_burst = max(0, int(spike_burst))
        self.profile = dict(profile or DEFAULT_PROFILE)
        self.rng = rng or random.Random(0)
        self.results: List[Dict] = []
        self._rlock = threading.Lock()
        self.sids: Dict[str, str] = {}           # tenant -> fleet sid
        self._next_step: Dict[str, int] = {}
        self._tlocks: Dict[str, threading.Lock] = {}
        self.offered = 0
        self._tenant_names = [f"tenant-{i}" for i in range(max(1, tenants))]

    def open_tenants(self) -> None:
        for name in self._tenant_names:
            out = self.fleet.handle({"op": "open", **self.profile})
            if not out.get("ok"):
                raise RuntimeError(f"open failed for {name}: {out}")
            sid = out["sid"]
            ini = self.fleet.handle({"op": "init", "sid": sid})
            if not ini.get("ok"):
                raise RuntimeError(f"init failed for {name}: {ini}")
            self.sids[name] = sid
            self._next_step[name] = 0
            self._tlocks[name] = threading.Lock()

    # one request: closed-loop per tenant (contiguous step ranges),
    # open-loop across tenants (the dispatcher never waits on this)
    def _issue(self, tenant: str) -> None:
        with self._tlocks[tenant]:
            first = self._next_step[tenant]
            last = first + self.steps - 1
            msg = {"op": "run", "sid": self.sids[tenant],
                   "first": first, "last": last}
            if self.flush_every > 0:
                msg["flush_every"] = self.flush_every
            if self.deadline > 0:
                msg["deadline"] = self.deadline
            t0 = time.perf_counter()
            try:
                out = self.fleet.handle(msg)
            except Exception as e:  # noqa: BLE001 - a lost answer is a
                # data point, not a harness crash
                out = {"ok": False,
                       "error": f"{type(e).__name__}: {e}"}
            ms = (time.perf_counter() - t0) * 1000.0
            status = str(out.get("status", ""))
            ok = bool(out.get("ok"))
            if not status:
                status = "ok" if ok else "error"
            # ok AND anomaly both ran to completion server-side: the
            # session advanced, so the next range follows contiguously
            if ok or status == "anomaly":
                self._next_step[tenant] = last + 1
            rec = {"tenant": tenant, "sid": self.sids[tenant],
                   "first": first, "last": last, "ok": ok,
                   "status": status, "latency_ms": ms,
                   "overloaded": bool(out.get("overloaded")),
                   "retry_after": out.get("retry_after"),
                   "error": str(out.get("error", ""))[:200],
                   "trace": str(out.get("trace", ""))}
            if ok:
                rec["outputs"] = out.get("outputs") or {}
            if out.get("anomaly"):
                rec["anomaly"] = out["anomaly"]
            with self._rlock:
                self.results.append(rec)

    def drive(self, schedule: List) -> int:
        """Run one schedule: floats (round-robin tenants) or
        (offset, tenant) pairs (replay).  Each arrival probes the
        ``load.arrival`` chaos site — an injected LoadSpike answers
        with an immediate burst of ``spike_burst`` extra arrivals.
        Returns the offered-request count (burst included)."""
        from yask_tpu.resilience.faults import Fault, LoadSpike, \
            fault_point
        threads: List[threading.Thread] = []
        names = list(self.sids)
        t0 = time.perf_counter()

        def launch(tenant: str) -> None:
            th = threading.Thread(target=self._issue, args=(tenant,),
                                  daemon=True)
            th.start()
            threads.append(th)
            self.offered += 1

        for i, item in enumerate(schedule):
            off, tenant = item if isinstance(item, tuple) \
                else (item, names[i % len(names)])
            if tenant not in self.sids:
                tenant = names[i % len(names)]
            delay = t0 + float(off) - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            burst = 0
            try:
                fault_point("load.arrival")
            except LoadSpike:
                burst = self.spike_burst
            except Fault:
                continue  # any other injected fault drops the arrival
            launch(tenant)
            for j in range(burst):
                launch(names[(i + 1 + j) % len(names)])
        for th in threads:
            th.join(timeout=600.0)
        return self.offered

    # ------------------------------------------------------- metrics

    def summary(self) -> Dict:
        lat = sorted(r["latency_ms"] for r in self.results if r["ok"])

        def pct(p: float) -> float:
            if not lat:
                return 0.0
            return lat[min(len(lat) - 1, int(p * (len(lat) - 1)))]

        n_ok = sum(1 for r in self.results if r["ok"])
        n_anom = sum(1 for r in self.results
                     if r["status"] == "anomaly")
        n_shed = sum(1 for r in self.results if r["overloaded"])
        offered = max(1, self.offered)
        return {"offered": self.offered, "completed": len(self.results),
                "ok": n_ok, "anomaly": n_anom, "overloaded": n_shed,
                "goodput": n_ok / offered,
                "p50_ms": pct(0.50), "p99_ms": pct(0.99)}

    # -------------------------------------------------------- audits

    def oracle_outputs(self, journal_path: str) -> Dict[int, Dict]:
        """Solo oracle: one in-process StencilServer runs the SAME
        profile through the SAME chunk boundaries (all tenants share
        the profile and deterministic init, so expected outputs depend
        only on the chunk's last step).  Runs with faults cleared —
        the oracle must be the uninjected twin."""
        import numpy as np
        from yask_tpu.serve import ServeRequest, StencilServer
        bounds = sorted({(r["first"], r["last"])
                         for r in self.results
                         if r["ok"] or r["status"] == "anomaly"})
        srv = StencilServer(journal_path=journal_path, preflight=False)
        self.oracle_anomalies = set()
        try:
            sid = srv.open_session(**self.profile)
            srv.init_vars(sid)
            out: Dict[int, Dict] = {}
            for first, last in bounds:
                h = srv.submit(ServeRequest(session=sid,
                                            first_step=first,
                                            last_step=last))
                r = srv.wait(h)
                if r.status == "anomaly":
                    # the UNINJECTED twin flags this boundary too:
                    # genuine physics (the undamped test profile grows
                    # to nonfinite past enough steps), not corruption —
                    # fleet answers here must ALSO be quarantined
                    self.oracle_anomalies.add(last)
                elif r.status != "ok":
                    raise RuntimeError(
                        f"oracle run [{first},{last}] not ok: "
                        f"{r.status} {r.error}")
                out[last] = {k: np.asarray(v)
                             for k, v in (r.outputs or {}).items()}
            return out
        finally:
            srv.shutdown()

    def audit(self, oracle: Optional[Dict[int, Dict]] = None,
              fleet_journal_rows: Optional[List[Dict]] = None) -> Dict:
        """The soak acceptance gate.  Raises AssertionError on any
        violation; returns the audit tally."""
        import numpy as np
        from tools.serve_client import decode_array
        compared = 0
        anom_bounds = getattr(self, "oracle_anomalies", set())
        for r in self.results:
            if r["status"] == "anomaly":
                # corrupted outputs may only surface quarantined —
                # never as a clean ok answer
                assert not r["ok"], f"anomaly released as ok: {r}"
                assert r.get("anomaly"), \
                    f"anomaly row without a structured verdict: {r}"
                continue
            if not r["ok"] or oracle is None:
                continue
            # sanity consistency: a boundary the uninjected oracle
            # quarantines can never be released clean by the fleet
            assert r["last"] not in anom_bounds, \
                f"oracle flags step {r['last']} anomalous but the " \
                f"fleet released it clean: {r}"
            exp = oracle.get(r["last"])
            assert exp is not None, \
                f"oracle has no boundary for step {r['last']}"
            for name, enc in (r.get("outputs") or {}).items():
                got = decode_array(enc)
                assert np.array_equal(got, np.asarray(exp[name])), \
                    f"{r['tenant']} [{r['first']},{r['last']}] " \
                    f"{name}: completed response diverged from the " \
                    f"solo oracle"
                compared += 1
        # exactly-once: per tenant, applied ranges tile [0, hi] with
        # no gap and no overlap
        for tenant in self.sids:
            done = sorted((r["first"], r["last"])
                          for r in self.results
                          if r["tenant"] == tenant
                          and (r["ok"] or r["status"] == "anomaly"))
            expect = 0
            for first, last in done:
                assert first == expect, \
                    f"{tenant}: step range [{first},{last}] applied " \
                    f"out of sequence (expected first={expect} — a " \
                    f"duplicate or lost application)"
                expect = last + 1
        # at most ONE journaled retry per idempotency key
        if fleet_journal_rows is not None:
            seen: Dict[str, int] = {}
            for row in fleet_journal_rows:
                if row.get("event") != "retry":
                    continue
                idem = str((row.get("detail") or {}).get("idem", ""))
                seen[idem] = seen.get(idem, 0) + 1
            dup = {k: v for k, v in seen.items() if v > 1}
            assert not dup, f"idempotency keys retried twice: {dup}"
        return {"bit_identical_arrays": compared,
                "oracle_anomalies": len(anom_bounds),
                "tenants": len(self.sids),
                "retries": 0 if fleet_journal_rows is None else sum(
                    1 for row in fleet_journal_rows
                    if row.get("event") == "retry")}


# ------------------------------------------------------------ helpers

def _fleet_env() -> None:
    """Process-env default every harness mode needs: the CPU platform
    unless another is named (a multi-worker fleet is CPU-only — one
    process per chip)."""
    if not os.environ.get("JAX_PLATFORMS"):
        os.environ["JAX_PLATFORMS"] = "cpu"


def _make_fleet(workdir: str, workers: int, autoscale=None):
    from tools.serve_fleet import ServeFleet
    return ServeFleet(
        n_workers=workers,
        cache_dir=os.path.join(workdir, "cache"),
        journal_dir=workdir,
        worker_args=["--no-preflight", "--window_ms", "5"],
        hb_secs=0.0, autoscale=autoscale)


def _fleet_rows(workdir: str) -> List[Dict]:
    from yask_tpu.serve.journal import ServeJournal
    return ServeJournal(os.path.join(
        workdir, "SERVE_JOURNAL.fleet.jsonl")).rows()


# -------------------------------------------------------------- modes

def run_load(args, workdir: str) -> int:
    """Plain load run (or replay): drive, audit against the oracle,
    print the summary."""
    _fleet_env()
    rng = random.Random(args.seed)
    fleet = _make_fleet(workdir, args.workers)
    try:
        h = LoadHarness(fleet, tenants=args.tenants, steps=args.steps,
                        flush_every=args.flush_every,
                        deadline=args.deadline, rng=rng)
        h.open_tenants()
        if args.replay:
            sched = replay_arrivals(args.replay, args.replay_speed)
            # re-map recorded tenants onto our sessions, preserving
            # the mix: distinct recorded names -> round-robin tenants
            names = sorted({t for _o, t in sched})
            ours = list(h.sids)
            remap = {n: ours[i % len(ours)]
                     for i, n in enumerate(names)}
            sched = [(o, remap[t]) for o, t in sched]
        else:
            sched = arrivals(args.arrivals, args.rate, args.duration,
                             rng, spike_mult=args.spike_mult)
        h.drive(sched)
        s = h.summary()
        oracle = None
        if not args.no_oracle:
            oracle = h.oracle_outputs(os.path.join(
                workdir, "SERVE_JOURNAL.oracle.jsonl"))
        tally = h.audit(oracle, _fleet_rows(workdir))
        print(json.dumps({"summary": s, "audit": tally},
                         sort_keys=True))
        return 0
    finally:
        fleet.close()


def run_soak(args, workdir: str) -> int:
    """Seeded chaos soak: load spike + worker kill + hang + zero
    output, all under one YT_FAULT_PLAN, gated on exactly-once +
    bit-identity (docs/resilience.md)."""
    from yask_tpu.resilience.faults import reset_faults
    _fleet_env()
    plan = [
        {"site": "load.arrival", "kind": "load_spike",
         "times": 2, "after": 3},
        {"site": "fleet.kill_worker", "kind": "worker_dead",
         "times": 1, "after": 5},
        {"site": "fleet.hang_worker", "kind": "hang",
         "secs": 0.3, "times": 1, "after": 9},
        {"site": "serve.respond", "kind": "zero_output",
         "times": 1, "after": 4},
    ]
    os.environ["YT_FAULT_PLAN"] = json.dumps(plan)
    reset_faults()
    rng = random.Random(args.seed)
    fleet = _make_fleet(workdir, max(2, args.workers))
    # replacements for chaos-killed workers must spawn CLEAN — the
    # injected plan applies to the first generation only
    fleet._base_env.pop("YT_FAULT_PLAN", None)
    try:
        h = LoadHarness(fleet, tenants=args.tenants, steps=args.steps,
                        flush_every=args.flush_every, spike_burst=4,
                        rng=rng)
        h.open_tenants()
        sched = arrivals("spike", args.rate, args.duration, rng,
                         spike_mult=args.spike_mult)
        h.drive(sched)
        # the oracle is the uninjected twin: clear the plan first
        os.environ.pop("YT_FAULT_PLAN", None)
        reset_faults()
        oracle = h.oracle_outputs(os.path.join(
            workdir, "SERVE_JOURNAL.oracle.jsonl"))
        tally = h.audit(oracle, _fleet_rows(workdir))
        s = h.summary()
        print(json.dumps({"summary": s, "audit": tally},
                         sort_keys=True))
        return 0
    finally:
        os.environ.pop("YT_FAULT_PLAN", None)
        reset_faults()
        fleet.close()


def run_check(args, workdir: str) -> int:
    """``make loadcheck``: the seeded closed-loop elastic scenario.
    Deterministic by construction (manual supervision ticks, burn
    thresholds, zero cooldown); a few CPU-timing-free assertions:

    1. a latency-burn spike trips a journaled ``scale_up`` (signal
       attached) and the fleet grows to 2 workers;
    2. the new worker warm-starts: first run answers with ZERO
       lowerings off the shared compile cache;
    3. admission recovers (a fresh open + run succeeds, queue empty);
    4. idle ticks drain the tail worker: ``scale_down`` row with the
       session migrated (zero lost), and the migrated session keeps
       serving contiguous steps.
    """
    saved = {k: os.environ.get(k) for k in (
        "YT_SLO_P99_MS", "YT_SLO_WINDOWS", "YT_FLEET_SCALE_UP_BURN",
        "YT_FLEET_SCALE_UP_QUEUE", "YT_FLEET_MIN_WORKERS",
        "YT_FLEET_MAX_WORKERS", "YT_FLEET_SCALE_COOLDOWN",
        "YT_FLEET_SCALE_DOWN_IDLE")}
    os.environ.update({
        "YT_SLO_P99_MS": "0.001",       # every request breaches
        "YT_SLO_WINDOWS": "2",          # short window: burn decays fast
        "YT_FLEET_SCALE_UP_BURN": "1.0",
        "YT_FLEET_SCALE_UP_QUEUE": "0",  # burn is the only trigger
        "YT_FLEET_MIN_WORKERS": "1",
        "YT_FLEET_MAX_WORKERS": "2",
        "YT_FLEET_SCALE_COOLDOWN": "0",
        "YT_FLEET_SCALE_DOWN_IDLE": "2",
    })
    _fleet_env()
    rng = random.Random(args.seed)
    fleet = _make_fleet(workdir, 1, autoscale=True)
    try:
        h = LoadHarness(fleet, tenants=2, steps=1, rng=rng)
        h.open_tenants()
        h.drive(arrivals("spike", 10.0, 1.0, rng, spike_mult=4.0))
        assert h.summary()["ok"] > 0, h.summary()

        # (1) the burn spike scales the fleet up, journaled
        fleet.supervise_tick()
        assert len(fleet.workers) == 2, \
            f"burn spike did not scale up ({len(fleet.workers)} workers)"
        ups = [r for r in _fleet_rows(workdir)
               if r.get("event") == "scale_up"]
        assert ups and "signal" in ups[-1].get("detail", {}), ups
        assert ups[-1]["detail"]["signal"]["max_burn"] >= 1.0, ups[-1]

        # (2) warm spawn: the new worker's first run = zero lowerings
        s = fleet.handle({"op": "open", **DEFAULT_PROFILE})
        assert s.get("ok") and s.get("worker") == 1, s
        ini = fleet.handle({"op": "init", "sid": s["sid"]})
        assert ini.get("ok"), ini
        r = fleet.handle({"op": "run", "sid": s["sid"],
                          "first": 0, "last": 0})
        assert r.get("ok"), r
        cs = fleet.handle({"op": "cache_stats"})["stats"]["1"]
        assert cs["lowerings"] == 0 and cs["disk_hits"] > 0, \
            f"scale-up worker re-lowered instead of warm-starting: {cs}"

        # (3) admission recovered: queue empty, fresh work flows
        m = fleet.handle({"op": "metrics"})["metrics"]
        assert m["queue_depth"] == 0, m
        r2 = fleet.handle({"op": "run", "sid": s["sid"],
                           "first": 1, "last": 1})
        assert r2.get("ok"), r2

        # (4) burn decays, idle ticks drain + retire the tail worker
        time.sleep(2.2)
        for _ in range(4):
            if len(fleet.workers) == 1:
                break
            fleet.supervise_tick()
        assert len(fleet.workers) == 1, "idle fleet did not scale down"
        downs = [r for r in _fleet_rows(workdir)
                 if r.get("event") == "scale_down"]
        assert downs, "no scale_down journal row"
        det = downs[-1].get("detail", {})
        assert s["sid"] in det.get("migrated", []), det
        assert det.get("lost") == [], det
        # the migrated session keeps serving, contiguous steps intact
        r3 = fleet.handle({"op": "run", "sid": s["sid"],
                           "first": 2, "last": 2})
        assert r3.get("ok"), f"migrated session lost after drain: {r3}"
        print(json.dumps({"loadcheck": "ok",
                          "scale_up": ups[-1]["detail"],
                          "scale_down": det}, sort_keys=True))
        return 0
    finally:
        fleet.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop / replay / chaos load harness for the "
                    "serving fleet")
    ap.add_argument("--arrivals", default="poisson",
                    choices=("poisson", "uniform", "step", "spike"))
    ap.add_argument("--rate", type=float, default=10.0,
                    help="offered arrivals per second")
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--spike-mult", type=float, default=4.0)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--steps", type=int, default=2,
                    help="steps per request")
    ap.add_argument("--flush-every", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request queue+run deadline seconds")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--replay", default=None,
                    help="re-drive a recorded SERVE_JOURNAL's "
                         "received rows (original tenant mix)")
    ap.add_argument("--replay-speed", type=float, default=1.0)
    ap.add_argument("--soak", action="store_true",
                    help="seeded chaos soak (load spike + worker "
                         "kill + hang + zero output)")
    ap.add_argument("--check", action="store_true",
                    help="deterministic loadcheck scenario (make "
                         "loadcheck)")
    ap.add_argument("--no-oracle", action="store_true",
                    help="skip the solo bit-identity oracle")
    ap.add_argument("--workdir", default=None,
                    help="scratch dir (default: a fresh temp dir)")
    args = ap.parse_args(argv)

    if args.workdir:
        workdir = args.workdir
        os.makedirs(workdir, exist_ok=True)
    else:
        import tempfile
        workdir = tempfile.mkdtemp(prefix="yt_load_")
    try:
        if args.check:
            return run_check(args, workdir)
        if args.soak:
            return run_soak(args, workdir)
        return run_load(args, workdir)
    except AssertionError as e:
        print(f"load_harness: FAIL: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
