#!/usr/bin/env python
"""A warm-cache serving FLEET: N ``tools/serve.py`` workers behind one
JSON-lines front.

Topology::

    client  ──stdio/TCP──  serve_fleet.py  ──stdio pipes──  worker 0
                                 │                          worker 1
                                 │                          ...
                           (routing table: sid -> worker)

* **Workers** are plain ``tools/serve.py`` stdio children
  (``tools/serve_client.py`` transport), each with its OWN journal
  (``SERVE_JOURNAL.w<i>.jsonl`` — per-worker lifecycle evidence, and
  how the affinity test proves a session never migrated) and a SHARED
  on-disk compile cache (``YT_COMPILE_CACHE``): worker 0's compiles
  land in the cache, so worker 1+'s first request deserializes with
  ZERO lowerings (``cache_stats``) — the fleet's scale-out contract.
* **Session affinity**: ``open`` places a session on one worker
  (admission control below) and every later op for that sid routes to
  the same worker — session state lives in worker memory, migration
  would lose it.  The fleet namespaces session ids (``f0000...``) so
  two workers can never hand out colliding ids.
* **Admission control**: placement reads each worker's live metrics
  (queue depth, open sessions — the same numbers the journal
  occupancy rows carry); the least-loaded worker wins, and when every
  worker's queue is past ``YT_FLEET_MAX_QUEUE`` (default 64) the op
  is rejected instead of queued — saturation answers fast, it does
  not time out slowly.  Routing decisions pass the ``fleet.route``
  fault point (``YT_FAULT_PLAN`` injectable; a classified fault
  rejects that op, it never kills the fleet).
* **Streaming** passes through: a worker's interleaved
  ``{"stream": true}`` lines are re-emitted to the fleet's client as
  they arrive (per-worker pipes are serialized by a lock, so a
  stream line can only belong to the in-flight call on that worker).
* **Supervision** (fleet failover): workers are spawned in their own
  process group and health-checked — a dead process, a missed
  ``ping`` heartbeat past the liveness deadline
  (``YT_FLEET_HB_DEADLINE``, consecutive-miss threshold
  ``YT_FLEET_HB_MISSES``), or an EOF mid-op all declare the worker
  dead.  The front SIGKILLs the whole group (``run_deadlined``
  semantics), spawns a replacement that warm-starts from the shared
  compile cache, and FAILS THE SESSIONS OVER: each routed session is
  re-opened on the replacement (``session=sid``), restored from the
  last banked checkpoint (the ``snapshot``/``restore`` worker ops —
  r14 interior-coordinate snapshots, banked at a
  ``YT_FLEET_CKPT_EVERY``-step cadence on op boundaries), and the
  state-mutating ops since that committed boundary are replayed in
  order.  The recovered state is bit-identical to an uninterrupted
  twin (the r14 kill-resume contract at fleet scope).  An op in
  flight on the dead worker is re-issued EXACTLY ONCE under its
  idempotency key (``idem``, front-stamped on every forwarded op):
  the retry happens only when no response was delivered, against
  state rolled back to the last committed boundary, so its effects
  apply once.  Already-emitted ``{"stream": true}`` lines may repeat
  on a retried streaming run (streams are at-least-once; the final
  response is exactly-once).  Every migration is journaled
  (``SERVE_JOURNAL.fleet.jsonl``: ``worker_dead`` → ``failover`` with
  the dead worker id, snapshot step and replayed step ranges →
  ``retry``).

* **Elasticity** (``--autoscale`` / ``YT_FLEET_AUTOSCALE=1``): an
  SLO-driven policy loop (``yask_tpu/serve/autoscale.py``) rides the
  supervision cadence — scale UP warm-spawns a worker from the shared
  compile cache (first request: zero lowerings), scale DOWN drains
  the tail worker (stop admitting, in-flight runs finish, live
  sessions snapshot + migrate through the failover path) before the
  kill.  Every decision is a journaled ``scale_up`` / ``drain`` /
  ``scale_down`` row carrying the triggering signal; decisions read
  ONLY fresh telemetry (stale per-worker blocks are excluded — the
  autoscaler never scales on dead data).  Saturation rejections are
  structured: ``{"overloaded": true, "retry_after": ...}``
  (worker-side brownout tiers live in the scheduler; see
  ``docs/serving.md``).

The fleet front performs no device work itself — every op is a
forwarded worker call over pipes; the guarded device sites live in the
workers' serve package.  Chaos injection: ``fleet.route`` (front),
``fleet.heartbeat`` (front, a dropped heartbeat), ``fleet.scale`` /
``fleet.drain`` (front, an aborted scaling action), and the
worker-side ``fleet.kill_worker`` / ``fleet.hang_worker`` sites in
``tools/serve.py``.

Usage::

    python tools/serve_fleet.py --workers 2 --cache-dir /tmp/ytcache
    # then speak the tools/serve.py JSON-lines protocol on stdio, or
    # --port for TCP.  Extra ops: {"op": "fleet_stats"} and
    # {"op": "metrics_snapshot"} — the latter answers the merged
    # fleet-wide telemetry snapshot (yask_tpu.obs.telemetry: histogram
    # sample windows pooled and re-ranked, never averaged percentiles);
    # the heartbeat loop banks the same snapshot every tick.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.serve_client import ServeClient, ServeClientError


def fleet_max_queue() -> int:
    try:
        return max(1, int(os.environ.get("YT_FLEET_MAX_QUEUE", "")
                          or 64))
    except ValueError:
        return 64


def _env_num(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def fleet_ckpt_every() -> int:
    """Checkpoint cadence in steps (``YT_FLEET_CKPT_EVERY``, default
    8): after a session accumulates this many run steps past its last
    banked snapshot, the front banks a fresh one at the next op
    boundary."""
    return max(1, int(_env_num("YT_FLEET_CKPT_EVERY", 8)))


def fleet_hb_deadline() -> float:
    """Heartbeat liveness deadline in seconds (``YT_FLEET_HB_DEADLINE``,
    default 10): a ``ping`` that has not answered by then is a miss."""
    return max(0.1, _env_num("YT_FLEET_HB_DEADLINE", 10.0))


def fleet_hb_misses() -> int:
    """Consecutive heartbeat misses before a worker is declared
    unhealthy and replaced (``YT_FLEET_HB_MISSES``, default 2)."""
    return max(1, int(_env_num("YT_FLEET_HB_MISSES", 2)))


class FleetWorker:
    """One spawned serve.py child + its pipe lock and journal path."""

    def __init__(self, idx: int, client: ServeClient,
                 journal_path: str, gen: int = 0):
        self.idx = idx
        self.gen = gen  # bumped on every replacement spawn
        self.client = client
        self.journal_path = journal_path
        self.lock = threading.Lock()  # serializes this worker's pipe
        self.sessions: set = set()
        self.hb_misses = 0
        #: set by the autoscaler ahead of retirement: a draining
        #: worker admits NO new sessions; in-flight work finishes and
        #: live sessions migrate before the kill.
        self.draining = False

    def alive(self) -> bool:
        """Process liveness (with a short grace for the EOF→exit
        race).  Socket-transport clients are assumed alive — only the
        spawned-worker topology is supervised."""
        p = self.client._proc
        if p is None:
            return True
        try:
            p.wait(timeout=1.0)
            return False
        except subprocess.TimeoutExpired:
            return True

    def call(self, op: str, on_stream=None, **fields) -> Dict:
        with self.lock:
            prev = self.client.on_stream
            self.client.on_stream = on_stream
            try:
                out = self.client.call(op, **fields)
            finally:
                self.client.on_stream = prev
        # the pipe-level request id is this worker-client's own; the
        # fleet front re-stamps its client's id in handle()
        out.pop("id", None)
        return out

    def occupancy(self) -> Dict:
        """Live load numbers for admission (falls back to the local
        session count when the worker cannot answer)."""
        try:
            m = self.call("metrics")["metrics"]
            return {"queue_depth": int(m.get("queue_depth", 0)),
                    "sessions": int(m.get("sessions", 0)),
                    "completed": int(m.get("completed", 0))}
        except (ServeClientError, OSError, ValueError):
            return {"queue_depth": 0, "sessions": len(self.sessions),
                    "completed": -1}


class ServeFleet:
    """The routing front: spawns the workers, owns the sid->worker
    table, forwards ops."""

    def __init__(self, n_workers: int = 2,
                 cache_dir: Optional[str] = None,
                 journal_dir: Optional[str] = None,
                 worker_args: List[str] = (),
                 env: Optional[Dict[str, str]] = None,
                 hb_secs: Optional[float] = None,
                 autoscale=None):
        from yask_tpu.serve.journal import ServeJournal
        self.closing = threading.Event()
        self._route_table: Dict[str, FleetWorker] = {}
        self._lock = threading.RLock()
        self._next_sid = 0
        self._next_idem = 0
        #: per-sid failover bank: stored open fields, the last banked
        #: checkpoint (raw wire form — passed back to ``restore``
        #: verbatim), and the state-mutating ops since that boundary.
        self._bank: Dict[str, Dict] = {}
        self._jdir = journal_dir or os.getcwd()
        base_env = dict(os.environ if env is None else env)
        if cache_dir:
            base_env["YT_COMPILE_CACHE"] = cache_dir
        self.cache_dir = base_env.get("YT_COMPILE_CACHE", "")
        self._base_env = base_env
        self._worker_args = list(worker_args)
        #: the front's own lifecycle journal (worker_dead / snapshot /
        #: failover / retry — the auditable migration trail).
        self.journal = ServeJournal(os.path.join(
            self._jdir, "SERVE_JOURNAL.fleet.jsonl"))
        #: last merged telemetry snapshot (banked by the heartbeat
        #: loop / refreshed by ``op metrics_snapshot``).
        self._telemetry: Optional[Dict] = None
        #: per-worker-idx last GOOD snapshot poll: {"ts", "snap",
        #: "gen"}.  A busy worker's block is carried forward from here
        #: stamped with its age; past the staleness horizon it is
        #: flagged ``stale`` and excluded from the merged fold — the
        #: autoscaler must not scale on dead data.
        self._snap_bank: Dict[int, Dict] = {}
        #: the autoscaling policy loop (None = fixed-size fleet).
        #: ``autoscale`` may be True (env-tuned policy), an
        #: AutoscalePolicy instance (tests), or None → the
        #: YT_FLEET_AUTOSCALE master switch decides.
        self._autoscaler = None
        if autoscale is None:
            from yask_tpu.serve.autoscale import fleet_autoscale_enabled
            autoscale = fleet_autoscale_enabled()
        if autoscale:
            from yask_tpu.serve.autoscale import AutoscalePolicy
            self._autoscaler = autoscale \
                if isinstance(autoscale, AutoscalePolicy) \
                else AutoscalePolicy.from_env()
        self.workers: List[FleetWorker] = []
        refused = int(n_workers) > 1 and self._one_chip_one_process()
        if refused:
            raise RuntimeError(refused)
        for i in range(max(1, int(n_workers))):
            self.workers.append(self._spawn_worker(i))
        self._hb_secs = _env_num("YT_FLEET_HB_SECS", 0.0) \
            if hb_secs is None else float(hb_secs)
        self._hb_thread = None
        if self._hb_secs > 0:
            self._hb_thread = threading.Thread(
                target=self._hb_loop, daemon=True)
            self._hb_thread.start()

    def _one_chip_one_process(self) -> str:
        """Why this fleet may not run a SECOND worker process ("" when
        it may).  Every worker opens the default backend, and a chip
        belongs to one process at a time: against a TPU the second
        worker fails or hangs at backend init.  Until workers are
        handed their own devices (ROADMAP R7/D10) more than one worker
        is admitted only on the CPU backend, requested by name."""
        if self._base_env.get("JAX_PLATFORMS", "").startswith("cpu"):
            return ""
        return ("serve_fleet: more than one worker needs "
                "JAX_PLATFORMS=cpu set by name — every worker opens "
                "the default backend, and a TPU chip belongs to one "
                "process at a time (a second worker would fail or "
                "hang); run --workers 1 on a chip")

    def _spawn_worker(self, idx: int, gen: int = 0) -> FleetWorker:
        """Spawn worker ``idx`` (its own process group so an unhealthy
        one dies whole via killpg; replacements reuse the journal path
        and warm-start from the shared compile cache)."""
        jpath = os.path.join(self._jdir, f"SERVE_JOURNAL.w{idx}.jsonl")
        wenv = dict(self._base_env)
        wenv["YT_SERVE_JOURNAL"] = jpath
        client = ServeClient.spawn(
            extra_args=list(self._worker_args),
            env=wenv, stderr=subprocess.DEVNULL,
            start_new_session=True)
        return FleetWorker(idx, client, jpath, gen=gen)

    # --------------------------------------------------------- routing

    def _worker_at(self, idx: int) -> Optional[FleetWorker]:
        """Bounds-safe slot lookup (caller need not hold the lock for
        a racy identity probe).  After a scale-down pops the tail, a
        stale worker ref's idx can exceed the list — that worker was
        retired, not replaced, and the answer is None."""
        with self._lock:
            if 0 <= idx < len(self.workers):
                return self.workers[idx]
        return None

    def _route(self, sid: str) -> FleetWorker:
        """Affinity: the worker that owns this session."""
        from yask_tpu.resilience.faults import fault_point
        fault_point("fleet.route")
        with self._lock:
            w = self._route_table.get(str(sid))
        if w is None:
            raise ServeClientError(
                f"unknown fleet session {sid!r} (not opened through "
                "this fleet, or already closed)")
        return w

    def _admit(self) -> FleetWorker:
        """Placement for a new session: least-loaded NON-DRAINING
        worker by live queue depth then session count; reject with a
        structured :class:`Overloaded` (Retry-After hint, journaled
        ``overloaded`` row) when the whole fleet is past the queue
        bound — saturation answers fast, it does not time out
        slowly."""
        from yask_tpu.resilience.faults import fault_point
        from yask_tpu.serve.api import Overloaded, serve_retry_after
        fault_point("fleet.route")
        cands = [w for w in list(self.workers) if not w.draining] \
            or list(self.workers)
        occ = [(w, w.occupancy()) for w in cands]
        bound = fleet_max_queue()
        if all(o["queue_depth"] >= bound for _w, o in occ):
            ra = serve_retry_after()
            self.journal.record(
                "-", "-", "overloaded", tier=2, retry_after=ra,
                queue_bound=bound, workers=len(occ))
            raise Overloaded(
                f"fleet saturated: every worker's queue depth >= "
                f"{bound} (YT_FLEET_MAX_QUEUE)", retry_after=ra)
        occ.sort(key=lambda t: (t[1]["queue_depth"],
                                t[1]["sessions"], t[0].idx))
        return occ[0][0]

    # --------------------------------------------------- supervision

    def _hb_loop(self) -> None:
        while not self.closing.wait(self._hb_secs):
            try:
                self.supervise_tick()
            except Exception:  # noqa: BLE001 - supervision must not
                pass           # take the front down

    def supervise_tick(self) -> None:
        """One synchronous health pass over the fleet (the background
        loop calls this every ``hb_secs``; tests call it directly).
        A dead process fails over immediately; an idle worker gets a
        ``ping`` under the liveness deadline — ``YT_FLEET_HB_MISSES``
        consecutive misses declare it unhealthy.  Busy workers are
        skipped: the in-flight call path detects death by EOF."""
        for w in list(self.workers):
            if self._worker_at(w.idx) is not w:
                continue  # replaced or retired since we listed
            if not w.alive():
                self._failover(w, cause="worker process exited")
                continue
            if not w.lock.acquire(blocking=False):
                continue
            try:
                ok = self._ping_deadlined(w)
            finally:
                w.lock.release()
            if ok:
                w.hb_misses = 0
                continue
            w.hb_misses += 1
            if w.hb_misses >= fleet_hb_misses():
                self._failover(
                    w, cause=f"missed {w.hb_misses} heartbeats "
                             f"(deadline {fleet_hb_deadline()}s)")
        # telemetry rides the same cadence: bank one merged fleet
        # snapshot per tick (busy workers are skipped, not queued
        # behind — a stale per-worker block beats a stalled heartbeat)
        try:
            self.collect_telemetry(block=False)
        except Exception:  # noqa: BLE001 - telemetry must not take
            pass           # supervision down
        # elastic sizing rides the same cadence, AFTER the telemetry
        # bank so decisions read this tick's freshness stamps
        try:
            self.autoscale_tick()
        except Exception:  # noqa: BLE001 - scaling must not take
            pass           # supervision down

    def _ping_deadlined(self, w: FleetWorker) -> bool:
        """One heartbeat under the liveness deadline.  Caller holds
        ``w.lock``.  ``fleet.heartbeat`` is the front-side chaos site:
        an injected fault here IS a dropped heartbeat.  The ping runs
        on a helper thread because a hung worker never answers — a
        blocked pipe read must cost the deadline, not the supervisor
        (``run_deadlined``'s contract without the subprocess)."""
        from yask_tpu.resilience.faults import Fault, fault_point
        try:
            fault_point("fleet.heartbeat")
        except Fault:
            return False
        result: Dict = {}

        def do_ping():
            try:
                result["out"] = w.client.call("ping")
            except Exception as e:  # noqa: BLE001
                result["err"] = e

        t = threading.Thread(target=do_ping, daemon=True)
        t.start()
        t.join(fleet_hb_deadline())
        return (not t.is_alive()) and "out" in result

    def _stale_after(self) -> float:
        """The staleness horizon: a per-worker block older than 3
        heartbeat intervals is dead data (3 missed polls ≈ the worker
        is hung or the loop is wedged).  Falls back to the liveness
        deadline when no background loop runs (tests tick manually)."""
        base = self._hb_secs if self._hb_secs > 0 \
            else fleet_hb_deadline()
        return 3.0 * base

    def collect_telemetry(self, block: bool = True) -> Dict:
        """Poll every worker's ``metrics_snapshot`` and merge into ONE
        fleet snapshot (``yask_tpu.obs.telemetry.merge_snapshots`` —
        histogram sample windows pooled and re-ranked; counters/gauges
        summed; per-worker blocks kept).  ``block=False`` is the
        heartbeat path: a busy worker is skipped rather than queued
        behind its in-flight op — its LAST GOOD block is carried
        forward instead, stamped with ``poll_age_secs``, and flagged
        ``stale`` past :meth:`_stale_after` (``merge_snapshots``
        excludes flagged blocks from the fold and lists them in
        ``stale_workers``).  A replacement worker never inherits its
        predecessor's bank: carried blocks are gen-checked.  The
        merged snapshot is banked on the fleet for ``fleet_stats`` /
        ``op metrics_snapshot`` to answer from."""
        import time
        from yask_tpu.obs.telemetry import merge_snapshots
        now = time.time()
        horizon = self._stale_after()
        per: Dict[str, Dict] = {}
        for w in list(self.workers):
            wid = f"w{w.idx}"
            snap: Optional[Dict] = None
            err = ""
            if block:
                try:
                    out = w.call("metrics_snapshot")
                    snap = dict(out.get("snapshot") or {})
                except Exception as e:  # noqa: BLE001
                    err = f"{type(e).__name__}: {e}"
            elif w.lock.acquire(blocking=False):
                try:
                    out = w.client.call("metrics_snapshot")
                    snap = dict(out.get("snapshot") or {})
                except Exception:  # noqa: BLE001
                    snap = None
                finally:
                    w.lock.release()
            if snap is not None:
                snap["gen"] = w.gen
                snap["poll_age_secs"] = 0.0
                with self._lock:
                    self._snap_bank[w.idx] = {
                        "ts": now, "snap": dict(snap), "gen": w.gen}
                per[wid] = snap
                continue
            # busy or failed poll: carry the banked block forward,
            # honestly aged — never a block from an older generation
            with self._lock:
                b = self._snap_bank.get(w.idx)
            if b is not None and b["gen"] == w.gen:
                age = max(0.0, now - b["ts"])
                snap = dict(b["snap"])
                snap["poll_age_secs"] = age
                if age > horizon:
                    snap["stale"] = True
                per[wid] = snap
            elif err:
                per[wid] = {"error": err}
        merged = merge_snapshots(per, ts=now)
        with self._lock:
            self._telemetry = merged
        return merged

    def _failover(self, w: FleetWorker, cause="") -> FleetWorker:
        """Replace a dead/unhealthy worker and fail its sessions over.
        Idempotent per worker object: concurrent detectors (heartbeat
        loop, in-flight EOF) race to the fleet lock and the losers see
        the replacement already installed."""
        with self._lock:
            cur = self._worker_at(w.idx)
            if cur is not w:
                return cur if cur is not None else w
            self.journal.record(
                f"w{w.idx}.g{w.gen}", "-", "worker_dead",
                worker=w.idx, gen=w.gen, cause=str(cause)[:200],
                sessions=sorted(w.sessions))
            self._kill_worker(w)
            repl = self._spawn_worker(w.idx, gen=w.gen + 1)
            self.workers[w.idx] = repl
            self._recover_sessions(w, repl)
            return repl

    @staticmethod
    def _kill_worker(w: FleetWorker) -> None:
        """SIGKILL the worker's whole process group (it was spawned
        with ``start_new_session=True``) and drop the pipes — the
        ``run_deadlined`` semantics applied to a worker."""
        import signal
        p = w.client._proc
        if p is not None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                try:
                    p.kill()
                except (OSError, ProcessLookupError):
                    pass
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
        for f in (w.client._w, w.client._r):
            try:
                f.close()
            except Exception:  # noqa: BLE001
                pass

    def _recover_sessions(self, dead: FleetWorker,
                          repl: FleetWorker) -> None:
        """Re-open every session routed to the dead worker on the
        replacement, restore the banked checkpoint, and replay the
        state-mutating ops past that committed boundary (deterministic
        — the r14 contract makes the result bit-identical to an
        uninterrupted run).  Caller holds the fleet lock."""
        for sid in sorted(dead.sessions):
            self._recover_one(sid, dead, repl)

    def _recover_one(self, sid: str, src: FleetWorker,
                     dst: FleetWorker, cause: str = "failover") -> bool:
        """Migrate ONE session ``src`` → ``dst`` through the banked
        checkpoint + replay-log path; journals a ``failover`` row
        either way (``cause`` distinguishes a death from an autoscaler
        drain).  An unrecoverable session is dropped from routing so
        it cannot block the rest of the fleet."""
        b = self._bank.get(sid)
        try:
            if b is None:
                raise ServeClientError("no banked open fields")
            dst.call("open", **b["open"])
            snap_step = None
            if b["snapshot"] is not None:
                out = dst.call("restore", sid=sid,
                               meta=b["snapshot"]["meta"],
                               state=b["snapshot"]["state"])
                if not out.get("ok"):
                    raise ServeClientError(
                        "banked snapshot did not apply")
                snap_step = int(
                    b["snapshot"]["meta"].get("cur_step", 0))
            replayed = []
            for m in b["log"]:
                dst.call(m["op"], **{k: v for k, v in m.items()
                                     if k not in ("op", "id")})
                if m["op"] == "run":
                    replayed.append(
                        [int(m.get("first", 0)),
                         m.get("last")])
            with self._lock:
                self._route_table[sid] = dst
                dst.sessions.add(sid)
                src.sessions.discard(sid)
            self.journal.record(
                sid, sid, "failover", dead_worker=src.idx,
                dead_gen=src.gen, to_worker=dst.idx,
                to_gen=dst.gen, snapshot_step=snap_step,
                replayed=replayed, cause=cause)
            return True
        except Exception as e:  # noqa: BLE001 - an unrecoverable
            # session must not block the rest of the fleet
            with self._lock:
                self._route_table.pop(sid, None)
                src.sessions.discard(sid)
            self.journal.record(
                sid, sid, "failover", dead_worker=src.idx,
                dead_gen=src.gen, recovered=False, cause=cause,
                error=f"{type(e).__name__}: {e}")
            return False

    # ---------------------------------------------------- autoscaling

    def autoscale_tick(self) -> None:
        """One autoscaler pass (rides the supervision cadence, after
        the telemetry bank).  No-op on a fixed-size fleet.  The policy
        (yask_tpu/serve/autoscale.py) decides; this method is the
        mechanism: warm spawn from the shared compile cache on UP,
        drain + migrate + retire on DOWN."""
        if self._autoscaler is None:
            return
        from yask_tpu.serve.autoscale import signals_from_snapshot
        with self._lock:
            merged = self._telemetry
            n = len(self.workers)
            nd = sum(1 for w in self.workers if w.draining)
        sig = signals_from_snapshot(merged, n, nd)
        dec = self._autoscaler.decide(sig)
        if dec is None:
            return
        if dec.action == "up":
            self._scale_up(dec)
        elif dec.action == "down":
            self._scale_down(dec)

    def _scale_up(self, dec) -> Optional[FleetWorker]:
        """Append one worker (warm spawn: the shared YT_COMPILE_CACHE
        means its first request deserializes with zero lowerings) and
        journal the decision joined to the triggering trace."""
        from yask_tpu.resilience.faults import Fault, fault_point
        try:
            fault_point("fleet.scale")
        except Fault as e:
            self.journal.record(
                "-", "-", "fault", site="fleet.scale", kind=e.kind,
                error=str(e)[:200])
            return None
        refused = self._one_chip_one_process()
        if refused:
            self.journal.record("-", "-", "fault", site="fleet.scale",
                                kind="refused", error=refused)
            return None
        with self._lock:
            idx = len(self.workers)
            w = self._spawn_worker(idx)
            self.workers.append(w)
        self.journal.record(
            f"w{idx}.g0", "-", "scale_up",
            trace_id=self._latest_breach_trace(),
            worker=idx, reason=dec.reason, signal=dec.signal,
            cache_dir=self.cache_dir)
        return w

    def _scale_down(self, dec) -> None:
        """Retire the tail worker: journal ``drain``, stop admitting
        (``draining`` flag), migrate every live session through the
        checkpoint path, then kill and pop.  Only the TAIL is ever
        retired so ``idx == list position`` stays invariant."""
        from yask_tpu.resilience.faults import Fault, fault_point
        with self._lock:
            if len(self.workers) <= 1:
                return
            w = self.workers[-1]
            if w.draining:
                return  # a prior drain is still in flight
            w.draining = True
        self.journal.record(
            f"w{w.idx}.g{w.gen}", "-", "drain", worker=w.idx,
            gen=w.gen, reason=dec.reason, signal=dec.signal,
            sessions=sorted(w.sessions))
        try:
            fault_point("fleet.drain")
        except Fault as e:
            with self._lock:
                w.draining = False  # aborted: keep serving
            self.journal.record(
                "-", "-", "fault", site="fleet.drain", kind=e.kind,
                error=str(e)[:200])
            return
        self._drain_worker(w, dec)

    def _drain_worker(self, w: FleetWorker, dec) -> None:
        """The mechanism behind a ``scale_down``: snapshot each live
        session at the drain boundary (fresh checkpoint → zero
        replay), migrate it to the least-loaded surviving worker, then
        retire the drained worker.  Waiting on the worker lock inside
        ``snapshot`` naturally lets in-flight (chunked) runs finish
        first — nothing in flight is abandoned."""
        migrated: List[str] = []
        lost: List[str] = []
        for sid in sorted(w.sessions):
            self._bank_snapshot(sid)
            dst = self._pick_target(exclude=w)
            if dst is None:
                with self._lock:
                    self._route_table.pop(sid, None)
                    w.sessions.discard(sid)
                lost.append(sid)
                continue
            ok = self._recover_one(sid, w, dst, cause="drain")
            (migrated if ok else lost).append(sid)
        with self._lock:
            if self.workers and self.workers[-1] is w:
                self.workers.pop()
            self._snap_bank.pop(w.idx, None)
        self._kill_worker(w)
        self.journal.record(
            f"w{w.idx}.g{w.gen}", "-", "scale_down", worker=w.idx,
            gen=w.gen, reason=dec.reason, signal=dec.signal,
            migrated=migrated, lost=lost)

    def _pick_target(self, exclude: FleetWorker) \
            -> Optional[FleetWorker]:
        """Least-loaded live, non-draining worker other than
        ``exclude`` (the migration destination during a drain)."""
        cands = [w for w in list(self.workers)
                 if w is not exclude and not w.draining and w.alive()]
        if not cands:
            return None
        occ = [(w, w.occupancy()) for w in cands]
        occ.sort(key=lambda t: (t[1]["queue_depth"],
                                t[1]["sessions"], t[0].idx))
        return occ[0][0]

    def _latest_breach_trace(self) -> str:
        """The newest journaled ``slo_breach`` row's trace id across
        the worker journals — the join key a ``scale_up`` row carries
        back to the request that tripped the burn-rate signal (""
        when no breach was ever journaled or tracing is off)."""
        best_ts, best = "", ""
        for w in list(self.workers):
            try:
                with open(w.journal_path, "r",
                          encoding="utf-8") as f:
                    for line in f:
                        if '"slo_breach"' not in line:
                            continue
                        try:
                            row = json.loads(line)
                        except ValueError:
                            continue
                        if row.get("event") != "slo_breach":
                            continue
                        ts = str(row.get("ts", ""))
                        if ts >= best_ts:  # ISO-8601 sorts by time
                            best_ts = ts
                            best = str(row.get("trace_id", "") or "")
            except OSError:
                continue
        return best

    # -------------------------------------------------- checkpointing

    def _stamp_idem(self, msg: dict) -> str:
        """Front-generated idempotency key, stamped onto every
        forwarded op (workers ignore unknown fields).  A retry after
        failover carries the SAME key, and the journal ``retry`` row
        records it — the exactly-once audit trail."""
        with self._lock:
            idem = msg.get("idem") or f"i{self._next_idem:06d}"
            self._next_idem += 1
        msg["idem"] = idem
        return idem

    @staticmethod
    def _stamp_trace(msg: dict) -> str:
        """Front-stamped end-to-end trace id (same shape as
        ``_stamp_idem``): one id per client op, riding the forwarded
        wire msg so the worker's journal rows and a failover
        replay (the banked msg is re-issued verbatim, gen+1 included)
        all join the SAME trace.  No-op unless ``YT_TRACE`` is on —
        the msg is untouched and "" comes back."""
        from yask_tpu.obs.tracer import new_trace_id, trace_enabled
        tid = str(msg.get("trace", "") or "")
        if not tid and trace_enabled():
            tid = new_trace_id()
            msg["trace"] = tid
        return tid

    @staticmethod
    def _mutates(op: str) -> bool:
        return op in ("fill", "init", "run", "restore")

    def _note_ok(self, sid: str, msg: dict) -> None:
        """Bookkeeping after a successful forwarded op: log state
        mutations for replay; bank a fresh checkpoint once the
        session has run ``YT_FLEET_CKPT_EVERY`` steps past the last
        committed boundary."""
        op = msg.get("op", "")
        if not self._mutates(op):
            return
        with self._lock:
            b = self._bank.get(sid)
            if b is None:
                return
            b["log"].append(dict(msg))
            if op == "run":
                first = int(msg.get("first", 0))
                last = msg.get("last")
                b["steps"] += (1 if last is None
                               else max(1, int(last) - first + 1))
                due = b["steps"] >= fleet_ckpt_every()
            else:
                due = False
        if due:
            self._bank_snapshot(sid)

    def _bank_snapshot(self, sid: str) -> bool:
        """Pull a checkpoint from the owning worker and bank it as the
        session's committed boundary (clears the replay log).  Banked
        in raw wire form — ``restore`` gets it back verbatim, so the
        front never decodes arrays.  A failed snapshot just keeps the
        longer replay log: correctness does not depend on cadence."""
        try:
            w = self._route(sid)
            out = w.call("snapshot", sid=sid)
        except Exception:  # noqa: BLE001
            return False
        if not out.get("ok"):
            return False
        with self._lock:
            b = self._bank.get(sid)
            if b is None:
                return False
            b["snapshot"] = {"meta": out["meta"],
                             "state": out["state"]}
            b["log"] = []
            b["steps"] = 0
        self.journal.record(
            sid, sid, "snapshot",
            step=int(out["meta"].get("cur_step", 0)), worker=w.idx)
        return True

    def _maybe_snapshot_before_run(self, sid: str) -> None:
        """Pre-run commit point: bank a checkpoint when none exists
        yet or when un-snapshotted fills/inits are in the log (state
        writes are cheaper to bank once than to hold for replay
        forever)."""
        with self._lock:
            b = self._bank.get(sid)
            need = b is not None and (
                b["snapshot"] is None
                or any(m.get("op") != "run" for m in b["log"]))
        if need:
            self._bank_snapshot(sid)

    # ------------------------------------------------------------- ops

    def handle(self, msg: dict, emit=None) -> dict:
        op = msg.get("op")
        fn = getattr(self, f"op_{op}", None)
        from yask_tpu.obs.tracer import activate, span
        from yask_tpu.serve.api import Overloaded
        tid = self._stamp_trace(msg)
        try:
            with activate(tid), \
                    span(f"fleet.{op}", phase="front", trace=tid,
                         sid=msg.get("sid", "")):
                if fn is not None:
                    out = fn(msg, emit)
                elif "sid" in msg:
                    # any other session-scoped op: pure affinity forward
                    out = self._forward(msg, emit)
                else:
                    out = {"ok": False, "error": f"unknown op {op!r}"}
        except Overloaded as e:
            # structured rejection: clients key on "overloaded" and
            # honor the Retry-After hint, no error-string parsing
            out = {"ok": False, "error": f"Overloaded: {e}",
                   "overloaded": True,
                   "retry_after": float(e.retry_after)}
        except Exception as e:  # noqa: BLE001 - the front must answer
            out = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        if "id" in msg:
            out["id"] = msg["id"]
        if tid and "trace" not in out:
            out["trace"] = tid
        return out

    def _forward(self, msg: dict, emit=None) -> dict:
        sid = msg["sid"]
        if msg.get("op") == "run":
            self._maybe_snapshot_before_run(sid)
        out = self._call_with_failover(msg, emit, sids=(sid,))
        # anomaly runs DID execute (sanity quarantined the outputs but
        # worker state advanced) — they must enter the replay log or a
        # later failover restores a state missing those steps.
        if out.get("ok") or out.get("status") == "anomaly":
            self._note_ok(sid, msg)
        return out

    def _call_with_failover(self, msg: dict, emit=None,
                            sids=()) -> dict:
        """Forward to the owning worker; when the worker DIED mid-op
        (EOF/broken pipe + process gone), fail over and re-issue the
        op exactly once under its idempotency key.  Application errors
        from a live worker re-raise untouched — only a lost answer is
        retryable."""
        idem = self._stamp_idem(msg)
        w = self._route(msg["sid"] if "sid" in msg else sids[0])
        try:
            return self._worker_call(w, msg, emit)
        except (ServeClientError, OSError) as e:
            replaced = self._worker_at(w.idx) is not w
            if not replaced and w.alive():
                raise  # the worker answered; not a death
            self._failover(w, cause=e)
            sid0 = msg.get("sid") or (sids[0] if sids else "")
            w2 = self._route(sid0)  # raises when not recovered
            self.journal.record(idem, sid0, "retry", idem=idem,
                                op=msg.get("op", ""), worker=w2.idx,
                                gen=w2.gen)
            return self._worker_call(w2, msg, emit)

    @staticmethod
    def _worker_call(w: FleetWorker, msg: dict, emit=None) -> dict:
        hook = None
        if emit is not None:
            def hook(ev):  # re-emit worker stream lines to our client
                try:
                    from tools.serve_client import encode_array
                    line = dict(ev)
                    if "outputs" in line:
                        line["outputs"] = {
                            k: encode_array(v)
                            for k, v in line["outputs"].items()}
                    if "id" in msg:
                        line["id"] = msg["id"]
                    emit(line)
                except Exception:  # noqa: BLE001 - beacon only
                    pass
        fields = {k: v for k, v in msg.items() if k not in ("op", "id")}
        try:
            return w.call(msg["op"], on_stream=hook, **fields)
        except ServeClientError as e:
            resp = getattr(e, "response", None)
            if isinstance(resp, dict):
                # the worker ANSWERED ok:false (rejected / anomaly /
                # app error): pass the STRUCTURED response through —
                # clients key on status/anomaly fields, and failover
                # must never re-run an op a live worker executed.
                out = dict(resp)
                out.pop("id", None)  # handle() re-stamps ours
                return out
            raise

    def op_open(self, msg, emit=None):
        w = self._admit()
        with self._lock:
            sid = msg.get("session") or f"f{self._next_sid:04d}"
            self._next_sid += 1
            if sid in self._route_table:
                return {"ok": False,
                        "error": f"fleet session {sid!r} already open"}
        fields = {k: v for k, v in msg.items() if k not in ("op", "id")}
        fields["session"] = sid
        try:
            out = w.call("open", **fields)
        except (ServeClientError, OSError) as e:
            resp = getattr(e, "response", None)
            if isinstance(resp, dict) and resp.get("overloaded"):
                # worker-level brownout (tier 2): the structured
                # rejection + Retry-After hint rides through the fleet
                out2 = dict(resp)
                out2.pop("id", None)
                return out2
            replaced = self._worker_at(w.idx) is not w
            if not replaced and w.alive():
                raise
            self._failover(w, cause=e)
            w = self._admit()  # re-place on a live worker, once
            out = w.call("open", **fields)
        with self._lock:
            self._route_table[out["sid"]] = w
            w.sessions.add(out["sid"])
            self._bank[out["sid"]] = {"open": dict(fields),
                                      "snapshot": None,
                                      "log": [], "steps": 0}
        out["worker"] = w.idx
        return out

    def op_close(self, msg, emit=None):
        w = self._route(msg["sid"])
        out = w.call("close", sid=msg["sid"])
        with self._lock:
            self._route_table.pop(msg["sid"], None)
            self._bank.pop(msg["sid"], None)
            w.sessions.discard(msg["sid"])
        return out

    def op_run_many(self, msg, emit=None):
        """Split by owning worker, forward each shard concurrently
        (submit-all-then-wait-all must reach each worker as one op to
        land inside its batching window), reassemble in order."""
        reqs = msg["requests"]
        shards: Dict[int, List[int]] = {}
        for i, m in enumerate(reqs):
            w = self._route(m["sid"])
            shards.setdefault(w.idx, []).append(i)
        results: List[Optional[dict]] = [None] * len(reqs)
        errs: List[str] = []

        def run_shard(widx: int, idxs: List[int]) -> None:
            shard_sids = [reqs[i]["sid"] for i in idxs]
            for sid in dict.fromkeys(shard_sids):
                self._maybe_snapshot_before_run(sid)
            sub = {"op": "run_many",
                   "requests": [reqs[i] for i in idxs]}
            if msg.get("trace"):
                sub["trace"] = msg["trace"]
            if "timeout" in msg:
                sub["timeout"] = msg["timeout"]
            if "id" in msg:
                sub["id"] = msg["id"]
            try:
                out = self._call_with_failover(sub, emit,
                                               sids=shard_sids)
                for i, r in zip(idxs, out["responses"]):
                    results[i] = r
                for i in idxs:
                    self._note_ok(reqs[i]["sid"],
                                  {"op": "run", **reqs[i]})
            except Exception as e:  # noqa: BLE001
                errs.append(f"worker {widx}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=run_shard, args=(wi, ix))
                   for wi, ix in shards.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            return {"ok": False, "error": "; ".join(errs)}
        return {"ok": True, "responses": results}

    def op_metrics(self, msg, emit=None):
        """Aggregated fleet metrics + the per-worker breakdown."""
        per = []
        for w in self.workers:
            try:
                m = w.call("metrics")["metrics"]
            except Exception as e:  # noqa: BLE001
                m = {"error": f"{type(e).__name__}: {e}"}
            m["worker"] = w.idx
            per.append(m)
        agg = {"queue_depth": sum(m.get("queue_depth", 0) for m in per),
               "sessions": sum(m.get("sessions", 0) for m in per),
               "completed": sum(m.get("completed", 0) for m in per),
               "workers": per}
        return {"ok": True, "metrics": agg}

    def op_fleet_stats(self, msg, emit=None):
        rows = []
        slo_breaches = 0
        for w in self.workers:
            row = {"worker": w.idx, "journal": w.journal_path,
                   "sessions": sorted(w.sessions),
                   **w.occupancy()}
            try:
                cs = w.call("cache_stats")
                row["cache"] = cs.get("stats", {})
                row["cache_dir"] = cs.get("cache_dir")
            except Exception as e:  # noqa: BLE001
                row["cache"] = {"error": f"{type(e).__name__}: {e}"}
            # SLO surfacing: the worker's monitor state + journaled
            # breach count (None slo = no YT_SLO_* knobs set)
            try:
                snap = w.call("metrics_snapshot").get("snapshot", {})
                row["slo"] = snap.get("slo")
                n = int((snap.get("journal") or {})
                        .get("slo_breaches", 0))
                row["slo_breaches"] = n
                slo_breaches += n
            except Exception as e:  # noqa: BLE001
                row["slo"] = {"error": f"{type(e).__name__}: {e}"}
            rows.append(row)
        out = {"ok": True, "cache_dir": self.cache_dir,
               "slo_breaches": slo_breaches, "workers": rows,
               "autoscale": self._autoscaler is not None,
               "draining": [w.idx for w in self.workers
                            if w.draining]}
        with self._lock:
            if self._telemetry is not None:
                out["telemetry_ts"] = self._telemetry.get("ts")
                out["stale_workers"] = list(
                    self._telemetry.get("stale_workers") or [])
        return out

    def op_metrics_snapshot(self, msg, emit=None):
        """The merged fleet-wide telemetry snapshot (fresh poll; the
        heartbeat loop banks the same shape every tick)."""
        return {"ok": True, "telemetry": self.collect_telemetry()}

    def op_cache_stats(self, msg, emit=None):
        """Per-worker compile-cache counters (warm-start evidence)."""
        out = {}
        for w in self.workers:
            try:
                out[str(w.idx)] = w.call("cache_stats").get("stats", {})
            except Exception as e:  # noqa: BLE001
                out[str(w.idx)] = {"error": f"{type(e).__name__}: {e}"}
        return {"ok": True, "stats": out}

    def op_shutdown(self, msg, emit=None):
        self.closing.set()
        return {"ok": True}

    # ------------------------------------------------------- lifecycle

    def close(self) -> None:
        self.closing.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
        for w in self.workers:
            try:
                with w.lock:
                    w.client.close()
            except Exception:  # noqa: BLE001
                pass


def _serve_stream(fleet: ServeFleet, rfile, wfile) -> None:
    wlock = threading.Lock()

    def emit(obj: dict) -> None:
        with wlock:
            wfile.write(json.dumps(obj, sort_keys=True) + "\n")
            wfile.flush()

    for line in rfile:
        line = line.strip()
        if not line:
            continue
        try:
            msg = json.loads(line)
        except ValueError as e:
            out = {"ok": False, "error": f"bad JSON: {e}"}
        else:
            out = fleet.handle(msg, emit=emit)
        emit(out)
        if fleet.closing.is_set():
            return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="N-worker stencil-serving fleet front")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--cache-dir", default=None,
                    help="shared compile cache (YT_COMPILE_CACHE; "
                         "workers 1+ warm-start from worker 0's "
                         "compiles)")
    ap.add_argument("--journal-dir", default=None,
                    help="directory for per-worker journals "
                         "(SERVE_JOURNAL.w<i>.jsonl; default: cwd)")
    ap.add_argument("--port", type=int, default=None,
                    help="listen on TCP (default: stdio)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--window_ms", type=float, default=None)
    ap.add_argument("--max_batch", type=int, default=None)
    ap.add_argument("--no-preflight", action="store_true")
    ap.add_argument("--hb_secs", type=float, default=5.0,
                    help="heartbeat supervision interval; 0 disables "
                         "the background health loop "
                         "(YT_FLEET_HB_SECS overrides when unset)")
    ap.add_argument("--autoscale", action="store_true",
                    help="enable the SLO-driven autoscaler "
                         "(YT_FLEET_MIN/MAX_WORKERS bounds, "
                         "YT_FLEET_SCALE_* thresholds; also "
                         "switchable via YT_FLEET_AUTOSCALE=1)")
    args = ap.parse_args(argv)

    wargs: List[str] = []
    if args.window_ms is not None:
        wargs += ["--window_ms", str(args.window_ms)]
    if args.max_batch is not None:
        wargs += ["--max_batch", str(args.max_batch)]
    if args.no_preflight:
        wargs += ["--no-preflight"]

    try:
        fleet = ServeFleet(n_workers=args.workers,
                           cache_dir=args.cache_dir,
                           journal_dir=args.journal_dir,
                           worker_args=wargs,
                           hb_secs=args.hb_secs,
                           autoscale=True if args.autoscale else None)
    except RuntimeError as e:
        sys.stderr.write(f"{e}\n")
        return 2
    try:
        if args.port is not None:
            import socket
            srv = socket.create_server((args.host, args.port))
            srv.settimeout(0.5)
            sys.stderr.write(
                f"serve_fleet: {len(fleet.workers)} workers on "
                f"{args.host}:{srv.getsockname()[1]}\n")
            sys.stderr.flush()
            threads = []
            try:
                while not fleet.closing.is_set():
                    try:
                        conn, _addr = srv.accept()
                    except socket.timeout:
                        continue
                    t = threading.Thread(
                        target=_serve_stream,
                        args=(fleet, conn.makefile("r", encoding="utf-8"),
                              conn.makefile("w", encoding="utf-8")),
                        daemon=True)
                    t.start()
                    threads.append(t)
            finally:
                srv.close()
                for t in threads:
                    t.join(timeout=2.0)
        else:
            sys.stderr.write(
                f"serve_fleet: {len(fleet.workers)} workers ready "
                "(stdio)\n")
            sys.stderr.flush()
            _serve_stream(fleet, sys.stdin, sys.stdout)
    finally:
        fleet.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
