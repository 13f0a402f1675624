"""The in-process server: registry + scheduler + metrics under one
facade.

Lifecycle::

    srv = StencilServer()                      # owns worker thread
    sid = srv.open_session(stencil="iso3dfd", radius=2, g=16,
                           mode="jit", wf=2)   # prepares ONCE per
                                               # profile; later tenants
                                               # share the executable
    srv.set_var(sid, "vel", 0.5)               # state lives server-side
    srv.set_var_slice(sid, "pressure", arr, first, last)
    resp = srv.request(ServeRequest(session=sid, first_step=0,
                                    last_step=3))
    srv.metrics()                              # latency / occupancy
    srv.shutdown()

**Warm start**: every executable a request needs is built through
``yask_tpu.cache.aot_compile``, so with ``YT_COMPILE_CACHE`` set a
restarted server's first request deserializes from disk — zero
lowerings (``cache.stats()["lowerings"] == 0``); :meth:`prewarm`
optionally pulls the compile forward to ``open_session`` time.

``open_session`` runs the checker's serve pass over the profile
(LOG-ONLY, the policy of ``checker.preflight``: a false positive must
not refuse a tenant) — ``SERVE-BATCH-INCOMPAT`` and
``SERVE-CACHE-COLD`` findings print to stderr and are kept on
``last_preflight`` for inspection.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, Optional

import numpy as np

from yask_tpu.obs.metrics import Registry, percentile as _pctl
from yask_tpu.obs.tracer import span
from yask_tpu.serve.api import ServeRequest, ServeResponse
from yask_tpu.serve.journal import ServeJournal
from yask_tpu.serve.registry import SessionRegistry
from yask_tpu.serve.scheduler import BatchScheduler


class StencilServer:
    def __init__(self, env=None, factory=None,
                 journal_path: Optional[str] = None,
                 window_secs: Optional[float] = None,
                 max_batch: Optional[int] = None,
                 preflight: bool = True):
        from yask_tpu import yk_factory
        self._factory = factory or yk_factory()
        self._env = env if env is not None else self._factory.new_env()
        self.journal = ServeJournal(journal_path)
        # journal growth control: a long-lived fleet worker restarts
        # onto the same SERVE_JOURNAL.w<i>.jsonl — compact it past the
        # YT_JOURNAL_MAX_MB threshold before appending more (between
        # servers is the safe compaction window).
        self.journal.compact_if_large()
        self.registry = SessionRegistry(self._factory, self._env)
        #: per-server metrics registry (obs.metrics) — the scheduler
        #: feeds it per release; ``metrics()["registry"]`` exports it.
        self.obs = Registry()
        self.scheduler = BatchScheduler(self.registry, self.journal,
                                        window_secs=window_secs,
                                        max_batch=max_batch,
                                        obs_registry=self.obs)
        self._preflight = bool(preflight)
        #: last serve-pass CheckReport (LOG-ONLY evidence).
        self.last_preflight = None
        self._lock = threading.RLock()

    # ------------------------------------------------------- sessions

    def open_session(self, stencil: str, radius: Optional[int] = None,
                     g=16, mode: str = "jit", wf: int = 2,
                     options: str = "",
                     session: Optional[str] = None,
                     bucket: Optional[bool] = None) -> str:
        """Open a tenant session.  ``bucket`` controls shape-bucket
        co-batching: None = the ``YT_SERVE_BUCKETING`` default (on),
        False = host exactly at ``g``, True = request bucketing.  A
        bucketed session is hosted on a profile at the next bucket-
        ladder rung >= g and runs as a masked sub-domain — results
        stay bit-identical to a solo run at ``g`` (the
        ``yask_tpu.serve.buckets`` contract); infeasible solutions
        (non-jit modes, IF_DOMAIN conditions) decline and open exact,
        with the structured reason journaled on every batched row."""
        # kept: a session's set-up (the profile's solution and its
        # prepare on first registration lie inside it)
        with span("serve.open", phase="setup", keep=True,
                  stencil=str(stencil), g=str(g), mode=mode) as sp:
            sid = self._open_session(stencil, radius, g, mode, wf,
                                     options, session, bucket)
            sp.set(sid=sid)
        return sid

    def _open_session(self, stencil, radius, g, mode, wf, options,
                      session, bucket) -> str:
        from yask_tpu.serve.api import (Overloaded, serve_retry_after,
                                        serve_bucketing_enabled)
        tier = self.scheduler.overload_tier()
        if tier >= 2:
            # brownout tier 2: admission is the ONLY thing refused —
            # existing sessions and in-flight requests are untouched
            ra = serve_retry_after()
            self.obs.counter("serve.overload.rejected_sessions").inc()
            self.journal.record(session or "-", session or "-",
                                "overloaded", tier=tier,
                                retry_after=ra, stencil=str(stencil))
            raise Overloaded(
                f"server overloaded (brownout tier {tier}): not "
                f"admitting new sessions; retry after {ra:g}s",
                retry_after=ra, tier=tier)
        requested = serve_bucketing_enabled() if bucket is None \
            else bool(bucket)
        decision, sub, host_g = self._plan_bucket(
            stencil, radius, g, mode, wf, options, requested)
        prof = self.registry.get_profile(stencil, radius, host_g, mode,
                                         wf, options)
        if self._preflight:
            self._run_preflight(prof)
        return self.registry.open_session(prof, session, sub_sizes=sub,
                                          bucket=decision).sid

    def _plan_bucket(self, stencil, radius, g, mode: str, wf: int,
                     options: str, requested: bool):
        """The open-time bucketing verdict: (BucketDecision,
        sub_sizes-or-None, host geometry).  Feasibility is probed on
        an UNPREPARED solution (equations + mode are all it needs), so
        a declined session never pays a wasted bucket-rung prepare."""
        from yask_tpu.serve.buckets import BucketDecision, plan_bucket
        try:
            gi = int(g)
        except (TypeError, ValueError):
            return (BucketDecision(
                "exact", g=0,
                reason=f"non-cubic geometry {g!r} serves exact"),
                None, g)
        if not requested:
            return (BucketDecision("exact", g=gi,
                                   reason="bucketing not requested"),
                    None, g)
        probe = self._factory.new_solution(self._env, stencil=stencil,
                                           radius=radius)
        probe.get_settings().mode = mode
        decision = plan_bucket(probe, gi, True)
        if decision.decision != "bucketed":
            return decision, None, g
        sub = None
        if decision.bucket != gi:
            sub = {d: gi for d in probe._opts.global_domain_sizes}
        return decision, sub, decision.bucket

    def _run_preflight(self, prof) -> None:
        """Serve-pass checks over the profile, log-only (findings
        print, the tenant is admitted)."""
        try:
            from yask_tpu.checker import run_checks
            report = run_checks(prof.ctx, passes=("serve",))
            self.last_preflight = report
            if report.errors or report.warnings:
                sys.stderr.write(report.render())
        except Exception as e:  # noqa: BLE001 - a checker bug must
            sys.stderr.write(   # never refuse a tenant
                f"serve preflight: internal failure "
                f"({type(e).__name__}: {e}); skipped\n")

    def close_session(self, sid: str) -> None:
        self.registry.close_session(sid)

    def session_mode(self, sid: str) -> str:
        return self.registry.session(sid).mode

    # ----------------------------------------------- state in/out

    def set_var(self, sid: str, var: str, value: float) -> None:
        # kept (with set_var_slice): a tenant's upload, the wait for
        # the device lock included; the fill's own span lies inside
        with span("serve.set_var", phase="setup", keep=True, sid=sid,
                  var=var) as sp, self.scheduler.session_ctx(sid) as ctx:
            v = ctx.get_var(var)
            v.set_all_elements_same(value)
            sp.set(bytes=v.get_num_storage_bytes())

    def set_var_slice(self, sid: str, var: str, buf,
                      first_indices, last_indices) -> int:
        buf = np.asarray(buf)
        with span("serve.set_var", phase="setup", keep=True, sid=sid,
                  var=var, bytes=int(buf.nbytes)), \
                self.scheduler.session_ctx(sid) as ctx:
            return ctx.get_var(var).set_elements_in_slice(
                buf, list(first_indices), list(last_indices))

    def get_var_slice(self, sid: str, var: str, first_indices,
                      last_indices):
        with self.scheduler.session_ctx(sid) as ctx:
            return ctx.get_var(var).get_elements_in_slice(
                list(first_indices), list(last_indices))

    def init_vars(self, sid: str) -> None:
        """The standard nonzero initial conditions
        (``init_solution_vars``) for this session's state — over the
        tenant's SUB-domain when the session is bucket-hosted, so a
        bucketed tenant starts bit-identical to its solo twin."""
        from yask_tpu.runtime.init_utils import init_solution_vars
        sess = self.registry.session(sid)
        with self.scheduler.session_ctx(sid) as ctx:
            init_solution_vars(ctx, sub_sizes=sess.sub_sizes)

    def session_bucket(self, sid: str) -> Dict:
        """The session's structured bucketing verdict (empty for the
        pre-bucketing open path)."""
        b = self.registry.session(sid).bucket
        return b.as_detail() if b is not None else {}

    # ----------------------------------------------------- requests

    def submit(self, req: ServeRequest, on_stream=None):
        return self.scheduler.submit(req, on_stream=on_stream)

    def wait(self, handle, timeout: Optional[float] = None
             ) -> ServeResponse:
        return self.scheduler.wait(handle, timeout)

    def request(self, req: ServeRequest,
                timeout: Optional[float] = None) -> ServeResponse:
        return self.scheduler.request(req, timeout)

    def run(self, sid: str, first_step: int,
            last_step: Optional[int] = None,
            outputs=(), timeout: Optional[float] = None,
            flush_every: int = 0, stream_outputs: bool = False
            ) -> ServeResponse:
        return self.request(
            ServeRequest(session=sid, first_step=first_step,
                         last_step=last_step,
                         outputs=tuple(outputs),
                         flush_every=int(flush_every),
                         stream_outputs=bool(stream_outputs)), timeout)

    def submit_run(self, sid: str, first_step: int,
                   last_step: Optional[int] = None, outputs=(),
                   flush_every: int = 0, stream_outputs: bool = False):
        """Non-blocking :meth:`run` — returns the pending handle for
        :meth:`wait`.  Submitting a whole sweep before waiting is what
        lands compatible requests inside one batching window."""
        return self.submit(
            ServeRequest(session=sid, first_step=first_step,
                         last_step=last_step,
                         outputs=tuple(outputs),
                         flush_every=int(flush_every),
                         stream_outputs=bool(stream_outputs)))

    # ------------------------------------------------- checkpointing

    def snapshot(self, sid: str) -> Dict:
        """An interior-coordinate checkpoint of the session's state
        (``yask_tpu.checkpoint/1``), taken under the session's device
        lock so it never races a running chunk.  Restores
        bit-identically across modes/paddings — the fleet front banks
        these for checkpoint-backed failover."""
        from yask_tpu.resilience.checkpoint import extract_snapshot
        with self.scheduler.session_ctx(sid) as ctx:
            return extract_snapshot(ctx)

    def restore(self, sid: str, snap: Dict) -> bool:
        """Apply a banked checkpoint onto the session (ring state +
        step counters).  Returns False on a schema/shape mismatch
        (``apply_snapshot`` contract: never raises)."""
        from yask_tpu.resilience.checkpoint import apply_snapshot
        with self.scheduler.session_ctx(sid) as ctx:
            return bool(apply_snapshot(ctx, snap))

    # ----------------------------------------------------- warm start

    def prewarm(self, sid: str, steps: int) -> int:
        """Build (or disk-load) the compiled chunks a ``steps``-long
        request will need, ahead of the first request.  Returns the
        number of chunk executables touched.  With ``YT_COMPILE_CACHE``
        set and warm, this deserializes — zero lowerings."""
        from yask_tpu.resilience.guard import guarded_call
        sess = self.registry.session(sid)
        n = max(1, int(steps))
        with self.scheduler.session_ctx(sid) as ctx:
            if sess.mode not in ("jit", "pallas"):
                return 0
            wf = ctx._opts.wf_steps
            if sess.mode == "pallas":
                wf = min(max(wf, 1), n)
            elif wf <= 0:
                wf = n
            sizes = set()
            rem = n
            while rem > 0:
                k = min(wf, rem)
                sizes.add(k)
                rem -= k
            getter = ctx._get_pallas_chunk if sess.mode == "pallas" \
                else ctx._get_compiled_chunk
            for k in sorted(sizes):
                guarded_call(getter, k, site="serve.run")
            return len(sizes)

    # ------------------------------------------------------- metrics

    def metrics(self) -> Dict:
        """Serving metrics over the retained samples: queue depth,
        batch occupancy, p50/p99 latency split queue/run/respond (the
        three cover a request end to end), cache-hit tiers,
        degradation counts."""
        samples = self.scheduler.samples()
        done = [s for s in samples if s["status"] in ("ok", "anomaly")]
        q = [s["queue_secs"] * 1e3 for s in done]
        r = [s["run_secs"] * 1e3 for s in done]
        rs = [s["respond_secs"] * 1e3 for s in done]
        tot = [(s["queue_secs"] + s["run_secs"] + s["respond_secs"])
               * 1e3 for s in done]
        occ = [s["batch"] for s in done]
        hits: Dict[str, int] = {}
        for s in done:
            hits[s["cache_hit"]] = hits.get(s["cache_hit"], 0) + 1
        return {
            "queue_depth": self.scheduler.queue_depth(),
            "sessions": len(self.registry.sessions()),
            "profiles": len(self.registry.profiles()),
            "completed": len(done),
            "ok": sum(1 for s in done if s["status"] == "ok"),
            "anomalies": sum(1 for s in done
                             if s["status"] == "anomaly"),
            "degraded": sum(1 for s in done if s["degraded"]),
            "bucketed": sum(1 for s in done if s.get("bucketed")),
            "preempted": sum(1 for s in done if s.get("preempted")),
            "batch_occupancy_mean": (sum(occ) / len(occ)) if occ
            else 0.0,
            "batch_occupancy_max": max(occ) if occ else 0,
            "p50_queue_ms": round(_pctl(q, 0.50), 3),
            "p99_queue_ms": round(_pctl(q, 0.99), 3),
            "p50_run_ms": round(_pctl(r, 0.50), 3),
            "p99_run_ms": round(_pctl(r, 0.99), 3),
            "p50_respond_ms": round(_pctl(rs, 0.50), 3),
            "p99_respond_ms": round(_pctl(rs, 0.99), 3),
            "p50_total_ms": round(_pctl(tot, 0.50), 3),
            "p99_total_ms": round(_pctl(tot, 0.99), 3),
            "compile_ms_total": round(sum(s["compile_secs"]
                                          for s in done) * 1e3, 1),
            "cache_hits": hits,
            # the obs registry's own view (same percentile math —
            # obs.metrics.percentile IS the historical _pctl); rides
            # op_metrics to the fleet front as the per-worker export.
            "registry": self.obs.snapshot(),
        }

    def metrics_snapshot(self) -> Dict:
        """The per-worker telemetry unit the fleet front aggregates
        (``op metrics_snapshot``): the registry snapshot WITH raw
        histogram sample windows (so the aggregator can merge windows
        and re-rank quantiles — never average percentiles), plus
        cache/journal occupancy counters and the SLO monitor's state
        (None unless YT_SLO_* configured one)."""
        from yask_tpu.cache import compile_cache
        snap = self.obs.snapshot_full()
        snap["v"] = "yask_tpu.telemetry/1"
        snap["cache"] = compile_cache.stats()
        jrows = self.journal.rows()
        snap["journal"] = {
            "rows": len(jrows),
            "inflight": sum(1 for r in jrows
                            if r.get("event") == "received")
            - sum(1 for r in jrows
                  if r.get("event") in ("ok", "anomaly", "rejected")),
            "slo_breaches": sum(1 for r in jrows
                                if r.get("event") == "slo_breach"),
        }
        snap["occupancy"] = {
            "queue_depth": self.scheduler.queue_depth(),
            "sessions": len(self.registry.sessions()),
            "profiles": len(self.registry.profiles()),
        }
        snap["slo"] = self.scheduler.slo_summary()
        return snap

    # ------------------------------------------------------ lifecycle

    def shutdown(self) -> None:
        self.scheduler.shutdown()
