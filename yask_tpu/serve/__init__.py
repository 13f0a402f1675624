"""yask_tpu.serve — the long-lived multi-tenant stencil-serving layer.

One process holds a **session registry** of prepared solutions
(:mod:`.registry`): a profile = one prepared ``StencilContext`` per
(stencil, geometry, dtype, mode, variant) configuration, a tenant =
one session id owning its own :class:`~yask_tpu.runtime.run_state.
RunState` under that shared compiled executable — the
per-run-state-out-of-StencilContext hoist finished end-to-end.  A
**dynamic micro-batching scheduler** (:mod:`.scheduler`) groups
compatible pending requests (same profile / mode / variant key / step
range) inside a bounded window into ONE vmapped ensemble execution
(:class:`~yask_tpu.runtime.ensemble.EnsembleRun` over the tenants'
existing RunStates), and a restarted server **warm-starts** from the
persistent AOT compile cache (``YT_COMPILE_CACHE``): the first request
answers with zero lowerings.

Every request runs through ``guarded_call`` at the ``serve.run`` fault
site, is journaled (schema ``yask_tpu.serve/1`` —
received/batched/ok/anomaly/rejected), passes result-sanity quarantine
before its response is released, and a classified device fault walks
the session down the PR 9 mode-degradation ladder instead of failing
the tenant.  Serving metrics (queue depth, batch occupancy, p50/p99
latency split queue/run, cache-hit tier) are read with
``srv.metrics()``.

Serving v2 adds **shape-bucket co-batching** (:mod:`.buckets`):
sessions opened at different geometries are hosted on shared bucket-
ladder rung profiles and ride ONE masked vmapped ensemble, bit-
identical to their solo runs; **chunked streaming** (``flush_every``
on the request: partial-result ``stream`` events at chunk boundaries,
long runs preemptible between chunks so short requests interleave);
and a **warm-cache fleet front** (``tools/serve_fleet.py``: N workers
behind one JSON-lines front with session-affinity routing, admission
control, and a shared on-disk compile cache).

Front ends: the in-process :class:`~yask_tpu.serve.server.
StencilServer` API, and the stdio/socket JSON-lines front in
``tools/serve.py`` (client: ``tools/serve_client.py``; fleet:
``tools/serve_fleet.py``).  See ``docs/serving.md``.
"""

from yask_tpu.serve.api import (ServeRequest, ServeResponse,
                                serve_bucketing_enabled,
                                serve_deadline_secs, serve_max_batch,
                                serve_window_secs)
from yask_tpu.serve.buckets import (BucketDecision, bucket_cobatch_feasible,
                                    bucket_for, bucket_ladder, plan_bucket)
from yask_tpu.serve.journal import (SERVE_SCHEMA, SERVE_TERMINAL,
                                    ServeJournal, default_serve_journal_path)
from yask_tpu.serve.registry import SessionRegistry
from yask_tpu.serve.server import StencilServer

__all__ = ["ServeRequest", "ServeResponse", "StencilServer",
           "SessionRegistry", "ServeJournal", "SERVE_SCHEMA",
           "SERVE_TERMINAL", "default_serve_journal_path",
           "serve_window_secs", "serve_max_batch",
           "serve_deadline_secs", "serve_bucketing_enabled",
           "BucketDecision", "bucket_ladder", "bucket_for",
           "plan_bucket", "bucket_cobatch_feasible"]
