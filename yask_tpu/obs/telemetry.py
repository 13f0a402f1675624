"""Fleet-wide telemetry: merge per-worker metric snapshots, export Prometheus.

The fleet front polls every worker for its registry snapshot (the new
``op metrics_snapshot``) on the heartbeat cadence and folds the results
into ONE fleet view with :func:`merge_snapshots`.  The merge rule that
matters: histograms are merged by pooling their raw sample windows and
re-ranking — percentiles are NEVER averaged (the mean of two worker
p99s is not the fleet p99).  Counters sum; gauges sum (queue depths and
occupancies are additive across workers) with per-worker values kept in
the ``workers`` block for anything that is not.

:func:`to_prometheus` renders any snapshot (per-worker or merged) as
Prometheus text exposition.  Metric names are derived mechanically from
registry names (``serve.total_ms`` → ``yt_serve_total_ms``) so they are
stable as long as the registry names are — ``tests/test_telemetry.py``
pins the flagship set.

Schema: ``yask_tpu.telemetry/1``.  Everything here is pure-Python and
JSON-able; nothing imports jax.
"""

from __future__ import annotations

import json
import re
from typing import Dict, List, Optional

from .metrics import percentile

TELEMETRY_SCHEMA = "yask_tpu.telemetry/1"

#: registry names every serving build must keep exporting — renames are
#: dashboard-breaking changes and fail tests/test_telemetry.py.  The
#: scheduler also emits two dynamic families whose PREFIXES are the
#: stable contract: ``serve.requests.<status>`` (ok/anomaly/rejected…)
#: and ``serve.cache.<tier>`` (cold/memory/disk).
STABLE_COUNTERS = (
    "serve.requests.ok",
    "serve.requests.anomaly",
    "serve.requests.rejected",
    "serve.degraded",
    "serve.preempted",
    "serve.d2h_bytes",
    "serve.sanity.exact",
    "serve.snapshot.reused_bytes",
)
STABLE_COUNTER_PREFIXES = ("serve.requests.", "serve.cache.",
                           "serve.overload.")
STABLE_GAUGES = ("serve.queue_depth",)
STABLE_HISTOGRAMS = (
    "serve.queue_ms",
    "serve.run_ms",
    "serve.respond_ms",
    "serve.total_ms",
    "serve.batch_occupancy",
)


def _merged_hist(summaries: List[Dict]) -> Dict:
    """Fold per-worker histogram summaries (with raw ``samples``) into
    one summary over the pooled window."""
    xs: List[float] = []
    count = 0
    mx = 0.0
    mean_num = 0.0
    for s in summaries:
        xs.extend(s.get("samples", ()))
        count += int(s.get("count", 0))
        mx = max(mx, float(s.get("max", 0.0)))
        mean_num += float(s.get("mean", 0.0)) * int(s.get("count", 0))
    return {"count": count,
            "mean": (mean_num / count) if count else 0.0,
            "p50": percentile(xs, 0.50),
            "p99": percentile(xs, 0.99),
            "max": mx,
            "window": len(xs)}


def merge_snapshots(per_worker: Dict[str, Dict],
                    ts: Optional[float] = None) -> Dict:
    """Merge worker ``Registry.snapshot_full()`` dicts into one fleet
    snapshot.

    ``per_worker`` maps a worker id to its snapshot; extra per-worker
    keys (``occupancy``, ``cache``, ``journal``, ``slo``) ride along in
    the ``workers`` block untouched.  The ``merged`` block sums counters
    and gauges and pools histogram samples (see module doc).

    A snapshot carrying ``stale: True`` (the fleet front stamps it when
    a worker has not answered a poll for 3 heartbeat intervals — it is
    the LAST known snapshot, not a fresh one) keeps its ``workers``
    block entry for inspection but is EXCLUDED from the merged fold,
    and its worker id lands in the top-level ``stale_workers`` list:
    a hung worker's dead numbers must not ride in fleet sums forever,
    and the autoscaler must be able to refuse to act on them.
    """
    counters: Dict[str, int] = {}
    gauges: Dict[str, float] = {}
    hists: Dict[str, List[Dict]] = {}
    workers: Dict[str, Dict] = {}
    stale: List[str] = []
    for wid, snap in sorted(per_worker.items()):
        snap = snap or {}
        if snap.get("stale"):
            stale.append(str(wid))
        else:
            for k, v in (snap.get("counters") or {}).items():
                counters[k] = counters.get(k, 0) + int(v)
            for k, v in (snap.get("gauges") or {}).items():
                gauges[k] = gauges.get(k, 0.0) + float(v)
            for k, s in (snap.get("histograms") or {}).items():
                hists.setdefault(k, []).append(s)
        # per-worker view without the raw windows (they can be large)
        wsnap = dict(snap)
        wsnap["histograms"] = {
            k: {kk: vv for kk, vv in s.items() if kk != "samples"}
            for k, s in (snap.get("histograms") or {}).items()}
        workers[str(wid)] = wsnap
    out = {"v": TELEMETRY_SCHEMA,
           "workers": workers,
           "stale_workers": stale,
           "merged": {
               "counters": dict(sorted(counters.items())),
               "gauges": dict(sorted(gauges.items())),
               "histograms": {k: _merged_hist(v)
                              for k, v in sorted(hists.items())}}}
    if ts is not None:
        out["ts"] = float(ts)
    return out


_NAME_RE = re.compile(r"[^a-zA-Z0-9_]")


def prom_name(name: str, prefix: str = "yt") -> str:
    """``serve.total_ms`` → ``yt_serve_total_ms`` (Prometheus charset)."""
    return f"{prefix}_{_NAME_RE.sub('_', name)}"


def _fmt(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


def to_prometheus(snapshot: Dict, prefix: str = "yt") -> str:
    """Render a snapshot (plain registry snapshot, or the ``merged`` /
    per-worker block of a fleet snapshot) as Prometheus text exposition.

    Histograms export as summaries: ``{quantile="0.5"|"0.99"}`` series
    plus ``_count`` / ``_sum`` / ``_max``.  When given a full fleet
    snapshot (has a ``merged`` key) the merged block is exported
    unlabeled and per-worker gauges/counters get a ``worker`` label.
    """
    lines: List[str] = []
    workers = snapshot.get("workers") if "merged" in snapshot else None
    body = snapshot.get("merged", snapshot)

    for k, v in sorted((body.get("counters") or {}).items()):
        n = prom_name(k, prefix)
        lines.append(f"# TYPE {n} counter")
        lines.append(f"{n} {_fmt(v)}")
        for wid, snap in sorted((workers or {}).items()):
            wv = (snap.get("counters") or {}).get(k)
            if wv is not None:
                lines.append(f'{n}{{worker="{wid}"}} {_fmt(wv)}')
    for k, v in sorted((body.get("gauges") or {}).items()):
        n = prom_name(k, prefix)
        lines.append(f"# TYPE {n} gauge")
        lines.append(f"{n} {_fmt(v)}")
        for wid, snap in sorted((workers or {}).items()):
            wv = (snap.get("gauges") or {}).get(k)
            if wv is not None:
                lines.append(f'{n}{{worker="{wid}"}} {_fmt(wv)}')
    for k, s in sorted((body.get("histograms") or {}).items()):
        n = prom_name(k, prefix)
        lines.append(f"# TYPE {n} summary")
        lines.append(f'{n}{{quantile="0.5"}} {_fmt(s.get("p50", 0.0))}')
        lines.append(f'{n}{{quantile="0.99"}} {_fmt(s.get("p99", 0.0))}')
        cnt = int(s.get("count", 0))
        lines.append(f"{n}_count {cnt}")
        lines.append(f"{n}_sum {_fmt(float(s.get('mean', 0.0)) * cnt)}")
        lines.append(f"{n}_max {_fmt(s.get('max', 0.0))}")
    return "\n".join(lines) + ("\n" if lines else "")


def snapshot_to_json(snapshot: Dict) -> str:
    return json.dumps(snapshot, sort_keys=True)
