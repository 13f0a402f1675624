#!/usr/bin/env python
"""Render TRACE_EVENTS.jsonl: per-phase breakdown + Perfetto export.

Reads the span rows the obs tracer appends (schema ``yask_tpu.trace/1``,
see ``yask_tpu/obs/tracer.py``) and answers the two questions a trace
exists for:

* **Where did the time go?**  The terminal report buckets spans by
  phase using SELF-TIME attribution — each span's duration minus the
  durations of its direct children in the same trace — so nested spans
  (``guard:run.chunk`` inside ``serve.chunk`` inside
  ``run.supervised``) are not double-counted, and queue-wait shows up
  as its own line instead of hiding inside compute.  (A shard
  program's exchange runs INSIDE the jitted program: no host span can
  hold it.  Its share is the device trace's; the ``run.launch`` span
  says what is sent, ``xrounds`` / ``xslabs`` / ``xbytes``.)
* **What did it look like?**  ``--perfetto OUT`` writes Chrome
  trace-event JSON (``{"traceEvents": [...]}``, ``ph: "X"`` complete
  events, µs timestamps): load it in ui.perfetto.dev or
  chrome://tracing.  One lane per (pid, tid) — the fleet front, each
  worker process, and the scheduler's device thread land on separate
  rows, aligned on wall-clock ``ts``.

Usage::

    python tools/obs_report.py                      # latest trace
    python tools/obs_report.py --trace t4f2ab...    # one trace
    python tools/obs_report.py --trace all          # everything
    python tools/obs_report.py --perfetto out.json  # + Perfetto dump
    python tools/obs_report.py --slow-calls         # the calls the
                                                    #   runtime called
                                                    #   slow, and why
    python tools/obs_report.py --setup              # why the job took
                                                    #   so long to start
    python -m yask_tpu.tools.log_to_csv --traces    # flat CSV instead

The span math (``pick_trace`` / ``self_times`` / ``phase_breakdown``)
lives in ``yask_tpu.obs.span_math`` and is re-exported here — one implementation for the terminal report and the
CSV exporter.

No device work, no jax import — safe to run anywhere, any time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from yask_tpu.obs.span_math import (  # noqa: F401  (re-exports)
    phase_breakdown,
    pick_trace,
    self_times,
)
from yask_tpu.obs.tracer import PHASES, default_trace_path, read_spans


def report(rows: List[Dict], top: int = 10, out=None) -> None:
    out = out or sys.stdout
    if not rows:
        out.write("no spans\n")
        return
    traces = sorted({r.get("trace", "") for r in rows})
    pids = sorted({r.get("pid", 0) for r in rows})
    t0 = min(float(r.get("ts", 0.0)) for r in rows)
    t1 = max(float(r.get("ts", 0.0)) + float(r.get("dur", 0.0))
             for r in rows)
    out.write(f"trace: {', '.join(traces)}\n")
    out.write(f"spans: {len(rows)}  processes: {len(pids)}  "
              f"wall: {t1 - t0:.4f} s\n\n")

    bk = phase_breakdown(rows)
    total = sum(b["secs"] for b in bk.values()) or 1.0
    order = [p for p in PHASES if p in bk] \
        + sorted(set(bk) - set(PHASES))
    out.write(f"{'phase':<12} {'self-time':>10} {'%':>6} {'spans':>6}\n")
    for ph in order:
        b = bk[ph]
        out.write(f"{ph:<12} {b['secs']:>9.4f}s "
                  f"{100.0 * b['secs'] / total:>5.1f}% "
                  f"{b['count']:>6}\n")

    out.write(f"\ntop {min(top, len(rows))} spans by duration:\n")
    for r in sorted(rows, key=lambda r: -float(r.get("dur", 0.0)))[:top]:
        attrs = json.dumps(r.get("attrs", {}), sort_keys=True)
        if len(attrs) > 60:
            attrs = attrs[:57] + "..."
        out.write(f"  {float(r.get('dur', 0.0)):>9.4f}s "
                  f"{(r.get('phase') or '-'):<10} "
                  f"{r.get('name', '?'):<24} {attrs}\n")


def counter_events(rows: List[Dict]) -> List[Dict]:
    """Counter tracks (``ph: "C"``) derived from the span stream, so
    Perfetto shows LOAD on the same timeline as latency:

    * ``serve.batch_occupancy`` — each ``serve.chunk`` span's ``batch``
      attr, raised at the chunk start and dropped back to 0 at its end;
    * ``serve.queue_depth`` — the number of concurrently open
      ``serve.queue_wait`` intervals, stepped at each edge.

    Both are per-pid tracks (a fleet trace gets one pair per worker)."""
    events: List[Dict] = []
    for r in rows:
        if r.get("name") != "serve.chunk":
            continue
        ts = float(r.get("ts", 0.0)) * 1e6
        dur = float(r.get("dur", 0.0)) * 1e6
        pid = r.get("pid", 0)
        occ = r.get("attrs", {}).get("batch", 1)
        events.append({"ph": "C", "name": "serve.batch_occupancy",
                       "ts": ts, "pid": pid, "tid": 0,
                       "args": {"occupancy": occ}})
        events.append({"ph": "C", "name": "serve.batch_occupancy",
                       "ts": ts + dur, "pid": pid, "tid": 0,
                       "args": {"occupancy": 0}})
    edges: List[tuple] = []
    for r in rows:
        if r.get("name") != "serve.queue_wait":
            continue
        ts = float(r.get("ts", 0.0)) * 1e6
        pid = r.get("pid", 0)
        edges.append((ts, 1, pid))
        edges.append((ts + float(r.get("dur", 0.0)) * 1e6, -1, pid))
    depth: Dict[int, int] = {}
    for ts, d, pid in sorted(edges):
        depth[pid] = depth.get(pid, 0) + d
        events.append({"ph": "C", "name": "serve.queue_depth",
                       "ts": ts, "pid": pid, "tid": 0,
                       "args": {"depth": depth[pid]}})
    return events


def to_perfetto(rows: List[Dict]) -> Dict:
    """Chrome trace-event JSON: ``ph: "X"`` complete events in µs on
    the wall clock, one lane per (pid, tid), phase as the category,
    span/trace ids + attrs in ``args``; plus the derived ``ph: "C"``
    load counter tracks (:func:`counter_events`)."""
    events: List[Dict] = []
    for pid in sorted({r.get("pid", 0) for r in rows}):
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0,
                       "args": {"name": f"yask_tpu pid {pid}"}})
    for r in rows:
        events.append({
            "ph": "X",
            "name": r.get("name", "?"),
            "cat": r.get("phase") or "other",
            "ts": float(r.get("ts", 0.0)) * 1e6,
            "dur": float(r.get("dur", 0.0)) * 1e6,
            "pid": r.get("pid", 0),
            "tid": r.get("tid", 0),
            "args": {"trace": r.get("trace", ""),
                     "span": r.get("span", ""),
                     "parent": r.get("parent", ""),
                     **r.get("attrs", {})},
        })
    events.extend(counter_events(rows))
    return {"traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"schema": "yask_tpu.trace/1"}}


def slow_calls_report(rows: List[Dict], out=None) -> int:
    """The operator's view of the call record: one line a
    ``run.slow`` marker (the runtime leaves one right behind each
    ``run_solution`` call that took more than 1.25 times the median of
    the calls before it), oldest first -- how long against which
    median, which launch's enqueue rose most, the final wait, the
    collector's seconds, involuntary context switches, and which of
    the three held the excess.  Returns the number of rows."""
    out = out or sys.stdout
    slow = sorted((r for r in rows if r.get("name") == "run.slow"),
                  key=lambda r: float(r.get("ts", 0.0)))
    if not slow:
        out.write("no slow calls\n")
        return 0
    out.write(f"{'wall ts':>14} {'first':>7} {'n':>4} {'secs':>8} "
              f"{'median':>8} {'launch':>6} {'enqueue':>8} "
              f"{'wait':>8} {'gc':>7} {'nivcsw':>6}  held by\n")
    for r in slow:
        a = r.get("attrs", {})
        out.write(f"{float(r.get('ts', 0.0)):>14.3f} "
                  f"{a.get('first', '?'):>7} {a.get('n', '?'):>4} "
                  f"{float(a.get('secs', 0.0)):>8.4f} "
                  f"{float(a.get('median', 0.0)):>8.4f} "
                  f"{a.get('worst_launch', -1):>6} "
                  f"{float(a.get('worst_enqueue_secs', 0.0)):>8.4f} "
                  f"{float(a.get('wait_secs', 0.0)):>8.4f} "
                  f"{float(a.get('gc_secs', 0.0)):>7.4f} "
                  f"{a.get('nivcsw', 0):>6}  {a.get('held_by', '?')}\n")
    return len(slow)


#: the spans of set-up outside phase ``setup``: what a first call
#: builds, pushes and derives (the tracer's kept spans,
#: docs/observability.md)
SETUP_SPANS = ("compile.chunk", "cache.aot", "state.to_device",
               "state.derive", "tuner.trial")


def setup_report(rows: List[Dict], out=None) -> int:
    """The operator's view of set-up: every span of phase ``setup``
    (import, env, solution, prepare, fills, a session's opening and
    uploads) and every build, push and derived fill
    (:data:`SETUP_SPANS`), oldest first, as a tree by the rows' own
    parent links -- seconds, seconds since the first row began, and the
    attrs that say what it was.  A set-up span under a span that is no
    part of set-up (a build inside a ``run.call``) starts a tree of its
    own, marked with where it ran.  Returns the number of trees."""
    out = out or sys.stdout
    by_id = {r["span"]: r for r in rows}
    kids: Dict[str, List[Dict]] = {}
    for r in rows:
        kids.setdefault(r.get("parent", ""), []).append(r)

    def is_setup(r):
        return r.get("phase") == "setup" or r.get("name") in SETUP_SPANS

    def under_setup(r):
        p = by_id.get(r.get("parent", ""))
        return p is not None and (is_setup(p) or under_setup(p))

    def ts(r):
        return float(r.get("ts", 0.0))

    roots = sorted((r for r in rows
                    if is_setup(r) and not under_setup(r)), key=ts)
    if not roots:
        out.write("no set-up spans\n")
        return 0
    start = ts(roots[0])
    shown = ("stencil", "mode", "var", "via", "bytes", "kind", "k", "n",
             "hit", "lower_secs", "load_secs", "since_start_s", "sid",
             "devices", "platform", "vars")

    def line(r, depth):
        a = r.get("attrs", {})
        parent = by_id.get(r.get("parent", ""))
        where = f"  (in {parent['name']})" if depth == 0 and parent \
            else ""
        said = " ".join(f"{k}={a[k]}" for k in shown if k in a)
        out.write(f"{ts(r) - start:>9.3f} {float(r['dur']):>9.3f}  "
                  f"{'  ' * depth}yt.{r['name']}{where}  {said}\n")
        for c in sorted(kids.get(r["span"], ()), key=ts):
            line(c, depth + 1)

    out.write(f"{'at s':>9} {'secs':>9}  span\n")
    for r in roots:
        line(r, 0)
    out.write(f"{'':>9} {sum(float(r['dur']) for r in roots):>9.3f}  "
              f"in {len(roots)} trees\n")
    return len(roots)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="per-phase breakdown + Perfetto export of the "
                    "obs span trace")
    ap.add_argument("--path", default=None,
                    help="trace file (default: YT_TRACE_EVENTS or "
                         "repo-root TRACE_EVENTS.jsonl)")
    ap.add_argument("--trace", default="",
                    help="trace id to report ('all' = every trace; "
                         "default: the latest)")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest-span list length")
    ap.add_argument("--perfetto", default=None, metavar="OUT",
                    help="also write Chrome/Perfetto trace-event JSON")
    ap.add_argument("--slow-calls", action="store_true",
                    help="list the run.slow markers (every trace "
                         "unless --trace names one) instead of the "
                         "span report")
    ap.add_argument("--setup", action="store_true",
                    help="the set-up spans (import, prepare, fills, "
                         "builds) as a tree with seconds (every trace "
                         "unless --trace names one) instead of the "
                         "span report")
    args = ap.parse_args(argv)

    spans = read_spans(args.path or default_trace_path())
    if args.setup:
        return 0 if setup_report(
            pick_trace(spans, args.trace or "all")) else 1
    if args.slow_calls:
        # a direct call's spans are a trace each: all of them, unless
        # one is asked for
        slow_calls_report(pick_trace(spans, args.trace or "all"))
        return 0 if spans else 1
    rows = pick_trace(spans, args.trace)
    report(rows, top=args.top)
    if args.perfetto:
        with open(args.perfetto, "w") as f:
            json.dump(to_perfetto(rows), f, sort_keys=True)
        sys.stdout.write(f"\nperfetto: {args.perfetto} "
                         f"({len(rows)} events)\n")
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
