"""Span math over ``TRACE_EVENTS.jsonl`` rows: which trace, each span's
self-time, the per-phase breakdown.

What ``tools/obs_report.py``'s terminal report is computed with, and
re-exported from there (:func:`pick_trace` / :func:`self_times` /
:func:`phase_breakdown`).  No jax import.
"""

from __future__ import annotations

from typing import Dict, List


def pick_trace(rows: List[Dict], trace: str = "") -> List[Dict]:
    """Filter rows to one trace id; default = the LATEST trace (the one
    whose newest span has the greatest wall ts); ``"all"`` keeps every
    row."""
    if trace == "all":
        return list(rows)
    if not trace:
        latest: Dict[str, float] = {}
        for r in rows:
            t = r.get("trace", "")
            latest[t] = max(latest.get(t, 0.0), float(r.get("ts", 0.0)))
        if not latest:
            return []
        trace = max(latest, key=lambda t: latest[t])
    return [r for r in rows if r.get("trace") == trace]


def self_times(rows: List[Dict]) -> Dict[str, float]:
    """span id → duration minus direct children's durations (floored
    at 0 — children on other threads can overlap their parent)."""
    child_dur: Dict[str, float] = {}
    for r in rows:
        p = r.get("parent", "")
        if p:
            child_dur[p] = child_dur.get(p, 0.0) + float(r.get("dur", 0.0))
    return {r["span"]: max(0.0, float(r.get("dur", 0.0))
                           - child_dur.get(r.get("span", ""), 0.0))
            for r in rows if "span" in r}


def phase_breakdown(rows: List[Dict]) -> Dict[str, Dict]:
    """Per-phase ``{secs, count}`` from self-times."""
    selfs = self_times(rows)
    out: Dict[str, Dict] = {}
    for r in rows:
        ph = r.get("phase") or "other"
        b = out.setdefault(ph, {"secs": 0.0, "count": 0})
        b["secs"] += selfs.get(r.get("span", ""), 0.0)
        b["count"] += 1
    return out
