"""Measured-vs-modeled roofline attribution over the span trace.

The measured per-phase wall time of one trace (span SELF-times —
duration minus direct children, the same attribution
``tools/obs_report.py`` prints) laid against what an HBM roofline
(``yask_tpu.runtime.roofline``'s dict) says the compute phase *should*
have cost: :func:`attribute` builds the measured report, :func:`join_model`
attaches the modeled side.

The span math (:func:`pick_trace` / :func:`self_times` /
:func:`phase_breakdown` / :func:`halo_cal_status`) lives here and is
re-exported by ``tools/obs_report.py`` — one implementation for the
terminal report and the CSV exporter.

A halo-cal-unstable trace is flagged (``halo_cal_unstable``) and
dropped from the ``--attribution`` table: its exchange split is noise.

Schema: ``yask_tpu.attribution/1``.  No jax import.
"""

from __future__ import annotations

from typing import Dict, List, Optional

ATTRIBUTION_SCHEMA = "yask_tpu.attribution/1"
ROOT_SPAN = "run.supervised"


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
    """Per-phase ``{secs, count}`` from self-times, with ``halo.share``
    exchange evidence moved out of the compute bucket (it measures a
    slice of a compute span's interval, not a nested child)."""
    selfs = self_times(rows)
    out: Dict[str, Dict] = {}
    halo_share = 0.0
    for r in rows:
        ph = r.get("phase") or "other"
        b = out.setdefault(ph, {"secs": 0.0, "count": 0})
        b["secs"] += selfs.get(r.get("span", ""), 0.0)
        b["count"] += 1
        if r.get("name") == "halo.share":
            halo_share += float(r.get("dur", 0.0))
    if halo_share > 0 and "compute" in out:
        out["compute"]["secs"] = max(
            0.0, out["compute"]["secs"] - halo_share)
        out["compute"]["halo_share_moved"] = halo_share
    return out


def halo_cal_status(rows: List[Dict]) -> Dict:
    """Aggregate the halo-calibration spans: rep/spread evidence plus
    whether any calibration came out UNSTABLE (an unstable split is
    noise, not a halo datum)."""
    cals = [r for r in rows if r.get("name") == "halo_cal"]
    att = [r.get("attrs", {}) for r in cals]
    return {
        "count": len(cals),
        "reps": sum(int(a.get("reps", 0) or 0) for a in att),
        "max_spread": max([float(a.get("spread", 0.0) or 0.0)
                           for a in att] or [0.0]),
        "unstable": sum(1 for a in att if a.get("unstable")),
    }


def attribute(rows: List[Dict], trace: str = "") -> Optional[Dict]:
    """Build the measured side of the attribution report for one trace:
    per-phase self-time seconds + shares, the root-span total they must
    reconcile against, and the halo-cal stability flag.  None when the
    trace has no spans."""
    rows = pick_trace(rows, trace)
    if not rows:
        return None
    tid = rows[0].get("trace", "")
    bk = phase_breakdown(rows)
    total = sum(b["secs"] for b in bk.values())
    root_secs = sum(float(r.get("dur", 0.0)) for r in rows
                    if r.get("name") == ROOT_SPAN)
    hc = halo_cal_status(rows)
    phases = {}
    for ph, b in sorted(bk.items()):
        phases[ph] = {"measured_secs": round(b["secs"], 6),
                      "share": round(b["secs"] / total, 4) if total else 0.0,
                      "count": b["count"]}
    return {"v": ATTRIBUTION_SCHEMA,
            "trace": tid,
            "phases": phases,
            "measured_total_secs": round(total, 6),
            "root_secs": round(root_secs, 6),
            "halo_cal_unstable": hc["unstable"]}


def join_model(report: Dict, roofline: Optional[Dict] = None,
               modeled: Optional[Dict] = None) -> Dict:
    """Attach the modeled side: explicit per-phase modeled seconds
    (``modeled={phase: secs}``) win; otherwise the compute phase is
    modeled from the run's roofline fraction (``roofline_frac`` =
    achieved/roofline rate, so the roofline-speed run would have taken
    ``measured × frac`` seconds).  ``efficiency`` = modeled/measured —
    1.0 means running exactly at the model, lower is headroom."""
    frac = (roofline or {}).get("roofline_frac")
    for ph, d in report.get("phases", {}).items():
        m = (modeled or {}).get(ph)
        if m is None and ph == "compute" and frac is not None:
            m = d["measured_secs"] * float(frac)
        if m is None:
            continue
        d["modeled_secs"] = round(float(m), 6)
        if d["measured_secs"] > 0:
            d["efficiency"] = round(float(m) / d["measured_secs"], 4)
    if roofline:
        report["roofline"] = {k: v for k, v in roofline.items()
                              if v is not None}
    return report
