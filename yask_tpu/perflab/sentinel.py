"""Regression sentinel: per-row guards over the perf ledger.

Generalizes the lone ad-hoc cube-wavefront guard the round-5 verdict
called out (``tools/bench_suite.py:172-179`` then; a :class:`GuardRule`
now): every produced row is checked against

* a **relative tolerance vs the trailing median** of the last N clean
  same-platform rows for its key (clean = prior guard did not say
  regression, and the machine was not overloaded — :func:`is_clean`),
* optional **absolute floors** for the sentinel rows whose collapse has
  bitten before (the r3 mosaic-geometry slide on the 128³ jit headline,
  the r4 skew mis-engage on the cube wavefront),

and on a breach performs one **automatic re-measure**: if the second
sample clears, the verdict is ``noise`` (both values recorded); if it
also breaches, ``regression``.  The verdict dict rides IN the row, so
the artifact itself says whether a low number was load noise or a real
slide — the question round 5 could not answer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from yask_tpu.perflab import ledger as _ledger

#: a 1-minute load average above this many times the CPU count marks the
#: row dirty: its value reflects contention, not the code under test.
LOAD_CLEAN_MAX = 1.5

#: units the sentinel guards (throughput and speedup rows; error/skip
#: marker rows pass through as ``unguarded``).
GUARDED_UNITS = ("GPts/s", "x")


@dataclass
class GuardRule:
    """One guard: matches row keys by substring (optionally per
    platform), enforces a relative tolerance vs the trailing clean
    median and/or an absolute floor."""
    name: str
    pattern: str = ""               # substring of the row key ("" = all)
    rel_tol: float = 0.35           # breach below (1−tol)×median
    floor: Optional[float] = None   # absolute breach threshold
    window: int = 5                 # trailing-median depth
    platforms: Optional[Tuple[str, ...]] = None   # None = any
    direction: str = "higher"       # "higher"|"lower" is better

    def matches(self, key: str, platform: str) -> bool:
        if self.pattern and self.pattern not in key:
            return False
        return self.platforms is None or platform in self.platforms

    def breaches(self, value: float, baseline: Optional[float]) -> bool:
        lo = self.direction == "lower"
        if self.floor is not None:
            if (value > self.floor) if lo else (value < self.floor):
                return True
        if baseline is not None and baseline > 0:
            lim = ((1.0 + self.rel_tol) * baseline if lo
                   else (1.0 - self.rel_tol) * baseline)
            if (value > lim) if lo else (value < lim):
                return True
        return False


#: Absolute floor for the 128³ jit CPU-proxy headline.  Set from the
#: round-6 recorded-load re-measure (2026-08-05, 1-core proxy host,
#: load1 0.2–0.4, calib ≈1.0 GPts/s): clean median 0.066 GPts/s over 5
#: samples (span 0.061–0.076).  perf_bisect replayed the row-key across
#: r4→r5 revisions on this same host: r5 code is 1.29× FASTER than r4
#: (0.0689 vs 0.0536), so the r4→r5 artifact slide (0.114→0.087) was
#: machine environment, not code — absolute floors must therefore sit
#: well under cross-host variance.  0.8× the clean median catches a
#: halving-class regression without tripping on host differences.
ISO3DFD_128_JIT_FLOOR = 0.052

#: Cube wavefront-speedup floor (was the lone ad-hoc guard in
#: tools/bench_suite.py).  perf_bisect across r3→r5 on one host:
#: r3-end 1.35×, r4 0.93× (the skew mis-engage halving — exactly what
#: this floor exists to catch), r5 profit-gate 1.67×, HEAD 1.74× — the
#: recorded 2.07×→1.82× "residue" is host-environmental; HEAD is the
#: best revision on equal footing (docs/performance.md "cube wavefront
#: residue").  1.5 catches the r4-class halving without flagging
#: cross-host variance.
CUBE_WAVEFRONT_FLOOR = 1.5

#: PROVISIONAL floor for the 2-D-vs-1-D skew speedup ratio
#: (bench_suite ``skew2d-speedup``).  No hardware history yet; the
#: failure class it guards is the r4 cube lesson
#: one dim up — the outer-dim carry mis-engaging and HALVING the rate
#: instead of helping.  0.75 flags a halving-class slide while
#: tolerating the CPU proxy's margin-model inversion (interpret-mode
#: carries are copies, not DMA savings).  Re-base from clean TPU rows
#: once tpu_session banks them.
SKEW2D_SPEEDUP_FLOOR = 0.75

#: PROVISIONAL floor for the shard_pallas overlapped-halo-exchange A/B
#: (bench_suite ``sp-overlap-speedup``: core/shell split forced on vs
#: the serial chunk→exchange schedule).  The failure class: the split
#: costs two extra kernel launches and a merge per K-group, so a
#: schedule bug (or a core region mis-shrunk to nothing) shows as the
#: ratio collapsing.  TPU-scoped: the CPU proxy measures 0.68–0.81×
#: BY CONSTRUCTION (ppermutes are same-host memcpys — there is no
#: collective latency to hide, only the extra launches to pay), so a
#: floor there would alarm on every suite run; the CPU arm stays
#: under the trailing-median backstop instead.  Re-base from clean
#: TPU rows once tpu_session banks the overlap_ab stage — on hardware
#: the ratio is the point of the feature and should clear 1.
SP_OVERLAP_SPEEDUP_FLOOR = 0.95

#: PROVISIONAL floor for the trapezoid-vs-skew/uniform A/B
#: (bench_suite ``trap-speedup``: the two-phase trapezoid/diamond
#: tiling forced via -trapezoid against the same config with the knob
#: off).  The failure class: the parallel-grid win is megacore
#: partitioning + the 2r fetch margin, and both evaporate if the
#: diamond fill passes grow past their model (band recompute is real
#: work) — a collapse of this ratio means the gate engaged where it
#: should not.  TPU-scoped: the CPU interpret proxy has no megacore
#: (cores=2 credit is pure overhead there) and serializes the fill
#: passes, so the proxy ratio sits below 1 BY CONSTRUCTION and only
#: the trailing-median backstop guards that arm.  Re-base from clean
#: TPU rows once tpu_session banks the trapezoid_ab stage.
TRAP_SPEEDUP_FLOOR = 0.9

#: PROVISIONAL floor for the ensemble batched-vs-sequential A/B
#: (bench_suite ``ensembleN-speedup``: N instances as one vmapped
#: program vs N fresh contexts each paying its own trace+lower+
#: compile).  The win has two legs — compile amortization (one build
#: for N members) and device saturation on small domains — and the
#: CPU proxy only measures the FIRST leg (an 8-wide vmap on one core
#: runs the math serially), so compile dominating at 64³ makes ≥2×
#: honest there.  The failure class this guards: the vmapped build
#: silently degrading to the sequential fallback (batched_reason
#: set), which pays N compiles again and collapses the ratio toward
#: 1.  CPU-scoped: re-base on hardware once tpu_session banks the
#: ensemble_ab stage — on a real chip the saturation leg should push
#: the ratio well past the compile-only bound.
ENSEMBLE_SPEEDUP_FLOOR = 2.0

#: PROVISIONAL floor for the serving-layer batched A/B (bench_suite
#: ``serve-batchN-speedup``: N tenants through ONE StencilServer —
#: submit-all-then-wait-all, co-batched by the scheduler window — vs N
#: fresh solo contexts each paying its own compile).  Same
#: compile-amortization leg as the ensemble floor, MINUS the serving
#: machinery's per-request tax (worker handoff, pre-request snapshots,
#: journal rows, sanity gating), which is exactly what this row
#: tracks: a regression here with a healthy ensemble row means the
#: server got expensive, not the batching.  CPU-scoped; re-base on
#: hardware.
SERVE_BATCH_SPEEDUP_FLOOR = 1.5

#: PROVISIONAL floor for the cross-PROFILE shape-bucket serving A/B
#: (bench_suite ``serve-bucket8-speedup``: 8 tenants across >=3
#: DISTINCT geometries through ONE server with bucketing ON — all
#: hosted on one bucket-rung profile, co-batched masked — vs the same
#: traffic with bucketing OFF, where each geometry pays its own
#: prepared profile and only same-geometry requests share a batch).
#: The win is compile amortization across geometries (G profiles ->
#: 1) plus occupancy (three small batches -> one big one); the CPU
#: proxy measures the compile leg.  Bit-identity against solo oracles
#: gates the row before any timing counts.  The failure class this
#: guards: open-session silently declining feasible tenants (every
#: session "exact" -> the arms converge toward 1x) or the masked
#: vmapped path degrading to sequential members.  CPU-scoped;
#: re-base on hardware.
SERVE_BUCKET_SPEEDUP_FLOOR = 1.5

#: PROVISIONAL floor for the cross-solution pipeline-fusion A/B
#: (bench_suite ``pipeline-fusion-speedup``: the 3-stage RTM chain —
#: forward iso wave, imaging correlation, 3-point smoothing — as ONE
#: merged program vs the host-chained schedule that round-trips every
#: binding through HBM plus host slice copies each step).  The HBM
#: model says 2× traffic for this chain (bound vars stream once
#: instead of write+read), and the chained arm additionally pays the
#: host push per binding per step, so ≥1.2× is conservative on the
#: CPU proxy where the push tax dominates.  The failure class this
#: guards: the merge silently falling back to host-chaining (fused
#: False in the ledger row) or a rewrite pessimization making the
#: merged program slower than its parts.  CPU-scoped: re-base on
#: hardware once tpu_session banks the pipeline_fusion_ab stage.
PIPELINE_FUSION_FLOOR = 1.2

#: PROVISIONAL floor for the push-memory tile-graph fusion A/B
#: (bench_suite ``pipeline-push-speedup``: the PURE rtm chain — no
#: img(t) self-read, so the merged image var is pushable — fused with
#: push ON vs the same fused program with ``-push off``, both at the
#: pallas K=1 schedule where every arm is bit-exact vs the
#: host-chained oracle).  The HBM model says the pushed var leaves
#: BOTH HBM paths (fused 20 → fused_push 16 B/pt on this chain), but
#: the CPU interpret proxy realizes only part of that as wall-clock
#: (VMEM tiles are numpy copies there), so the floor sits at parity:
#: the failure class it guards is push ENGAGING AND LOSING — a
#: pessimization where keeping the tile in VMEM costs more than the
#: round-trip it saves (extra seeding, margin recompute), which must
#: never bank as a win.  Engagement itself is asserted by the section
#: (a silent decline raises, it cannot bank 1.0×).  CPU-scoped;
#: re-base from clean TPU rows once tpu_session banks the push_ab
#: stage — on hardware the traffic drop is the point.
PIPELINE_PUSH_FLOOR = 1.0

#: PROVISIONAL floor for the device-resident bulk-serving A/B
#: (bench_suite ``serve-resident-speedup``: the same 4-session x
#: 4-item work list drained by ResidentExecutor.run_queue — one
#: device-lock hold, one end-of-queue sync, one extraction per
#: session — vs per-request scheduler dispatch).  The acceptance bar
#: is "strictly faster at occupancy >= 4"; measured CPU rows sit at
#: 4–6×.  1.5 flags the failure class — the resident path regrowing
#: per-item synchronization (a block_until_ready or host extraction
#: sneaking into the item loop) — without tripping on scheduler-window
#: jitter.  Responses are bit-gated identical across arms before the
#: row banks.  CPU-scoped; re-base on hardware.
SERVE_RESIDENT_FLOOR = 1.5

#: PROVISIONAL floor for the load harness's goodput fraction
#: (tools/load_harness.py ``load-goodput``: completed-ok responses /
#: offered requests on a seeded open-loop run, unit "x" so the
#: sentinel guards it).  The harness's deterministic --check scenario
#: offers load a 1–2 worker CPU fleet can absorb after scale-up, so a
#: healthy run completes (nearly) everything: deadline fast-fails,
#: brownout session rejections, and saturation errors all subtract
#: from goodput, which is exactly the failure class this guards — an
#: overload-control bug silently rejecting admissible traffic, or an
#: autoscaler that stops responding to pressure.  0.9 tolerates a
#: straggler request dying at harness shutdown while flagging any
#: systematic shedding.  CPU-scoped; chaos-soak runs (injected kills/
#: hangs/corruption lower goodput BY DESIGN) bank with distinct
#: ``load-soak`` keys that this pattern does not match.
LOAD_GOODPUT_FLOOR = 0.9

DEFAULT_RULES: List[GuardRule] = [
    GuardRule(name="iso3dfd-128-jit-floor",
              pattern="128^3 fp32 cpu throughput",
              floor=ISO3DFD_128_JIT_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    GuardRule(name="cube-wavefront-floor",
              pattern="wavefront-speedup",
              floor=CUBE_WAVEFRONT_FLOOR, rel_tol=0.25),
    GuardRule(name="skew2d-speedup-floor",
              pattern="skew2d-speedup",
              floor=SKEW2D_SPEEDUP_FLOOR, rel_tol=0.25),
    GuardRule(name="sp-overlap-speedup-floor",
              pattern="sp-overlap-speedup",
              floor=SP_OVERLAP_SPEEDUP_FLOOR, rel_tol=0.25,
              platforms=("tpu",)),
    GuardRule(name="trap-speedup-floor",
              pattern="trap-speedup",
              floor=TRAP_SPEEDUP_FLOOR, rel_tol=0.25,
              platforms=("tpu",)),
    GuardRule(name="ensemble-speedup-floor",
              pattern="ensemble",
              floor=ENSEMBLE_SPEEDUP_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    GuardRule(name="serve-batch-speedup-floor",
              pattern="serve-batch",
              floor=SERVE_BATCH_SPEEDUP_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    GuardRule(name="serve-bucket-speedup-floor",
              pattern="serve-bucket",
              floor=SERVE_BUCKET_SPEEDUP_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    GuardRule(name="pipeline-fusion-floor",
              pattern="pipeline-fusion",
              floor=PIPELINE_FUSION_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    GuardRule(name="pipeline-push-floor",
              pattern="pipeline-push",
              floor=PIPELINE_PUSH_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    GuardRule(name="serve-resident-floor",
              pattern="serve-resident",
              floor=SERVE_RESIDENT_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    GuardRule(name="load-goodput-floor",
              pattern="load-goodput",
              floor=LOAD_GOODPUT_FLOOR, rel_tol=0.25,
              platforms=("cpu",)),
    # the backstop every throughput/speedup row gets: trailing clean
    # median, generous tolerance (CPU-proxy trial noise is real)
    GuardRule(name="trailing-median", rel_tol=0.35),
]


def is_clean(row: Dict) -> bool:
    """Usable as regression baseline: the row's own guard did not say
    regression/breach, the result passed the sanity guards (quarantined
    rows carry anomalous — e.g. all-zero — data whose wall-clock is
    meaningless), and the machine was not overloaded when measured."""
    if row.get("quarantined"):
        return False
    st = row.get("guard", {}).get("status", "ok")
    if st in ("regression", "breach", "anomaly"):
        return False
    prov = row.get("provenance", {})
    load = prov.get("loadavg") or []
    ncpu = prov.get("ncpu") or 0
    if load and ncpu:
        try:
            if float(load[0]) / float(ncpu) > LOAD_CLEAN_MAX:
                return False
        except (TypeError, ValueError):
            return False
    return True


def _applicable(rules: List[GuardRule], key: str,
                platform: str) -> List[GuardRule]:
    return [r for r in rules if r.matches(key, platform)]


def check_row(key: str, value: float, unit: str, platform: str,
              history: List[Dict],
              rules: Optional[List[GuardRule]] = None,
              remeasure: Optional[Callable[[], float]] = None) -> Dict:
    """Evaluate one measurement against its guards; returns the verdict
    dict stored under the row's ``guard`` field.

    ``history`` is this key's prior ledger rows (same platform, file
    order); only clean rows feed the trailing median.  On a breach,
    ``remeasure`` (when given) is called ONCE for a second sample:
    clearing → ``noise``, still breaching → ``regression``; without a
    re-measure hook the verdict stays ``breach``.
    """
    if unit not in GUARDED_UNITS:
        return {"status": "unguarded", "unit": unit}
    rules = DEFAULT_RULES if rules is None else rules
    match = _applicable(rules, key, platform)
    if not match:
        return {"status": "unguarded"}
    verdict: Dict = {"rules": [r.name for r in match]}
    baselines = {}
    for r in match:
        b = _ledger.trailing_median(history, n=r.window, accept=is_clean)
        baselines[r.name] = b
        if r.floor is not None:
            verdict["floor"] = r.floor
    bl = next((b for b in baselines.values() if b is not None), None)
    if bl is not None:
        verdict["baseline"] = round(bl, 4)
        if bl > 0:
            verdict["ratio"] = round(float(value) / bl, 4)

    def breached(v: float) -> List[str]:
        return [r.name for r in match if r.breaches(v, baselines[r.name])]

    first = breached(float(value))
    if not first:
        verdict["status"] = "ok" if bl is not None or any(
            r.floor is not None for r in match) else "no_history"
        return verdict
    verdict["breached"] = first
    if remeasure is None:
        verdict["status"] = "breach"
        return verdict
    try:
        v2 = float(remeasure())
    except Exception as e:  # noqa: BLE001 - verdict must still record
        verdict["status"] = "regression"
        verdict["remeasure_error"] = str(e)[:160]
        return verdict
    verdict["remeasured"] = round(v2, 4)
    verdict["status"] = "regression" if breached(v2) else "noise"
    return verdict


#: absolute phase-share drift (in share points, 0..1) that flags an
#: attribution row against the trailing clean median of its key — a
#: phase quietly growing from 10% to 30% of the run is exactly the
#: "where did the time go" regression the span trace exists to catch.
ATTRIBUTION_SHARE_TOL = 0.15


def check_attribution(shares: Dict[str, float], history: List[Dict],
                      tol: float = ATTRIBUTION_SHARE_TOL,
                      window: int = 5) -> Dict:
    """Guard an attribution row's per-phase SHARES against the trailing
    clean median of prior ``source: "attribution"`` rows for the same
    key.  Shares are compared absolutely (share points), not
    relatively — a 1%→3% phase tripling is noise, a 10%→30% one is a
    drift.  Verdict statuses mirror :func:`check_row`:
    ``no_history`` / ``ok`` / ``drift`` (with the offending phases and
    their medians recorded in the verdict)."""
    clean = [r for r in history if is_clean(r)][-window:]
    if not clean:
        return {"status": "no_history", "rule": "attribution-share-drift"}
    meds: Dict[str, float] = {}
    for ph in shares:
        vals = sorted(
            float((r.get("extra", {}).get("shares") or {}).get(ph, 0.0))
            for r in clean)
        meds[ph] = vals[len(vals) // 2]
    drifted = {ph: {"share": round(s, 4), "median": round(meds[ph], 4)}
               for ph, s in shares.items()
               if abs(s - meds[ph]) > tol}
    verdict: Dict = {"rule": "attribution-share-drift", "tol": tol,
                     "window": len(clean)}
    if drifted:
        verdict["status"] = "drift"
        verdict["drifted"] = drifted
    else:
        verdict["status"] = "ok"
    return verdict


def guard_and_append(key: str, value: float, unit: str, platform: str,
                     source: str, provenance: Dict,
                     rules: Optional[List[GuardRule]] = None,
                     remeasure: Optional[Callable[[], float]] = None,
                     roofline: Optional[Dict] = None,
                     extra: Optional[Dict] = None,
                     path: Optional[str] = None,
                     sanity: Optional[Dict] = None) -> Dict:
    """The one-call producer path: look up this key's history in the
    ledger, evaluate the guards (with optional re-measure), build the
    row with the verdict inside, append it, return it.

    ``sanity`` is a result-sanity verdict from
    :func:`yask_tpu.resilience.check_output`: a failed one quarantines
    the row (``quarantined: true`` + structured ``anomaly`` field,
    guard status ``anomaly``) instead of guarding it — no re-measure is
    attempted (re-timing corrupt data proves nothing) and
    :func:`is_clean` keeps it out of every trailing-median baseline.

    ``source="bisect"`` rows are excluded from the history: they replay
    HISTORICAL revisions (tools/perf_bisect.py) and must not shift the
    trailing median the current code is judged against."""
    if sanity and not sanity.get("ok", True):
        from yask_tpu.resilience import anomaly_fields
        af = anomaly_fields(sanity)
        guard = {"status": "anomaly",
                 "anomalies": af["anomaly"]["anomalies"]}
        row = _ledger.make_row(key, value, unit, platform, source,
                               provenance, guard=guard,
                               roofline=roofline, extra=extra)
        row.update(af)
        _ledger.append_row(row, path=path)
        return row
    history = [r for r in
               _ledger.read_rows(path=path, key=key, platform=platform)
               if r.get("source") != "bisect"]
    if not any(is_clean(r) for r in history):
        # fresh clone / untracked ledger: seed the baseline from the
        # committed BENCH_*.json snapshots (older than any live row)
        history = _ledger.seed_rows_from_bench(key, platform) + history
    guard = check_row(key, value, unit, platform, history, rules=rules,
                      remeasure=remeasure)
    row = _ledger.make_row(key, value, unit, platform, source,
                           provenance, guard=guard, roofline=roofline,
                           extra=extra)
    _ledger.append_row(row, path=path)
    return row
