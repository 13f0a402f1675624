#!/usr/bin/env python
"""Differential checker-soundness harness (``make conformance``).

The static checker (``yask_tpu.checker``) promises that its verdict on
a configured solution predicts what the runtime will do WITHOUT
executing anything.  This harness tests that promise differentially:
for each seed it generates a random solution + configuration, asks the
checker for a static verdict, then actually runs the pallas path
against the jit oracle, and compares the two answers.

A **disagreement** is either direction of drift:

* ``unsound``    — the checker reported NO errors, but the pallas
  build/run raised, or the run's output mismatched the jit oracle
  beyond the field-tolerance policy (``compare_data(...,
  field_epsilon=1e-4)`` — fused in-tile evaluation legitimately
  reassociates long sums, so isolated field-ulp differences are not
  corruption; see ``docs/checking.md``).
* ``overstrict`` — the checker reported an error, yet the identical
  configuration built, ran, and matched the oracle.

Anything else is agreement: clean+match, or error+raise (the checker
predicted the refusal), or error+mismatch (the checker predicted the
corruption).  The jit oracle itself failing on a checker-clean config
also counts as ``unsound`` — the races pass exists precisely to flag
solutions the core analysis rejects.

The generated space covers the structures the checker rules are about:
2-D/3-D domains, radius 1..4, ring depth 1..2, multi-stage chains,
same-point-read written vars (the r21 skew-carry regression class),
IF_DOMAIN condition bands, misc-index coefficient vars, scratch
intermediates, partial-dim read vars WITH the minor dim (legal) and
WITHOUT it (the Mosaic lane-alignment refusal), reverse time, random
block sizes (including below skew carry floors), wf_steps 1..3, and
explicit VMEM budgets (shared by both arms, so the checker's
TPU-default budget and the interpret host's looser default cannot
disagree about which budget is being judged).

On a disagreement the failing configuration is greedily minimized
(features dropped one at a time while the disagreement persists) and
written as a replayable JSON repro under ``tools/logs/`` — rerun with
``--replay tools/logs/conformance_<seed>.json``.

Usage::

    python tools/checker_conformance.py              # 200 seeds
    python tools/checker_conformance.py --seeds 500 --base 1000
    python tools/checker_conformance.py --quick      # the 16-seed
                                                     # tier-1 subset
    python tools/checker_conformance.py --replay tools/logs/....json

Exit status is nonzero iff any disagreement survived.  Always runs on
the CPU interpret host — a differential sweep must never take a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
from typing import Dict, List, Optional, Tuple

# A differential sweep is CPU work by definition: name the interpret
# host BEFORE jax can load, so it never takes a chip.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCHEMA = "yask_tpu.conformance/1"

#: the oracle-match policy: fused in-tile evaluation reassociates long
#: staggered sums (different FMA contraction than XLA's fusion), which
#: shows up as isolated field-ulp differences — NOT corruption.  A real
#: geometry bug produces O(field) errors and fails this by orders of
#: magnitude (the pre-fix awp skew carry: 52k+ points past it).
FIELD_EPSILON = 1e-4

#: per-case wall clock before the resilience guard kills the case
DEADLINE_ENV = "YT_CONFORMANCE_DEADLINE"

#: the tier-1 quick subset (tests/test_conformance.py): seeds chosen
#: 0..N so the covered feature mix is stable run to run
QUICK_SEEDS = 16

_FEATURES = ("two_stage", "same_point_chain", "condition", "misc_var",
             "scratch", "partial_minor", "partial_no_minor", "reverse")


# ---------------------------------------------------------------- gen
def gen_config(seed: int) -> dict:
    """One random-but-reproducible configuration.  Pure function of the
    seed (``random.Random(seed)``), JSON-round-trippable, replayable."""
    rng = random.Random(seed)
    ndims = rng.choice((2, 3))
    r = rng.choice((1, 1, 2, 2, 3, 4))
    wf = rng.choice((1, 1, 2, 2, 3))
    ring = rng.choice((1, 1, 2))
    g = rng.choice((16, 20, 24) if ndims == 3 else (24, 32, 48))
    feats = {
        "two_stage": rng.random() < 0.35,
        "same_point_chain": rng.random() < 0.30,
        "condition": rng.random() < 0.30,
        "misc_var": rng.random() < 0.25,
        "scratch": rng.random() < 0.25,
        "partial_minor": rng.random() < 0.20,
        "partial_no_minor": rng.random() < 0.15,
        "reverse": rng.random() < 0.10,
    }
    # reverse time + deep ring both change the write target; keep the
    # generator in the space the oracle covers (reverse uses ring 1)
    if feats["reverse"]:
        ring = 1
    # block sizes over the LEAD dims only (the minor dim always tiles
    # full-lane); None = let the planner choose.  Occasionally tiny, to
    # walk the skew fallback ladder.
    lead = ndims - 1
    block: Dict[str, Optional[int]] = {}
    for i, d in enumerate("xyz"[:lead]):
        block[d] = rng.choice((None, None, 8, 16, 16, g))
    skew = rng.choice((None, None, None, True, False))
    vmem_mb = rng.choice((0, 0, 0, 64, 100))
    steps = max(2, wf * 2)
    return {"schema": SCHEMA, "seed": seed, "ndims": ndims, "g": g,
            "r": r, "wf": wf, "ring": ring, "block": block,
            "skew": skew, "vmem_mb": vmem_mb, "steps": steps,
            "features": feats}


def build_solution(cfg: dict):
    """A ``yc_solution_base`` from a config — the same front-end path
    user stencils take, so the checker sees nothing special."""
    from yask_tpu.compiler.solution_base import yc_solution_base

    feats = cfg["features"]
    ndims = cfg["ndims"]
    r = cfg["r"]
    ring = cfg["ring"]
    rng = random.Random(cfg["seed"] ^ 0x5EED)
    coef = [round(rng.uniform(0.01, 0.2), 4) for _ in range(r + 1)]

    class _Gen(yc_solution_base):
        def __init__(self):
            super().__init__(f"conf_{cfg['seed']}")

        def define(self):
            t = self.new_step_index("t")
            dims = [self.new_domain_index(d) for d in "xyz"[:ndims]]
            u = self.new_var("U", [t] + dims)

            def at(var, tt, **off):
                args = [dims[i] + off.get("xyz"[i], 0)
                        for i in range(ndims)]
                return var(tt, *args)

            # the core star stencil: ± offsets up to r in every dim
            e = at(u, t) * coef[0]
            for i in range(1, r + 1):
                for d in "xyz"[:ndims]:
                    e = e + (at(u, t, **{d: i})
                             + at(u, t, **{d: -i})) * coef[i]
            if ring == 2:
                e = e + at(u, t - 1) * 0.05

            if feats["misc_var"]:
                im = self.new_misc_index("i")
                c = self.new_var("C", [im])
                e = e * c(0) + c(1)

            if feats["scratch"]:
                s = self.new_scratch_var("S", dims)
                s(*dims).EQUALS(at(u, t) + at(u, t, x=1) * 0.5)
                e = e + s(*[dims[0] - 1] + dims[1:]) * 0.25

            if feats["partial_minor"]:
                # read-only var that DOES include the minor dim: legal
                p = self.new_var("P", dims[1:] if ndims > 1 else dims)
                e = e + p(*(dims[1:] if ndims > 1 else dims)) * 0.1

            if feats["partial_no_minor"]:
                # read-only var MISSING the minor dim: no lane-aligned
                # Mosaic DMA window exists — the checker must flag it
                # and the pallas mode must refuse
                q = self.new_var("Q", dims[:-1])
                e = e + q(*dims[:-1]) * 0.1

            m = None
            if feats["same_point_chain"]:
                # written var read ONLY at zero spatial offset (the awp
                # anelastic mem pattern — the r21 skew-carry class)
                m = self.new_var("M", [t] + dims)
                e = e + at(m, t) * 0.2

            tw = t - 1 if feats["reverse"] else t + 1
            lhs = at(u, tw)
            if feats["condition"]:
                first = self.first_domain_index(dims[0])
                last = self.last_domain_index(dims[0])
                band = ((dims[0] >= first + r + 1)
                        & (dims[0] <= last - (r + 1)))
                lhs.EQUALS(e).IF_DOMAIN(band)
                at(u, tw).EQUALS(at(u, t) * 0.5).IF_DOMAIN(~band)
            else:
                lhs.EQUALS(e)

            if m is not None:
                at(m, tw).EQUALS(at(m, t) * 0.5 + at(u, tw) * 0.1)

            if feats["two_stage"]:
                v = self.new_var("V", [t] + dims)
                ev = at(v, t) * 0.9
                for d in "xyz"[:ndims]:
                    ev = ev + (at(u, tw, **{d: 1})
                               + at(u, tw, **{d: -1})) * 0.05
                at(v, tw).EQUALS(ev)

    return _Gen()


# ---------------------------------------------------------------- run
def _make_ctx(env, cfg: dict, mode: str, wf: int = 1):
    from yask_tpu import yk_factory
    ctx = yk_factory().new_solution(env, build_solution(cfg))
    ctx.apply_command_line_options(f"-g {cfg['g']}")
    o = ctx.get_settings()
    o.mode = mode
    o.wf_steps = wf
    if cfg.get("vmem_mb"):
        o.vmem_budget_mb = cfg["vmem_mb"]
    if cfg.get("skew") is not None:
        o.skew_wavefront = cfg["skew"]
    for d, b in (cfg.get("block") or {}).items():
        if b:
            ctx.set_block_size(d, b)
    return ctx


def static_verdict(env, cfg: dict) -> dict:
    """The checker's answer, WITHOUT executing: the legality passes
    over an unprepared context (pure geometry planning)."""
    from yask_tpu.checker import run_checks
    try:
        ctx = _make_ctx(env, cfg, "pallas", wf=cfg["wf"])
        report = run_checks(ctx, passes=("mosaic", "vmem", "races",
                                         "explain"))
    except Exception as e:   # the checker must NEVER raise — itself a
        return {"clean": False, "checker_raised": True,   # finding
                "error": f"{type(e).__name__}: {e}", "rules": []}
    errs = report.errors
    return {"clean": not errs, "checker_raised": False,
            "rules": sorted({d.rule for d in errs}),
            "messages": [d.message[:200] for d in errs[:4]]}


def _run_one(ctx, cfg: dict):
    from yask_tpu.runtime.init_utils import init_solution_vars
    ctx.prepare_solution()
    init_solution_vars(ctx)
    if cfg["features"]["reverse"]:
        ctx.run_solution(cfg["steps"], 0)
    else:
        ctx.run_solution(0, cfg["steps"] - 1)
    return ctx


def dynamic_verdict(env, cfg: dict) -> dict:
    """What actually happens: jit oracle, then the pallas arm, then the
    field-tolerant comparison."""
    from yask_tpu.utils.exceptions import YaskException
    try:
        ref = _run_one(_make_ctx(env, cfg, "jit"), cfg)
    except YaskException as e:
        return {"oracle_ok": False, "ran": False,
                "error": f"oracle: {e}"}
    try:
        p = _run_one(_make_ctx(env, cfg, "pallas", wf=cfg["wf"]), cfg)
    except YaskException as e:
        return {"oracle_ok": True, "ran": False, "error": str(e)[:300]}
    bad = p.compare_data(ref, field_epsilon=FIELD_EPSILON)
    return {"oracle_ok": True, "ran": True, "match": bad == 0,
            "mismatches": int(bad)}


def classify(static: dict, dynamic: dict) -> str:
    """Agreement classes — see the module docstring."""
    if static.get("checker_raised"):
        return "unsound"          # run_checks may never raise
    if static["clean"]:
        if not dynamic["oracle_ok"]:
            return "unsound"      # core analysis rejected a clean cfg
        if not dynamic["ran"]:
            return "unsound"      # missed infeasibility
        return "agree-clean" if dynamic["match"] else "unsound"
    # checker reported errors:
    if dynamic["oracle_ok"] and dynamic["ran"] and dynamic["match"]:
        return "overstrict"       # predicted failure never happened
    return "agree-error"


def run_case(env, cfg: dict) -> dict:
    """One differential case under the resilience guard (deadline +
    fault classification — tools never hang unattended)."""
    from yask_tpu.resilience.guard import guarded_call

    def _case():
        st = static_verdict(env, cfg)
        dy = dynamic_verdict(env, cfg)
        return {"cfg": cfg, "static": st, "dynamic": dy,
                "verdict": classify(st, dy)}

    deadline = float(os.environ.get(DEADLINE_ENV, "300"))
    try:
        return guarded_call(_case,
                            site=f"suite.conformance.{cfg['seed']}",
                            deadline_secs=deadline)
    except Exception as e:
        # a hang/crash on a case the checker passed is itself a
        # soundness datum; one it flagged is agreement
        st = static_verdict(env, cfg)
        return {"cfg": cfg, "static": st,
                "dynamic": {"oracle_ok": True, "ran": False,
                            "error": f"{type(e).__name__}: {e}"},
                "verdict": "agree-error" if not st["clean"]
                           else "unsound"}


# ------------------------------------------------------------ minimize
def minimize(env, cfg: dict, verdict: str) -> dict:
    """Greedy 1-feature-at-a-time shrink: drop each enabled feature and
    keep the drop while the same disagreement class persists."""
    cur = json.loads(json.dumps(cfg))
    changed = True
    while changed:
        changed = False
        for f in _FEATURES:
            if not cur["features"].get(f):
                continue
            trial = json.loads(json.dumps(cur))
            trial["features"][f] = False
            if run_case(env, trial)["verdict"] == verdict:
                cur = trial
                changed = True
    return cur


def write_repro(out_dir: str, result: dict) -> str:
    os.makedirs(out_dir, exist_ok=True)
    seed = result["cfg"]["seed"]
    path = os.path.join(out_dir, f"conformance_{seed}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    return path


# ---------------------------------------------------------------- main
def sweep(seeds, out_dir: str,
          progress=None) -> Tuple[Dict[str, int], List[dict]]:
    """Run the differential sweep; returns (verdict counts,
    disagreement results with minimized repro configs attached)."""
    from yask_tpu import yk_factory
    env = yk_factory().new_env()
    counts: Dict[str, int] = {}
    bad: List[dict] = []
    for seed in seeds:
        res = run_case(env, gen_config(seed))
        v = res["verdict"]
        counts[v] = counts.get(v, 0) + 1
        if v in ("unsound", "overstrict"):
            res["minimized"] = minimize(env, res["cfg"], v)
            res["repro"] = write_repro(out_dir, res)
            bad.append(res)
        if progress:
            progress(seed, res)
    return counts, bad


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=200)
    ap.add_argument("--base", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help=f"the {QUICK_SEEDS}-seed tier-1 subset")
    ap.add_argument("--replay", metavar="JSON",
                    help="re-run one repro (or raw config) file")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "logs"))
    args = ap.parse_args(argv)

    if args.replay:
        with open(args.replay) as f:
            blob = json.load(f)
        cfg = blob.get("minimized") or blob.get("cfg") or blob
        from yask_tpu import yk_factory
        env = yk_factory().new_env()
        res = run_case(env, cfg)
        print(json.dumps({k: res[k] for k in
                          ("static", "dynamic", "verdict")}, indent=2))
        return 0 if res["verdict"].startswith("agree") else 1

    n = QUICK_SEEDS if args.quick else args.seeds
    seeds = range(args.base, args.base + n)

    def _progress(seed, res):
        tag = res["verdict"]
        if tag in ("unsound", "overstrict"):
            print(f"seed {seed}: {tag.upper()} — repro {res['repro']}")
        elif (seed - args.base + 1) % 25 == 0:
            print(f"...{seed - args.base + 1}/{n}")

    counts, bad = sweep(seeds, args.out, progress=_progress)
    print("conformance:", json.dumps(counts, sort_keys=True))
    for res in bad:
        mini = res["minimized"]
        print(f"  seed {res['cfg']['seed']} {res['verdict']}: "
              f"features={[f for f, on in mini['features'].items() if on]} "
              f"static={res['static']['rules']} "
              f"dynamic={res['dynamic']}")
    print(f"conformance: {len(bad)} disagreement(s) over {n} seeds")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
