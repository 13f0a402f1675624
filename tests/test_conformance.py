"""Differential checker-soundness harness: the tier-1 slice.

``tools/checker_conformance.py`` compares the static checker's verdict
against what actually happens on the interpret host; ``make
conformance`` runs the full 200-seed sweep.  Tier-1 keeps:

* the 16-seed ``--quick`` subset (one param per seed, so a regression
  names the seed that caught it — replay with
  ``python tools/checker_conformance.py --replay <repro json>``);
* the planner↔checker byte-equality pin: the ``tile_bytes`` the vmem
  pass reports in its ``VMEM-OK`` detail must equal the ``tile_bytes``
  of the chunk the runtime actually builds at the same budget —
  the "one code path, the model cannot drift" invariant, asserted
  down to the byte;
* generator determinism + a forced agreement-by-refusal case.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import checker_conformance as conf  # noqa: E402

from yask_tpu import yk_factory
from yask_tpu.checker import run_checks


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


# ------------------------------------------------------------- quick
@pytest.mark.parametrize("seed", range(conf.QUICK_SEEDS))
def test_quick_seed_agrees(env, seed):
    """Static and dynamic verdicts agree on every quick-subset seed."""
    res = conf.run_case(env, conf.gen_config(seed))
    assert res["verdict"].startswith("agree"), (
        f"seed {seed} {res['verdict']}: static={res['static']} "
        f"dynamic={res['dynamic']}")


# --------------------------------------------------------- generator
def test_gen_config_deterministic_and_replayable():
    """Same seed → identical config, and the config survives a JSON
    round trip (the repro files depend on both)."""
    for seed in (0, 7, 1234):
        a = conf.gen_config(seed)
        b = conf.gen_config(seed)
        assert a == b
        assert json.loads(json.dumps(a)) == a
        assert a["schema"] == conf.SCHEMA


def test_quick_subset_covers_features():
    """The 16 quick seeds exercise a non-trivial feature mix — if the
    generator's distribution shifts, this names what went dark."""
    cfgs = [conf.gen_config(s) for s in range(conf.QUICK_SEEDS)]
    on = {f for c in cfgs for f, v in c["features"].items() if v}
    assert len(on) >= 4, f"quick subset only covers {sorted(on)}"
    assert {c["ndims"] for c in cfgs} == {2, 3}
    assert any(c["wf"] > 1 for c in cfgs)


def test_forced_refusal_is_agreement(env):
    """A var missing the minor dim: the mosaic pass must flag it AND
    the pallas mode must refuse — agreement by predicted refusal, the
    error arm of the agreement classes."""
    cfg = conf.gen_config(3)
    cfg["features"] = {f: False for f in conf._FEATURES}
    cfg["features"]["partial_no_minor"] = True
    res = conf.run_case(env, cfg)
    assert res["verdict"] == "agree-error", res
    assert not res["static"]["clean"]
    assert res["static"]["rules"], "refusal must carry rule ids"


# ------------------------------------------------- byte-equality pin
def _configured(env, vmem_mb):
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=4)
    ctx.apply_command_line_options("-g 32")
    o = ctx.get_settings()
    o.mode = "pallas"
    o.wf_steps = 2
    o.vmem_budget_mb = vmem_mb
    return ctx


def test_checker_tile_bytes_matches_runtime(env):
    """The vmem pass's VMEM-OK ``tile_bytes`` equals the executed
    chunk's ``tiling["tile_bytes"]`` at the same explicit budget.  Both
    come from ``build_pallas_chunk`` (plan_only vs real build) — this
    pins that they STAY one code path, byte for byte."""
    from yask_tpu.runtime.init_utils import init_solution_vars

    report = run_checks(_configured(env, 64), passes=("vmem",))
    oks = [d for d in report.diagnostics if d.rule == "VMEM-OK"]
    assert oks, [d.rule for d in report.diagnostics]
    checked = oks[0].detail["tile_bytes"]
    assert checked > 0

    ctx = _configured(env, 64)
    ctx.prepare_solution()
    init_solution_vars(ctx)
    ctx.run_solution(0, 1)
    tilings = [t for t in ctx._pallas_tiling.values() if t]
    assert tilings, "pallas run recorded no tiling"
    built = tilings[0]["tile_bytes"]
    assert built == checked, (
        f"checker modeled {checked} B/tile but the runtime built "
        f"{built} B/tile — plan_only and the real build diverged")
    # same blocks too, not just a byte coincidence
    ok_block = list(oks[0].detail["block"])
    assert list(tilings[0]["block"]) == ok_block
