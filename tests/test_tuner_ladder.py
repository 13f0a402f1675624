"""Unit tests for the auto-tuner's vmem-budget ladder and the Mosaic
VMEM-OOM infeasibility classification (never-fatal acceptance rule).

These run on stub contexts — no jax, no compilation — so the breaker
and ladder state machines are pinned in tier-1 regardless of backend
availability.
"""

import pytest

from yask_tpu.runtime.auto_tuner import AutoTuner


class _Env:
    def __init__(self):
        self.msgs = []

    def trace_msg(self, m):
        self.msgs.append(m)


class _Ana:
    step_dir = 1
    domain_dims = ["x", "y", "z"]


class _Opts:
    def __init__(self, mb=0, ladder=True):
        self.vmem_budget_mb = mb
        self.tune_vmem_ladder = ladder
        self.wf_steps = 1
        self.block_sizes = {"x": 0, "y": 0}


class _Ctx:
    def __init__(self, mb=0, ladder=True):
        self._env = _Env()
        self._opts = _Opts(mb, ladder)
        self._ana = _Ana()
        self._tuned = False


def _tuner(mb=0, ladder=True):
    t = AutoTuner(_Ctx(mb, ladder))
    t.trial_secs = 0.0
    t.best_rate = None
    return t


# ---------------------------------------------------------------- rungs

def test_ladder_rungs_auto_budget():
    assert _tuner(mb=0, ladder=True)._ladder_rungs() == [64, 96, 120]


def test_ladder_rungs_pinned_budget():
    # an explicit -vmem_mb disables the sweep (single rung, old behavior)
    assert _tuner(mb=80, ladder=True)._ladder_rungs() == [80]


def test_ladder_rungs_disabled():
    assert _tuner(mb=0, ladder=False)._ladder_rungs() == [0]


# ----------------------------------------------- OOM classification

def _vmem_oom():
    raise RuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: Ran out of "
        "memory in memory space vmem. Used 140.0M (register allocator "
        "spill slots), limit 128.0M")


def _backend_err():
    raise RuntimeError("INTERNAL: stream terminated by RST_STREAM")


def test_vmem_oom_is_infeasible_never_fatal():
    """A Mosaic VMEM OOM marks the candidate infeasible and NEVER
    trips the outage breaker, however many rungs strike out."""
    t = _tuner()
    for i in range(10):
        r = t._measure((2, (8, 16 + i)), _vmem_oom)
        assert r == float("inf")
    assert getattr(t, "_consec_fails", 0) == 0


def test_outage_breaker_still_trips():
    """Backend errors WITHOUT a vmem signature (a dead backend) still
    re-raise after 3 consecutive failures."""
    t = _tuner()
    assert t._measure((1, (8, 16)), _backend_err) == float("inf")
    assert t._measure((2, (8, 16)), _backend_err) == float("inf")
    with pytest.raises(RuntimeError):
        t._measure((3, (8, 16)), _backend_err)


def test_vmem_oom_does_not_feed_breaker():
    """Interleaved VMEM OOMs neither advance nor trip the breaker."""
    t = _tuner()
    t._measure((1, (8, 16)), _backend_err)
    t._measure((2, (8, 16)), _vmem_oom)      # backend alive: no count
    t._measure((3, (8, 16)), _backend_err)
    assert t._consec_fails == 2
    with pytest.raises(RuntimeError):
        t._measure((4, (8, 16)), _backend_err)


def test_unrelated_exception_still_raises():
    t = _tuner()

    def boom():
        raise ValueError("not a backend thing")
    with pytest.raises(ValueError):
        t._measure((1, (8, 16)), boom)


# ------------------------------------------------------- ladder walk

def test_walk_ladder_applies_winning_rung():
    t = _tuner(mb=0, ladder=True)
    rates = {64: 2.0, 96: 1.0, 120: 3.0}
    seen = []

    def walk_one(mb, ladder):
        assert ladder is True
        assert t.ctx._opts.vmem_budget_mb == mb   # rung active during walk
        seen.append(mb)
        return (4, (8, 16)), rates[mb]

    k = t._walk_ladder(walk_one, ["x", "y"])
    assert seen == [64, 96, 120]
    assert k == 4
    assert t.ctx._opts.wf_steps == 4
    assert t.ctx._opts.block_sizes == {"x": 8, "y": 16}
    assert t.ctx._opts.vmem_budget_mb == 96
    assert t.ctx._tuned


def test_walk_ladder_single_rung_keeps_budget():
    t = _tuner(mb=80, ladder=True)

    def walk_one(mb, ladder):
        assert mb == 80 and ladder is False
        return (2, (8, 16)), 1.0

    t._walk_ladder(walk_one, ["x", "y"])
    assert t.ctx._opts.vmem_budget_mb == 80


def test_walk_ladder_all_infeasible_keeps_settings():
    t = _tuner(mb=0, ladder=True)

    def walk_one(mb, ladder):
        return (2, (8, 16)), float("inf")

    k = t._walk_ladder(walk_one, ["x", "y"])
    assert k == t.ctx._opts.wf_steps == 1          # untouched
    assert t.ctx._opts.vmem_budget_mb == 0         # budget restored
    assert t.ctx._tuned                            # but tuning concluded


# -------------------------------------------------------- apply_best

def test_apply_best_with_budget_element():
    t = _tuner()
    t.results = {(2, (8, 16), 96): 0.5, (4, (8, 16), 64): 1.0,
                 (8, (8, 16), 120): float("inf")}
    t.apply_best()
    assert t.ctx._opts.wf_steps == 2
    assert t.ctx._opts.block_sizes == {"x": 8, "y": 16}
    assert t.ctx._opts.vmem_budget_mb == 96


def test_apply_best_shard_prefix_with_budget():
    t = _tuner()
    t.results = {("sp", 2, (4, 8), 120): 0.1, ("sp", 4, (4, 8), 64): 0.4}
    t.apply_best()
    assert t.ctx._opts.wf_steps == 2
    assert t.ctx._opts.block_sizes == {"x": 4, "y": 8}
    assert t.ctx._opts.vmem_budget_mb == 120


def test_apply_best_legacy_keys_leave_budget_alone():
    t = _tuner(mb=0)
    t.results = {(2, (8, 16)): 0.5, (4,): 1.0}
    t.apply_best()
    assert t.ctx._opts.wf_steps == 2
    assert t.ctx._opts.vmem_budget_mb == 0

# ---------------------------------------- ladder plan-signature dedupe

def test_dedup_ladder_aliases_identical_plans():
    """Two rungs whose plan signatures agree share one measurement."""
    t = _tuner()
    t._plan_signature = lambda k, blk, mb: '{"block": [8, 16]}'
    k1 = (2, (8, 16), 64)
    k2 = (2, (8, 16), 96)
    assert t._dedup_ladder_key(2, (8, 16), 64, k1) is False  # first seen
    t.results[k1] = 0.5
    assert t._dedup_ladder_key(2, (8, 16), 96, k2) is True
    assert t.results[k2] == 0.5
    assert t.ladder_dedup_hits == 1
    assert any("plans identically" in m for m in t.ctx._env.msgs)


def test_dedup_ladder_distinct_plans_not_aliased():
    t = _tuner()
    t._plan_signature = lambda k, blk, mb: f'{{"limit": {mb}}}'
    t.results[(2, (8, 16), 64)] = 0.5
    t._dedup_ladder_key(2, (8, 16), 64, (2, (8, 16), 64))
    assert t._dedup_ladder_key(2, (8, 16), 96,
                               (2, (8, 16), 96)) is False
    assert (2, (8, 16), 96) not in t.results
    assert t.ladder_dedup_hits == 0


def test_dedup_ladder_no_signature_no_dedupe():
    """A failed plan (signature None) must never alias anything."""
    t = _tuner()
    t._plan_signature = lambda k, blk, mb: None
    t.results[(2, (8, 16), 64)] = 0.5
    assert t._dedup_ladder_key(2, (8, 16), 96,
                               (2, (8, 16), 96)) is False
    assert t.ladder_dedup_hits == 0


def test_dedup_ladder_existing_key_untouched():
    """A key that already has a measurement is never overwritten."""
    t = _tuner()
    t._plan_signature = lambda k, blk, mb: '{"same": 1}'
    t.results[(2, (8, 16), 64)] = 0.5
    t.results[(2, (8, 16), 96)] = 0.7
    assert t._dedup_ladder_key(2, (8, 16), 96,
                               (2, (8, 16), 96)) is False
    assert t.results[(2, (8, 16), 96)] == 0.7
