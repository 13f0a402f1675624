"""The models the program itself computes with: the HBM roofline of a
measured rate (``runtime/roofline.py``, printed by the harness) and
the ICI/DCN link model the comm
scheduler orders mesh axes by (``parallel/comm_plan.py``)."""

import pytest

from yask_tpu.parallel.comm_plan import (link_model, link_secs,
                                         order_comm_axes)
from yask_tpu.runtime.roofline import (ctx_roofline, format_roofline,
                                       roofline)


def test_roofline_model_values():
    # 0.5 GPts/s at 21.1 B/pt = 10.55 GB/s; vs 819 GB/s/chip × 1
    r = roofline(0.5, 21.09, 819e9, ndev=1)
    assert r["hbm_bytes_pp"] == 21.09
    assert r["hbm_gbps"] == 10.5
    assert r["roofline_frac"] == round(0.5 * 21.09 * 1e9 / 819e9, 4)
    # unknown peak (CPU proxy): fraction absent, not a fake zero
    assert roofline(0.5, 21.09, 0.0)["roofline_frac"] is None
    # mesh scaling: 4 chips double-double the denominator
    r4 = roofline(2.0, 21.09, 819e9, ndev=4)
    assert r4["roofline_frac"] == round(2.0 * 21.09 * 1e9 / (4 * 819e9), 4)


def test_ctx_roofline_matches_pre_hoist_formula():
    # gbps = rate × (read+write bytes/pt); frac = gbps/peak — from a
    # real prepared context so hbm_model_bytes_pp is the live model
    from yask_tpu import yk_factory
    env = yk_factory().new_env()
    ctx = yk_factory().new_solution(env, stencil="3axis", radius=1)
    ctx.apply_command_line_options("-g 16")
    ctx.prepare_solution()
    rb, wb = ctx.hbm_model_bytes_pp()
    rate = 0.25
    roof = ctx_roofline(ctx, env, rate)
    assert roof["hbm_bytes_pp"] == round(rb + wb, 2)
    assert roof["hbm_gbps"] == round(rate * (rb + wb), 1)
    peak = env.get_hbm_peak_bytes_per_sec()
    if peak:
        assert roof["roofline_frac"] == round(
            rate * (rb + wb) * 1e9 / (peak * env.get_num_ranks()), 4)
    else:
        assert roof["roofline_frac"] is None
    txt = format_roofline(roof)
    assert "hbm-bytes-per-point (read+write):" in txt
    assert "achieved-HBM (GB/s):" in txt


def test_format_roofline_prints_fraction_only_when_known():
    known = format_roofline(roofline(0.5, 20.0, 819e9))
    assert "hbm-roofline-fraction (%): 1.22\n" in known
    assert "hbm-roofline-fraction" not in format_roofline(
        roofline(0.5, 20.0, 0.0))


@pytest.mark.parametrize("kind,device,gbps,lat", [
    ("ici", "TPU v5 lite", 45.0, 1.0),
    ("ici", "TPU v5p", 90.0, 1.0),
    ("ici", "", 40.0, 1.0),             # CPU proxy mesh: the default
    ("dcn", "TPU v5 lite", 12.5, 25.0),
])
def test_link_model_by_device_kind(kind, device, gbps, lat):
    link = link_model(device, kind)
    assert link == {"kind": kind, "gbps": gbps, "latency_us": lat}
    # flight time: latency + bytes over bandwidth
    assert link_secs(1e6, link) == pytest.approx(
        lat * 1e-6 + 1e6 / (gbps * 1e9))


def test_order_comm_axes_dcn_first_then_longest_flight():
    costs = {"x": {"kind": "ici", "secs": 1e-5},
             "y": {"kind": "ici", "secs": 3e-5},
             "z": {"kind": "dcn", "secs": 2e-6}}
    assert order_comm_axes(costs) == ["z", "y", "x"]
    # ties keep the input (domain-dim) order
    tie = {"x": {"kind": "ici", "secs": 1e-5},
           "y": {"kind": "ici", "secs": 1e-5}}
    assert order_comm_axes(tie) == ["x", "y"]
