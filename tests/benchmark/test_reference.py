"""The plain references, the need-bytes, and what the comparison
catches: a field rounded to bfloat16, one corrupted point."""

import importlib.util
import os
import sys

import numpy as np
import pytest

from bench_util import BENCH

sys.path.insert(0, BENCH)
import check  # noqa: E402


def stencil(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, os.path.join(BENCH, "stencils", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_scipy_laplacian_is_the_plain_one():
    iso = stencil("iso3dfd")
    a = np.random.default_rng(3).random((20, 19, 23))
    np.testing.assert_allclose(iso.laplacian_fast(a, 8),
                               iso.laplacian(a, 8), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("name,wf,want", [
    ("iso3dfd", 2, 10.0),     # p(t), p(t-1), vel read + two levels kept
    ("cube", 4, 2.0),         # A(t) read + one level kept
    ("iso3dfd", 1, 20.0),
])
def test_need_bytes_equal_the_hand_worked_values(name, wf, want):
    assert stencil(name).need_bytes_per_point_step(wf) == want


def test_second_difference_weights():
    iso = stencil("iso3dfd")
    assert iso.second_diff_coefficients(1) == pytest.approx([-2.0, 1.0])
    c = iso.second_diff_coefficients(8)
    assert c[0] + 2 * sum(c[1:]) == pytest.approx(0.0, abs=1e-12)
    # exact on x^2: second derivative 2
    assert sum(2 * ck * k * k for k, ck in enumerate(c)) == \
        pytest.approx(2.0)


def test_cube_is_the_mean_of_27_with_zero_outside():
    cube = stencil("cube")
    a = np.zeros((5, 5, 5))
    a[0, 0, 0] = 27.0
    out = cube.step([a], {}, 1)[0]
    assert out[0, 0, 0] == 1.0 and out[1, 1, 1] == 1.0
    assert out[2, 0, 0] == 0.0 and out.sum() == 8.0


def test_iso3dfd_step_matches_a_pointwise_loop():
    iso = stencil("iso3dfd")
    rng = np.random.default_rng(0)
    old, cur = rng.random((2, 6, 5, 7))
    r = 2
    c = iso.second_diff_coefficients(r)
    pad = np.pad(cur, r)
    want = np.empty_like(cur)
    for i, j, k in np.ndindex(cur.shape):
        lap = 3 * c[0] * cur[i, j, k]
        for d in range(1, r + 1):
            lap += c[d] * (
                pad[i + r - d, j + r, k + r] + pad[i + r + d, j + r, k + r]
                + pad[i + r, j + r - d, k + r] + pad[i + r, j + r + d, k + r]
                + pad[i + r, j + r, k + r - d] + pad[i + r, j + r, k + r + d])
        want[i, j, k] = 2 * cur[i, j, k] - old[i, j, k] + 0.1 * lap
    got = iso.step([old, cur], {"vel": 0.1}, r)
    assert got[0] is cur
    np.testing.assert_allclose(got[1], want, rtol=1e-12, atol=1e-12)


CFG = {"iso3dfd": {"radius": 8, "consts": {"vel": 0.1}},
       "cube": {"radius": 1, "consts": {}}}
DOMAIN, BLOCK, STEPS = [48, 40, 56], 4, 4


def whole_field(name, fill):
    """The reference over the whole toy domain: what a sound program
    would hold after STEPS steps."""
    return check.reference_block(stencil(name), CFG[name], DOMAIN,
                                 [0, 0, 0], max(DOMAIN), STEPS, fill)


@pytest.mark.parametrize("name", ["iso3dfd", "cube"])
def test_cone_blocks_equal_the_whole_field(name):
    fill = check.draw_fill(11, DOMAIN, [1, 1, 1], BLOCK)
    whole = whole_field(name, fill)[:DOMAIN[0], :DOMAIN[1], :DOMAIN[2]]
    for pname, lo in check.probes(DOMAIN, fill, BLOCK).items():
        got = check.reference_block(stencil(name), CFG[name], DOMAIN, lo,
                                    BLOCK, STEPS, fill)
        cut = tuple(slice(a, a + BLOCK) for a in lo)
        np.testing.assert_allclose(got, whole[cut], rtol=1e-12,
                                   atol=1e-12, err_msg=pname)


@pytest.mark.parametrize("name", ["iso3dfd", "cube"])
@pytest.mark.parametrize("fault", ["none", "float32", "bf16", "one-point"])
def test_what_the_comparison_catches(name, fault):
    fill = check.draw_fill(5, DOMAIN, [1, 1, 1], BLOCK)
    whole = whole_field(name, fill)[:DOMAIN[0], :DOMAIN[1], :DOMAIN[2]]
    said = []
    blocks = {}
    for pname, lo in check.probes(DOMAIN, fill, BLOCK).items():
        b = whole[tuple(slice(a, a + BLOCK) for a in lo)].copy()
        if fault == "float32":
            b = b.astype(np.float32)
        elif fault == "bf16":
            b = check.bf16_round(b)
        elif fault == "one-point" and pname == "seam":
            b[1, 2, 3] *= 1.01
        blocks[pname] = b
    ok = check.compare(stencil(name), CFG[name], DOMAIN, BLOCK, STEPS,
                       fill, blocks, 1e-4, said.append)
    assert ok == (fault in ("none", "float32")), said
    assert len(said) == 3 and all("limit 1.000e-04" in s for s in said)


@pytest.mark.parametrize("name", ["iso3dfd", "cube"])
def test_the_bf16_control_comes_out_not_correct(name):
    fill = check.draw_fill(9, DOMAIN, [1, 1, 1], BLOCK)
    said = []
    assert not check.compare(stencil(name), CFG[name], DOMAIN, BLOCK,
                             STEPS, fill, None, 1e-4, said.append,
                             control=True)
    assert all("control(bf16)" in s for s in said)


def test_seed_law_and_large_seeds():
    a = check.seq_box([4, 5, 6], [1, 2, 3], [3, 4, 6], 0.05, 1)
    i = np.arange(120).reshape(4, 5, 6)[1:3, 2:4, 3:6]
    np.testing.assert_array_equal(
        a, ((i % 17 + 1.0) * 0.05 * 2).astype(np.float32))
    big = check.draw_fill(2 ** 31 + 12345, [768] * 3, [1, 1, 1], 8)
    again = check.draw_fill(2 ** 31 + 12345, [768] * 3, [1, 1, 1], 8)
    assert big == again and 0.03 <= big["scale"] <= 0.07
    four = check.draw_fill(3, [1024] * 3, [4, 1, 1], 8)
    assert (four["seam"][0] + 4) % 256 == 0   # straddles a shard seam


def test_dead_or_non_finite_fields_are_not_alive():
    z = {"a": np.zeros((2, 2, 2))}
    assert not check.alive(z)
    assert check.alive({"a": np.ones((2, 2, 2))})
    assert not check.alive({"a": np.full((2, 2, 2), np.nan)})
    assert check.block_error(np.full((2, 2, 2), np.inf),
                             np.ones((2, 2, 2))) == float("inf")
