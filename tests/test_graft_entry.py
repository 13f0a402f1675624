"""Driver entry-point regression tests: entry() must stay jittable and
dryrun_multichip must work for the device counts the driver may probe."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_entry_jits():
    import jax
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert set(out.keys()) == {"pressure", "vel"}
    leaf = out["pressure"][-1]
    # minor (lane) dim: interior+halos rounded to a 128-multiple so HBM
    # physical layout == logical extent (Mosaic DMA alignment policy)
    assert leaf.shape[-1] % 128 == 0 and leaf.shape[-1] >= 128 + 16


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_dryrun_multichip(n):
    import jax
    if len(jax.devices()) < n:
        pytest.skip("not enough virtual devices")
    import __graft_entry__ as ge
    ge.dryrun_multichip(n)
