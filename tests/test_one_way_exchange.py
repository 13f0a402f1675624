"""The Pallas shard program sends each var across each face with the
width, and in the direction, that the group's equations read it
(``SolutionAnalysis.group_ghost_widths``; ``shard_step
_prep_shard_pallas``), where the group is one step of one stage; a
deeper group keeps the symmetric cone.

``lbm_d3q19`` is the case: a population is pulled from ``x - c_i``, so
the five that move towards +x are read one row to the LEFT in x and
nowhere to the right, the five that move towards -x the other way, and
the rest population, the eight with ``c_x = 0`` and both masks at the
point alone.  Three things are held:

* the analysis gives each of the nineteen vars its side, in x and in y,
  and gives iso3dfd and awp what the cone gives them;
* on four of the virtual CPU devices, at ``ranks`` [4, 1, 1], [2, 2, 1]
  and [1, 4, 1], 10 steps of ``shard_pallas`` agree at EVERY point of
  all nineteen fields with the benchmark's float64 reference -- with
  every ghost row that a round did not refresh made NaN
  (``poison.poison_unrefreshed_ghosts``), so a read of one is a wrong
  answer; and the poison bites: with the sides swapped the same run is
  wrong;
* the launch's attrs: 10 slabs a later round at x/4, 5 up and 5 down,
  all of them read; awp's and iso3dfd's equal to what the geometry gave
  before (``test_shard_launch_attrs.reckoned``).
"""

import functools
import os
import sys

import numpy as np
import pytest

from poison import poison_unrefreshed_ghosts
from test_shard_launch_attrs import CASES, make, reckoned

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402  (the benchmark's seeding law)
import test_lbm_reference as ref  # noqa: E402  (the reference, loaded)

STENCIL = ref.STENCIL
NAMES = ref.NAMES
#: lattice vector of ``f<i>``, in the reference's own order
VEC = ref.VEC
#: x and y of 4 rows a shard at x/4 and y/4: a shard is narrower than
#: a block, and every row of it but two lies on a face
BOX = (16, 32, 40)
STEPS = 10
TOLERANCE = 1e-5
GRIDS = ([4, 1, 1], [2, 2, 1], [1, 4, 1])


def solution(stencil, radius=None):
    from yask_tpu import yk_factory
    fac = yk_factory()
    return fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)


# -- (a) the analysis ----------------------------------------------------

def side(c):
    """(left, right) ghost rows a pulled population with lattice
    component ``c`` reads: one row against the way it moves."""
    return (1, 0) if c > 0 else (0, 1) if c < 0 else (0, 0)


def test_lbm_populations_are_read_against_the_way_they_move():
    ana = solution("lbm_d3q19")._ana
    need = ana.group_ghost_widths(1)
    assert need == ana.ghost_reads()
    for i, c in enumerate(VEC):
        got = need.get(f"f{i}", {})
        for d, comp in zip("xyz", c):
            assert got.get(d, (0, 0)) == side(comp), (i, d)
    for d in "xy":
        sides = [need.get(f"f{i}", {}).get(d, (0, 0)) for i in range(19)]
        assert (sides.count((1, 0)), sides.count((0, 1)),
                sides.count((0, 0))) == (5, 5, 9)
    # the rest population and the masks are read at the point alone
    assert not any(need.get(k) for k in ("f0", "fluid", "accel"))
    # two steps fused read the first one's values in the margin: cone
    assert ana.group_ghost_widths(2) is None


@pytest.mark.parametrize("stencil,radius,k,need", [
    ("iso3dfd", 8, 2, None),            # K > 1: the cone
    ("awp_abc", None, 1, None),         # dependent stages: the cone
    ("iso3dfd", 8, 1, {"pressure": {d: (8, 8) for d in "xyz"}}),
])
def test_the_other_solvers_keep_what_they_have(stencil, radius, k, need):
    ana = solution(stencil, radius)._ana
    assert ana.group_ghost_widths(k) == need
    if stencil == "iso3dfd":
        # ``vel`` is read at the point: no entry, whatever the depth
        assert set(ana.ghost_reads()) == {"pressure"}
    else:
        assert len(ana.stages) > 1
        # every stress and velocity is read both ways, (1, 2) or (2, 1)
        reads = ana.ghost_reads()
        assert {lr for v in reads.values() for lr in v.values()} \
            >= {(1, 2), (2, 1)}


# -- (b) every point, ghosts poisoned ------------------------------------

@functools.lru_cache(maxsize=None)
def coefficients():
    domain = list(BOX)
    return {name: check.coefficient_box(c, [0, 0, 0], domain)
            for name, c in check.coefficients(
                STENCIL, ref.config_for(domain), domain).items()}


@functools.lru_cache(maxsize=None)
def seeded():
    domain = list(BOX)
    fill = check.draw_fill(2147483923, domain, [1, 1, 1], ref.BLOCK)
    return {name: levels[-1] for name, levels in check.initial_state(
        STENCIL, domain, [0, 0, 0], domain, fill).items()}


@functools.lru_cache(maxsize=None)
def reference():
    domain, lo = list(BOX), [0, 0, 0]
    state = {name: [a.astype(np.float64)] for name, a in seeded().items()}
    for _ in range(STEPS):
        state = STENCIL.step(state, coefficients(), None, lo=lo,
                             domain=domain)
    return {name: levels[-1] for name, levels in state.items()}


def sharded(ranks):
    """``STEPS`` steps of ``shard_pallas`` on the rank grid ``ranks``;
    every field whole, and the launch's attrs."""
    domain = list(BOX)
    last = [n - 1 for n in domain]
    ctx = solution("lbm_d3q19")
    if ctx._env.get_num_ranks() < 4:
        pytest.skip("needs four of the virtual CPU devices")
    ctx.apply_command_line_options(
        "-g_x {} -g_y {} -g_z {} -mode shard_pallas -wf_steps 1".format(
            *domain))
    for d, r in zip("xyz", ranks):
        ctx.set_num_ranks(d, r)
    ctx.prepare_solution()
    for name, value in coefficients().items():
        if name in STENCIL.SCALARS:
            ctx.get_var(name).set_element(value, [])
        else:
            ctx.get_var(name).set_elements_in_slice(value, [0, 0, 0], last)
    for name, level in seeded().items():
        var = ctx.get_var(name)
        newest = var.get_last_valid_step_index()
        var.set_elements_in_slice(level, [newest, 0, 0, 0],
                                  [newest] + last)
    ctx.run_solution(0, STEPS - 1)
    out = {}
    for name in NAMES:
        var = ctx.get_var(name)
        t = var.get_last_valid_step_index()
        out[name] = np.asarray(
            var.get_elements_in_slice([t, 0, 0, 0], [t] + last))
    attrs, = ctx._launch_attrs.values()
    ctx.end_solution()
    return out, attrs


def gap(out):
    """The largest of the nineteen fields' errors over every point
    (NaN anywhere reads as infinite)."""
    worst = max(check.block_error(out[name], reference()[name])
                for name in NAMES)
    return worst if np.isfinite(worst) else np.inf


@pytest.mark.parametrize("ranks", GRIDS, ids=lambda r: "x".join(map(str, r)))
def test_every_point_agrees_with_unrefreshed_ghosts_poisoned(
        ranks, monkeypatch):
    rounds = poison_unrefreshed_ghosts(monkeypatch)
    out, attrs = sharded(ranks)
    assert gap(out) <= TOLERANCE
    # the up-front round and one a step but the last were poisoned:
    # both bands of the 39 arrays in every split dim, but the band a
    # one-row ghost fills whole
    split = sum(1 for r in ranks if r > 1)
    assert len(rounds) == 2 and min(rounds) >= 19 * split
    assert attrs["xbytes_read"] == attrs["xbytes"] > 0
    assert attrs["xslabs_read"] == attrs["xslabs"]
    # per split dim: both slots of the ten once, then the newest of
    # each after every step but the last
    assert attrs["xslabs"] == split * (20 + (STEPS - 1) * 10)


def test_the_poison_bites_where_a_side_is_not_sent(monkeypatch):
    """The same run with every var's sides swapped (a population sent
    the way it does NOT move): what the equations read is then a ghost
    row no round refreshed, and the every-point comparison says so."""
    from yask_tpu.compiler.analysis import SolutionAnalysis
    real = SolutionAnalysis.group_ghost_widths

    def swapped(self, fuse_steps=1):
        need = real(self, fuse_steps)
        return need and {k: {d: (r, l) for d, (l, r) in v.items()}
                         for k, v in need.items()}

    monkeypatch.setattr(SolutionAnalysis, "group_ghost_widths", swapped)
    poison_unrefreshed_ghosts(monkeypatch)
    out, attrs = sharded([4, 1, 1])
    assert gap(out) > 1000 * TOLERANCE
    # the schedule sent as much as ever; none of it was asked for
    assert attrs["xslabs"] == 20 + (STEPS - 1) * 10
    assert attrs["xslabs_read"] == attrs["xbytes_read"] == 0


# -- (c) what a launch says it sent --------------------------------------

def test_lbm_sends_ten_slabs_a_round_at_x4_five_each_way(monkeypatch):
    from yask_tpu.parallel import shard_step
    rounds = []
    real = shard_step.exchange_many

    def exchange_many(items, *args, **kw):
        rounds.append([(g.var.get_name(), w) for _a, g, w in items])
        return real(items, *args, **kw)

    monkeypatch.setattr(shard_step, "exchange_many", exchange_many)
    _out, attrs = sharded([4, 1, 1])
    first, each = rounds
    # a round is handed every array it could refresh; it sends the ten
    # that cross x, each one row and one way
    # (the 39 arrays and the three scalars; the nineteen written)
    assert len(first) == 39 + len(STENCIL.SCALARS) and len(each) == 19
    for handed, per_var in ((first, 2), (each, 1)):
        sent = [(k, w) for k, w in handed if w]
        assert len(sent) == 10 * per_var
        for k, w in sent:
            assert w == {"x": side(VEC[int(k[1:])][0])}, k
        assert [w["x"] for _k, w in sent].count((1, 0)) == 5 * per_var
        still = {k for k, w in handed if not w}
        assert still >= {"f0"} | {f"f{i}" for i, c in enumerate(VEC)
                                  if c[0] == 0}
    assert {"fluid", "accel"} <= {k for k, w in first if not w}
    assert (attrs["xrounds"], attrs["halo"], attrs["stages"]) \
        == (STEPS, 1, 1)
    assert attrs["xslabs"] == attrs["xslabs_x"] == 20 + (STEPS - 1) * 10
    assert attrs["xbytes_read"] == attrs["xbytes"] == attrs["xbytes_x"] \
        == attrs["xbytes_read_x"]
    # a slab is one row of a padded shard: y and z with their pads
    assert attrs["xbytes"] % attrs["xslabs"] == 0


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_cone_sends_what_it_sent(case):
    """iso3dfd at K=2 and awp at K=1 (four stages): every count the
    launch gave before, from the geometry alone, and beside them what
    of it an equation reads -- all of iso3dfd's but ``vel``'s two slabs
    of the first round, and of awp's all but the memory variables',
    which the stress stage reads at the point."""
    stencil, radius, K, domain, n = CASES[case]
    ctx = make(stencil, radius, K, domain)
    ctx.run_solution(0, n - 1)
    attrs, = ctx._launch_attrs.values()
    halo, rounds, slabs, nbytes = reckoned(ctx, K, n)
    assert (attrs["halo"], attrs["xrounds"], attrs["xslabs"],
            attrs["xbytes"]) == (halo, rounds, slabs, nbytes)
    assert (attrs["xslabs_x"], attrs["xbytes_x"]) == (slabs, nbytes)
    reads = ctx._ana.ghost_reads()
    unread = [k for k, g in ctx._program.geoms.items()
              if not g.is_scratch and "x" in g.domain_dims
              and max(reads.get(k, {}).get("x", (0, 0))) == 0]
    assert stencil != "iso3dfd" or unread == ["vel"]
    # two faces of every slot once, then of the newest slot a round
    sent_unread = sum(
        2 * (ctx._program.geoms[k].num_slots
             + (rounds - 1) * ctx._program.geoms[k].is_written)
        for k in unread)
    assert attrs["xslabs"] - attrs["xslabs_read"] == sent_unread > 0
    assert 0 < attrs["xbytes_read"] < attrs["xbytes"]
    assert all(isinstance(v, (int, str, float)) for v in attrs.values())
