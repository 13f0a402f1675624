"""The shard program's K-group loop hands its carry on without copying
it (``parallel/shard_step.py carry_period``, ``build_pallas_chunk(
reuse_evicted=)``): a scan iteration runs two groups, so what it hands
on was written into buffers the loop already owns, and a ring that only
rotates takes its new level in the slot it evicts.

Held here: the loop is the one-group-a-call schedule to the bit, for an
even and an odd group count and with a remainder, serial and
overlapped, on x/4 and on 2x2; the optimised HLO of the interpret-mode
program has no ``copy`` of a padded shard array in the loop's body (the
CPU compiler's: the chip's is quoted in ``PERF.md``); the launch span
names the groups peeled off the scan; and an evicted slot handed over
as NaN (``tests/poison.py``) shows nowhere in the result.
"""

import re

import numpy as np
import pytest

from yask_tpu import yk_factory

#: stencil, radius, -wf_steps, domain, ranks, steps a call ->
#: (groups a scan iteration, scan iterations, peeled before, after,
#: outputs a group writes onto the slot it evicts)
CASES = {
    # overlapped (a shard of 16 >= 2 x hK): group 0 ahead of the scan
    "iso3dfd-k2-x4-even": (("iso3dfd", 2, 2, (64, 16, 128), (4, 1, 1), 10),
                           (2, 2, 1, 0, 0)),
    "iso3dfd-k2-x4-odd": (("iso3dfd", 2, 2, (64, 16, 128), (4, 1, 1), 8),
                          (2, 1, 1, 1, 0)),
    "iso3dfd-k2-x4-rem": (("iso3dfd", 2, 2, (64, 16, 128), (4, 1, 1), 9),
                          (2, 1, 1, 2, 0)),
    "iso3dfd-k2-2x2-even": (("iso3dfd", 2, 2, (32, 48, 128), (2, 2, 1), 10),
                            (2, 2, 1, 0, 0)),
    "iso3dfd-k2-2x2-rem": (("iso3dfd", 2, 2, (32, 48, 128), (2, 2, 1), 7),
                           (2, 1, 1, 1, 0)),
    # serial (no split at K=1): the call's last group behind the scan
    "awp_abc-k1-x4-odd": (("awp_abc", None, 1, (32, 16, 128), (4, 1, 1), 10),
                          (2, 4, 0, 2, 6)),
    "awp_abc-k1-x4-even": (("awp_abc", None, 1, (32, 16, 128), (4, 1, 1), 9),
                           (2, 4, 0, 1, 6)),
    # one group a call: no loop to speak of
    "awp_abc-k1-x4-one": (("awp_abc", None, 1, (32, 16, 128), (4, 1, 1), 1),
                          (1, 0, 0, 1, 6)),
}


def make(stencil, radius, K, domain, ranks):
    """Dense and seeded, never a lone impulse."""
    fac = yk_factory()
    env = fac.new_env()
    if env.get_num_ranks() < 4:
        pytest.skip("needs four of the virtual CPU devices")
    ctx = fac.new_solution(env, stencil=stencil, radius=radius)
    ctx.apply_command_line_options(
        "-g_x {} -g_y {} -g_z {} ".format(*domain)
        + f"-mode shard_pallas -wf_steps {K}")
    for d, r in zip("xyz", ranks):
        if r > 1:
            ctx.set_num_ranks(d, r)
    ctx.prepare_solution()
    rng = np.random.RandomState(48)
    for name in ctx.get_var_names():
        var = ctx.get_var(name)
        if not var.get_domain_dim_names():
            var.set_element(0.05, [])
        elif var.get_step_dim_name():
            for t in range(var.get_first_valid_step_index(),
                           var.get_last_valid_step_index() + 1):
                var.set_elements_in_slice(
                    rng.rand(*domain).astype(np.float32) * 0.1,
                    [t, 0, 0, 0], [t] + [n - 1 for n in domain])
        else:
            var.set_all_elements_same(0.1)
    return ctx


def loop_attrs(ctx, n):
    """The loop's part of the launch attrs of the ``n``-step program."""
    attrs, = [a for key, a in ctx._launch_attrs.items() if key[1] == n]
    return tuple(attrs[k] for k in ("loop_groups", "loop_iters",
                                    "peeled_before", "peeled_after",
                                    "reused"))


def finite(ctx):
    for name in ctx.get_var_names():
        var = ctx.get_var(name)
        if var.get_step_dim_name():
            t = var.get_last_valid_step_index()
            sizes = [ctx._opts.global_domain_sizes[d]
                     for d in var.get_domain_dim_names()]
            block = np.asarray(var.get_elements_in_slice(
                [t] + [0] * len(sizes), [t] + [s - 1 for s in sizes]))
            if not np.isfinite(block).all():
                return False
    return True


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_loop_is_the_one_group_a_call_schedule_to_the_bit(case):
    (stencil, radius, K, domain, ranks, n), want = CASES[case]
    whole = make(stencil, radius, K, domain, ranks)
    whole.run_solution(0, n - 1)
    assert loop_attrs(whole, n) == want
    # the tiling record says the same of the variant compiled last
    loop = whole.compiled_plans()[-1]["loop"]
    assert tuple(loop.values()) == want
    # every group of the call is somewhere
    assert want[2] + want[0] * want[1] + want[3] == -(-n // K)
    apiece = make(stencil, radius, K, domain, ranks)
    for t in range(0, n, K):
        apiece.run_solution(t, min(t + K, n) - 1)
    assert loop_attrs(apiece, K)[:4] == (1, 0, 0, 1)
    assert finite(whole)
    assert whole.compare_data(apiece, epsilon=0.0, abs_epsilon=0.0) == 0


@pytest.mark.parametrize("rings,K,want", [
    ({"a": 1}, 1, 2),               # out of place: a -> f(a) -> f(f(a))
    ({"p": 2}, 2, 2),               # both slots renewed a group
    ({"p": 2}, 1, 2),               # [s0, s1] -> [s1, new]
    ({"p": 2, "v": 1}, 4, 2),
    ({"p": 4}, 1, 4),               # back in place after four
    ({"p": 4}, 2, 2),
    ({"p": 3}, 1, 2),               # lcm(2, 3) = 6 groups: not worth it
    ({"p": 3, "ro": 0}, 3, 2),      # a read-only array has no say
])
def test_the_carrys_period_from_the_rings_alone(rings, K, want):
    import types
    from yask_tpu.parallel.shard_step import carry_period
    prog = types.SimpleNamespace(geoms={
        n: types.SimpleNamespace(is_written=bool(L), num_slots=L or 1)
        for n, L in rings.items()})
    assert carry_period(prog, list(rings), K) == want


def loop_bodies(text):
    """``{computation: [instruction lines]}`` of the ``while`` bodies
    of an HLO module that hold an exchange: the K-group loop's, not
    the grid loops interpret mode makes of a kernel."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1) if head.group(1) in bodies else None
            if name:
                found[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            found[name].append(line.strip())
    return {name: lines for name, lines in found.items()
            if any("collective-permute" in ln for ln in lines)}


def shard_shapes(ctx, K):
    """The padded shapes of one shard's arrays, as HLO writes them."""
    from yask_tpu.ops.tile_planner import TilePlan
    plan = TilePlan(ctx._program, K)
    rad = ctx._ana.fused_step_radius()
    pad = {d: plan.halo(d) for d in plan.lead}
    pad[plan.minor] = rad.get(plan.minor, 0) * K
    local = ctx._csol.plan(
        ctx._opts.rank_domain_sizes,
        global_sizes=ctx._opts.global_domain_sizes,
        extra_pad={d: (w, w) for d, w in pad.items()})
    return {"f32[" + ",".join(str(n) for n in g.shape) + "]"
            for g in local.geoms.values()
            if not g.is_scratch and len(g.shape) == 3}


#: case -> whole-array copies allowed in the loop's body, a group.  The
#: tree before this loop: 6 and 4.  The overlapped body's pair is the
#: CPU compiler's copy of each newest slot ahead of the unpack (the
#: core reads the slot as it was before the exchange); the chip's
#: compiler orders the unpack behind the core and makes none.
COPIES_A_GROUP = {"awp_abc-k1-x4-odd": 0, "iso3dfd-k2-x4-even": 2}


@pytest.mark.parametrize("case", sorted(COPIES_A_GROUP))
def test_the_loops_body_copies_no_padded_array(case):
    (stencil, radius, K, domain, ranks, n), want = CASES[case]
    ctx = make(stencil, radius, K, domain, ranks)
    ctx.run_solution(0, n - 1)
    text, = [t for t in ctx.compiled_texts()
             if t.startswith("HloModule jit_yt_shard_pallas,")]
    shapes = shard_shapes(ctx, K)
    body, = loop_bodies(text).values()
    copies = [ln for ln in body
              if re.search(r" = (\S+?)(\{\S*)? copy\(", ln)
              and re.search(r" = (f32\[[\d,]+\])", ln).group(1) in shapes]
    # x/4: a round is one coalesced collective each way
    groups = sum(" collective-permute(" in ln for ln in body) // 2
    assert groups == want[0] == 2
    assert len(copies) <= COPIES_A_GROUP[case] * groups, copies
    # the same arrays do ride the carry: the shapes are the right ones
    assert any(shape in ln for ln in body for shape in shapes)


def test_an_evicted_slot_handed_over_as_nan_shows_nowhere(monkeypatch):
    """awp's six stresses take their new level in the slot the ring
    gives up; the kernel starts no DMA from that slot.  With the slot
    NaN throughout when the launch gets it, the result is the
    unpoisoned run's to the bit: every cell of the re-used array was
    written, re-zeroed or refreshed before anything read it."""
    from poison import poison_reused_slots
    (stencil, radius, K, domain, ranks, n), want = CASES["awp_abc-k1-x4-odd"]
    clean = make(stencil, radius, K, domain, ranks)
    clean.run_solution(0, n - 1)
    poisoned = poison_reused_slots(monkeypatch)
    dirty = make(stencil, radius, K, domain, ranks)
    dirty.run_solution(0, n - 1)
    assert poisoned and max(poisoned) == want[4] == 6
    assert finite(dirty)
    assert dirty.compare_data(clean, epsilon=0.0, abs_epsilon=0.0) == 0


def test_a_launch_compiled_alone_reuses_nothing():
    """The one-chip launch's inputs are not donated: an aliased one
    would be copied first, at every launch.  Only the shard program's
    whole-shard chunks ask."""
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="awp_abc")
    ctx.apply_command_line_options(
        "-g_x 32 -g_y 16 -g_z 128 -mode pallas -wf_steps 1")
    ctx.prepare_solution()
    ctx.run_solution(0, 1)
    row, = ctx.compiled_plans()
    assert len(row["fetch_skipped"]) == 6 and row["loop"] is None
    assert ctx._built_pallas_tiling()["reused"] == []
