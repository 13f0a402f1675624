"""The cell ``lbm-d3q19-ldc-4chip.advance``: the one-chip cell's solver
(``lbm_d3q19``, its constants, its reference) at 512^3 over four chips,
x split, through the Pallas shard program that sends each population
only across the face it crosses.  Its configuration names the public
multi-rank run it stands for in a source of its own, lists every value
it sets and reduces nothing; the cell is listed where awp's is and
where the one-chip cell's kernel shares are, no closed list touched;
its ``--tiny --trace 1`` run on four host devices is ``correct`` in all
57 numbers with the seam probe across a shard face, sends 11 slabs a
step and reads every byte it sends; the bfloat16 control is not
correct; the reader that came with it, ``parallel.exchange_read_share``,
gives 1.0, 0.26 and ``None`` on hand-made launch spans; and ``omega``
is the highest of the one-chip rule's values that stays finite on a box
that lays the seeded pattern as 512^3 lays it."""

import json
import os
import sys
import types

import pytest

from bench_util import BENCH, NOT_ON_CPU, ROOT, manifest, result_line, \
    run_cell
from test_manifest import cells_keep_the_rules

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

CELL = "lbm-d3q19-ldc-4chip.advance"
CONFIG = "lbm-d3q19-ldc-4chip"
ONE_CHIP = "lbm-d3q19-ldc-1chip"
AWP = "awp-abc-r2-4chip.advance"
NEW_METRIC = "parallel.exchange_read_share"
#: awp's lists that an accepted test, or the issue, closes
CLOSED = {"runtime.slow_call_share", "runtime.slow_call_worst_ms",
          "kernel.fetch_gib_per_step"}
#: the one-chip cell's kernel shares, which awp's cell does not report
KERNEL_SHARES = {"kernel.hbm_moved_share", "kernel.dag_gops_per_s"}


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


CFG = config(CONFIG)
with open(os.path.join(BENCH, "traffic", "advance.json")) as _f:
    TRAFFIC = json.load(_f)
STEPS = int(TRAFFIC["steps_per_call"])


def test_the_configuration_is_the_one_chip_solver_over_four_chips():
    one = config(ONE_CHIP)
    assert (CFG["stencil"], CFG["radius"], CFG["dtype"]) \
        == ("lbm_d3q19", None, "float32")
    assert (CFG["mode"], CFG["ranks"], CFG["chips"], CFG["wf_steps"]) \
        == ("shard_pallas", [4, 1, 1], 4, 1)
    assert CFG["domain"] in ([512, 512, 512], [512, 384, 512],
                             [512, 256, 512])
    assert CFG["reduced"] == [] and CFG["tolerance"] == 1e-4
    # the constants, masks and lid are the one-chip configuration's
    assert CFG["consts"] == one["consts"]
    assert CFG["consts"]["omega"] in (1.8, 1.5, 1.2, 1.0)
    assert CFG["probe_block"] == one["probe_block"]
    # the dry run keeps z whole (accel's layers) and splits x four ways
    assert CFG["tiny_domain"][2] == CFG["domain"][2]
    assert CFG["tiny_domain"][0] % 4 == 0
    # a source of its own, the multi-rank run and the law it keeps
    assert len(CFG["source"]) <= 200 and "\n" not in CFG["source"]
    for part in ("waLBerla", "UniformGridGPU", "D3Q19", "lid-driven",
                 "one block a GPU", "by face", "470.lbm", "519.lbm_r"):
        assert part in CFG["source"], part
    rows = manifest()["configs"]
    row, = [c for c in rows if c["name"] == CONFIG]
    assert row["source"] == CFG["source"] and row["reduced"] == []
    assert [c["source"] for c in rows].count(row["source"]) == 1
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert os.path.isfile(os.path.join(ROOT, row["file"]))
    # everything recalled and everything set here is listed
    assumed = CFG["assumed"]
    assert {"what", "block a device", "domain", "omega u_lid_x u_lid_y",
            "masks", "initial state", "state", "tolerance"} <= set(assumed)
    assert sum("as recalled" in v for v in assumed.values()) >= 5
    assert not any("TO BE FILLED" in v for v in assumed.values())
    # the rung it runs at, the compiler's refusal above it, the state a
    # chip holds and the program's own temporaries
    for part in ("x".join(map(str, CFG["domain"])).replace("x", " x "),
                 "RESOURCE_EXHAUSTED", "of 15.75G hbm", "GiB a chip",
                 "program_temp_gib"):
        assert part in assumed["domain"], part
    # the guarantees: the one-chip cell's, and the exchange's
    assert CFG["guarantees"].startswith(one["guarantees"])
    for part in ("received before the step that reads it",
                 "sent only across faces it crosses",
                 "less often or less wide"):
        assert part in CFG["guarantees"], part
    assert {"what", "pull", "arrays", "float32", "z fastest", "exchange"} \
        <= set(CFG["departures"])
    # no second copy of the reference: the stencil's file serves both
    assert os.path.isfile(os.path.join(BENCH, "stencils",
                                       CFG["stencil"] + ".py"))


def test_the_cell_is_listed_where_its_readers_find_something():
    m = manifest()
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (4, "advance", CONFIG)
    assert (TRAFFIC["kind"], STEPS) == ("advance", 10)
    lists = {p["name"]: p.get("workloads") for p in m["per_layer"]}
    like = {n for n, ws in lists.items() if ws and AWP in ws}
    mine = {n for n, ws in lists.items() if ws and CELL in ws}
    assert mine == (like - CLOSED) | KERNEL_SHARES | {NEW_METRIC}
    assert "parallel.shell_ms_per_step" not in mine     # K=1: no shells
    assert lists[NEW_METRIC] == [CELL]
    entry, = [p for p in m["per_layer"] if p["name"] == NEW_METRIC]
    assert entry == {"name": NEW_METRIC, "unit": "share",
                     "better": "higher", "source": "program_span",
                     "layer": "parallel", "moves": "gpts_per_s",
                     "workloads": [CELL]}
    assert os.path.isfile(os.path.join(BENCH, "metrics",
                                       NEW_METRIC + ".py"))
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "gpts_per_s")["workloads"]
    # every list that holds it is in the manifest's own order of cells,
    # whatever cells come after this one
    order = [w["name"] for w in m["workloads"]]
    for ws in list(lists.values()) + [
            e.get("workloads") for e in m["end_to_end"]]:
        if ws and CELL in ws:
            assert ws == [n for n in order if n in ws]


def test_the_manifest_keeps_its_rules_with_the_cell_in_it():
    m = manifest()
    cells_keep_the_rules(m)
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(m["workloads"]) // 2)
    assert len(m["configs"]) <= 24 and len(m["workloads"]) <= 24
    assert len(next(w for w in m["workloads"]
                    if w["name"] == CELL)["why"]) <= 200


@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, devices=4, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct_in_57_numbers_across_a_face(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert {ln.split(" check ")[1].split(" at ")[0] for ln in said} \
        == {f"{p} f{i}" for p in ("corner", "far", "seam")
            for i in range(19)}
    assert len(said) == 57
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and f"after {STEPS} steps" in ln for ln in said)
    assert max(float(ln.split(" error ")[1].split()[0]) for ln in said) \
        <= CFG["tolerance"] / 10
    # the seam probe's block lies across a face between two shards
    seam = next(ln for ln in said if " seam f3 " in ln)
    origin = json.loads(seam.split(" at ")[1].split(" after ")[0])
    shard = CFG["tiny_domain"][0] // CFG["ranks"][0]
    assert origin[0] % shard + CFG["probe_block"] > shard
    plan, = [ln for ln in r.stdout.splitlines() if " plan: " in ln]
    plan = json.loads(plan.split("plan: ", 1)[1])
    assert plan["fuse_steps"] == 1 and plan["interpret"]
    assert plan["overlap_exchange"] is False        # K=1: nothing to hide
    assert res["metrics"]["compile.in_window"]["value"] == 0


def test_the_traced_tiny_run_sends_eleven_slabs_a_step_all_of_them_read(
        traced):
    """Span attrs are on the CPU's host plane too: both slots of the
    ten populations that cross x once, then their newest slot after
    each step but the last, one row each and one way."""
    _r, res = traced
    got = res["metrics"]
    listed = [p["name"] for p in manifest()["per_layer"]
              if "workloads" not in p or CELL in p["workloads"]]
    assert NEW_METRIC in listed
    assert got["parallel.slabs_per_step"]["value"] \
        == pytest.approx((20 + (STEPS - 1) * 10) / STEPS, rel=1e-12) \
        == 11.0
    assert got[NEW_METRIC] == {"value": 1.0, "unit": "share"}
    assert got["parallel.exchange_mib_per_step"]["value"] > 0
    # the rest is what a CPU run can read of the cell's lists
    readable = {n for n in listed if not n.startswith(NOT_ON_CPU)}
    assert readable <= set(got)


def test_the_bf16_control_is_not_correct():
    r = run_cell(CELL, "--control", devices=4)
    assert r.returncode == 0, r.stdout + r.stderr
    assert result_line(r.stdout)["correct"] is False
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 57 and all("control(bf16)" in ln for ln in said)
    assert min(float(ln.split(" error ")[1].split()[0]) for ln in said) \
        > 10 * CFG["tolerance"]


# -- the reader, on hand-made spans --------------------------------------

@pytest.fixture(scope="module")
def reader():
    return bench_run.load_module("metrics", NEW_METRIC)


def a_run(attrs, calls=3):
    """A stand-in traced run: ``calls`` whole ``yt.run.call`` of 10
    steps inside a ``bench.call`` each, one ``yt.run.launch`` in each
    carrying ``attrs`` (spans as ``program_spans.load_xplane`` gives
    them: name, start, duration, thread, stats)."""
    spans = []
    for i in range(calls):
        t = 100.0 * i
        spans += [["bench.call", t, 90.0, "main", {}],
                  ["yt.run.call", t + 1, 80.0, "main", {"n": STEPS}],
                  ["yt.run.launch", t + 2, 5.0, "main", dict(attrs)],
                  ["yt.run.wait", t + 8, 70.0, "main", {}]]
    return types.SimpleNamespace(host_spans=spans)


@pytest.mark.parametrize("attrs,share", [
    # the change's launch at 512^3: 110 slabs, all of them read
    ({"xslabs": 110, "xbytes": 153190400, "xslabs_read": 110,
      "xbytes_read": 153190400}, 1.0),
    # all nineteen both ways, both masks once: 11 of 42 slabs a step
    ({"xslabs": 420, "xbytes": 584908800, "xslabs_read": 110,
      "xbytes_read": 153190400}, 0.2619047619047619),
    # nothing that was sent was asked for
    ({"xslabs": 110, "xbytes": 153190400, "xslabs_read": 0,
      "xbytes_read": 0}, 0.0),
    # the parent's launches say what they send and not what is read
    ({"xslabs": 420, "xbytes": 584908800}, None),
    # a mode that exchanges nothing
    ({"k": 10}, None),
    ({"xslabs": 0, "xbytes": 0, "xslabs_read": 0, "xbytes_read": 0},
     None),
])
def test_the_reader_divides_what_is_read_by_what_is_sent(reader, attrs,
                                                         share):
    got = reader.read(a_run(attrs))
    assert got == (pytest.approx(share, rel=1e-12) if share else share)
    if share:
        assert round(got, 2) in (1.0, 0.26)


def test_the_reader_finds_nothing_without_a_traced_call(reader):
    assert reader.read(types.SimpleNamespace(host_spans=[])) is None
    # a call cut by the window's end is not counted
    run = a_run({"xbytes": 8, "xbytes_read": 8}, calls=1)
    run.host_spans[1][2] = 200.0
    assert reader.read(run) is None


# -- omega ----------------------------------------------------------------

def test_omega_is_the_highest_that_stays_finite_on_the_matched_box(
        monkeypatch):
    """The one-chip rule at this size: 36 x 36 x 104 leaves the
    remainders mod 17 that 512 x 512 x 512 leaves (2, 2, 2), so the
    seeding law lays its 1 : 17 pattern as the cell lays it.  ``lbm.c``'s
    1.95 is non-finite within 300 steps there; 1.8, the first of the
    rule's values, stays finite (10 000 steps by hand, 85 s of jit on
    the CPU; 600 here)."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_lbm_reference as one_chip
    box = (36, 36, 104)
    assert [n % 17 for n in box] == [n % 17 for n in CFG["domain"]]
    monkeypatch.setattr(one_chip, "STABLE_BOX", box)
    assert CFG["consts"]["omega"] == 1.8
    assert "1.95" in CFG["assumed"]["omega u_lid_x u_lid_y"]
    assert "36 x 36 x 104" in CFG["assumed"]["omega u_lid_x u_lid_y"]
    assert not one_chip.finite_after(1.95, 300)
    assert one_chip.finite_after(CFG["consts"]["omega"], 600)
