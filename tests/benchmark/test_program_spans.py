"""``program_spans.reduce`` and the eleven readers on hand-made event
lists whose answers are worked out in the comments, and on an event
list recorded on the chip."""

import json
import os
import sys
import types

import pytest

from bench_util import BENCH

sys.path.insert(0, BENCH)
import program_spans as ps  # noqa: E402
import run as bench_run  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
MAIN, WORKER = "python3", "yt-serve-worker"


def ev(name, a, b, thread=MAIN, **stats):
    return [name, a * MS, (b - a) * MS, thread, stats]


def op(name, a, b, label=""):
    return [name, a * MS, (b - a) * MS, label]


def advance():
    """Two 100 ms calls on one busy device (and one nearly idle).
    Call A: two K=4 launches, then a 2-step remainder; call B: two K=4
    launches, no remainder, and a 1.5 ms gap between its kernels."""
    k, kc = "yt_cube_r1_k4", "yt_cube_r1_k4.1 custom-call"
    spans = [
        ev("bench.call", 0, 100), ev("bench.call", 100, 200),
        ev("yt.run.call", 1, 99, mode="pallas", first=0, n=10),
        ev("yt.run.launch", 2, 12, k=4), ev("yt.run.launch", 12, 20, k=4),
        ev("yt.run.wait", 20, 60),
        ev("yt.run.remainder", 60, 98, n=2),
        ev("yt.run.launch", 61, 63, k=2), ev("yt.run.wait", 63, 97),
        ev("yt.run.call", 101, 199, mode="pallas", first=10, n=8),
        ev("yt.run.launch", 102, 110, k=4),
        ev("yt.run.launch", 110, 120, k=4),
        ev("yt.run.wait", 120, 198)]
    d0 = [op(kc, 3, 28, k), op("copy.1 copy", 28, 30),
          op(kc, 30, 55, k), op("copy.1 copy", 55, 57),
          op("while.1 while", 62, 96),           # spans the two below
          op("add_multiply_fusion.2 fusion", 62, 90),
          op("copy.8 copy", 90, 96),
          op(kc, 103, 128, k), op("copy.1 copy", 128, 130),
          op(kc, 131.5, 156.5, k), op("copy.1 copy", 156.5, 158.5)]
    mods = [[k, 3 * MS, 27 * MS], [k, 30 * MS, 27 * MS],
            ["yt_xla_chunk", 62 * MS, 34 * MS],
            [k, 103 * MS, 27 * MS], [k, 131.5 * MS, 27 * MS]]
    return {"spans": spans,
            "devices": {"/device:TPU:0": d0,
                        "/device:TPU:1": [op(kc, 3, 20, k)]},
            "modules": {"/device:TPU:0": mods}}


def served():
    """Three requests of a second each; the worker's spans on their
    own thread; the device works 30, 20 and 30 ms inside the chunks."""
    spans, ops = [], []
    for i, (snap, run, resp, busy) in enumerate(
            [(300, 50, 630, 30), (400, 40, 540, 20), (350, 60, 570, 30)]):
        t, rid = 1000 * i, f"r{i:06d}"
        spans += [
            ev("bench.request", t, t + 1000),
            ev("yt.serve.request", t + 1, t + 999, rid=rid),
            ev("yt.serve.snapshot", t + 10, t + 10 + snap, WORKER,
               rid=rid, bytes=2 ** 30),
            ev("yt.serve.chunk", t + 10 + snap, t + 10 + snap + run,
               WORKER, rid=rid),
            ev("yt.run.call", t + 11 + snap, t + 9 + snap + run, WORKER,
               mode="pallas", n=16),
            ev("yt.serve.respond", t + 10 + snap + run, t + 990, WORKER,
               rid=rid),
            ev("yt.serve.sanity", t + 800, t + 900, WORKER, rid=rid),
            ev("yt.serve.journal", t + 900, t + 905, WORKER, rid=rid)]
        ops.append(op("yt_iso3dfd_r8_k2.1 custom-call", t + 15 + snap,
                      t + 15 + snap + busy, "yt_iso3dfd_r8_k2"))
    return {"spans": spans, "devices": {"/device:TPU:0": ops},
            "modules": {}}


def four_chips():
    """One 10-step call of the shard program: the whole-shard chunk of
    the first group, then a core and two shell kernels; a pad and a
    merge fusion that the executable's text puts under named scopes,
    and a copy that has only its module's name."""
    k = "yt_iso3dfd_r8_k2"
    ops = [op(f"{k}.1 custom-call", 0, 10, k),
           op(f"{k}_core.7 custom-call", 12, 42, k + "_core"),
           op(f"{k}_shell.14 custom-call", 42, 46, k + "_shell"),
           op(f"{k}_shell.15 custom-call", 46, 50, k + "_shell"),
           op("pad.140 pad", 50, 52),
           op("copy.61 copy", 52, 55),
           op("slice_dynamic-update-slice_fusion.17 fusion", 55, 56)]
    return {"spans": [ev("bench.call", 0, 100),
                      ev("yt.run.call", 0, 100, mode="shard_pallas",
                         n=10),
                      ev("yt.run.launch", 1, 3, k=10),
                      ev("yt.run.wait", 3, 99)],
            "devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [
                ["yt_shard_pallas", 0, 95 * MS]]},
            "scopes": ps.scope_map([SHARD_HLO])}


#: what ``Compiled.as_text()`` prints, cut to the lines that matter
SHARD_HLO = """\
HloModule jit_yt_shard_pallas, is_scheduled=true, entry_computation_layout={(f32[256]{0:T(8,128)})->f32[256]{0}}

%fused_computation.52 (p: f32[304]) -> f32[304] {
  %p = f32[304]{0} parameter(0)
  ROOT %dus.1 = f32[304]{0} dynamic-update-slice(%p, %p), metadata={op_name="jit(yt_shard_pallas)/shard_map/yt_exchange_unpack/dynamic_update_slice" source_file="shard_step.py" source_line=88}
}

ENTRY %main.9 (a: f32[256]) -> f32[304] {
  %constant.1 = f32[]{:T(128)} constant(0), metadata={op_name="jit(yt_shard_pallas)/shard_map"}
  %pad.140 = f32[304]{0:T(8,128)} pad(%a, %constant.1), padding=24_24, metadata={op_name="jit(yt_shard_pallas)/shard_map/yt_shard_pad/jit(_pad)/pad"}
  %copy.61 = f32[304]{0:T(8,128)} copy(%pad.140)
  %yt_iso3dfd_r8_k2_core.7 = (f32[304]{0}) custom-call(%copy.61), custom_call_target="tpu_custom_call", metadata={op_name="jit(yt_shard_pallas)/shard_map/while/body/closed_call/yt_iso3dfd_r8_k2_core/pallas_call"}
  ROOT %slice_dynamic-update-slice_fusion.17 = f32[304]{0} fusion(%copy.61), kind=kLoop, calls=%fused_computation.52, metadata={op_name="jit(yt_shard_pallas)/shard_map/while/body/closed_call/yt_shell_merge/scatter"}
}
"""


def two_clients():
    """Two clients whose requests overlap in time and share one batch:
    the worker snapshots a then b, runs one chunk for both, answers a,
    then b.  b's interval holds every span of a's but the first."""
    spans = [
        ev("bench.request", 0, 1000, "client#1"),
        ev("bench.request", 100, 1900, "client#2"),
        ev("yt.serve.request", 1, 999, "client#1", rid="a"),
        ev("yt.serve.request", 101, 1899, "client#2", rid="b"),
        ev("yt.serve.snapshot", 110, 410, WORKER, rid="a"),
        ev("yt.serve.snapshot", 410, 810, WORKER, rid="b"),
        ev("yt.serve.chunk", 810, 860, WORKER, rid="a", rids="a,b",
           batch=2),
        ev("yt.run.call", 811, 859, WORKER, mode="pallas", n=16),
        ev("yt.serve.respond", 860, 990, WORKER, rid="a"),
        ev("yt.serve.respond", 990, 1890, WORKER, rid="b")]
    return {"spans": spans, "modules": {}, "devices": {
        "/device:TPU:0": [op("yt_iso3dfd_r8_k2.1 custom-call", 815, 845,
                             "yt_iso3dfd_r8_k2")]}}


def straddling():
    """A window of one unit: a call that began before it (warm-up's
    last) is no whole call; a launch on another thread belongs to no
    call here; a jit call's launches advance no fused step."""
    k = "yt_cube_r1_k4"
    spans = [
        ev("bench.call", 100, 200),
        ev("yt.run.call", 50, 150, mode="pallas", n=8),
        ev("yt.run.launch", 51, 91, k=4),
        ev("yt.run.call", 101, 199, "other#2", mode="jit", n=8),
        ev("yt.run.launch", 102, 110, "other#2", k=8),
        ev("yt.run.call", 101, 199, mode="pallas", n=8),
        ev("yt.run.launch", 102, 105, k=4),
        ev("yt.run.launch", 105, 107, k=4),
        ev("yt.run.wait", 107, 198)]
    return {"spans": spans, "modules": {}, "devices": {
        "/device:TPU:0": [op(f"{k}.1 custom-call", 110, 150, k),
                          op(f"{k}.1 custom-call", 150, 190, k)]}}


def reader(name, events, **kind):
    """``metrics/<name>.py`` read on a run that holds ``events``."""
    run = types.SimpleNamespace(
        program_spans=ps.reduce(events) if events else {},
        cell=types.SimpleNamespace(kind=types.SimpleNamespace(**kind)))
    return bench_run.load_module("metrics", name).read(run)


# what each reader must give on the lists above, worked out by hand
BY_HAND = [
    # A: launches 10 + 8 + 2 (the remainder's own counts); B: 8 + 10
    ("runtime.enqueue_ms_per_call", advance, 19.0),
    # A: remainder 38 of a 98 ms call; B: none, so 0; the median
    ("runtime.remainder_share", advance, 100 * 38 / 98 / 2),
    # four kernels of 25 ms on device 0 over 16 fused steps: the
    # remainder's 2 steps and its fusion are in neither, nor is the
    # nearly idle device 1
    ("kernel.fused_ms_per_step", advance, 100 / 16),
    # idle 0-3, 57-62, 96-103, 158.5-200 = 56.5 ms (130-131.5 is under
    # 2 ms and left out); launch/wait/remainder cover 2-98 and
    # 102-198, so 0-2, 98-102 and 198-200 = 8 ms are unaccounted for
    ("device.idle_unspanned_share", advance, 100 * 8 / 56.5),
    # medians of (300, 400, 350), (50, 40, 60), (630, 540, 570)
    ("serve.snapshot_s", served, 0.35),
    ("serve.run_s", served, 0.05),
    ("serve.respond_s", served, 0.57),
    # busy 80 of 3000 ms; snapshot/chunk/respond cover 10-990 of each
    # second, so 10 + 20 + 20 + 10 = 60 ms of the 2920 idle are not
    ("device.idle_unspanned_share.serve", served, 100 * 60 / 2920),
    # whole 10 + core 30 + shells 4 + 4 over 10 steps; the pad is no
    # kernel
    ("kernel.fused_ms_per_step", four_chips, 4.8),
    ("parallel.shell_ms_per_step", four_chips, 0.8),
    # the pad (2 ms) and the merge fusion (1 ms) are under scopes, the
    # copy (3 ms) is under none: 3 ms over 10 steps
    ("parallel.pack_ms_per_step", four_chips, 0.3),
    # by rid: a 300/50/130, b 400/50/900 (the chunk is both's); by
    # time alone b would be given a's respond and a b's snapshot
    ("serve.snapshot_s", two_clients, 0.35),
    ("serve.run_s", two_clients, 0.05),
    ("serve.respond_s", two_clients, 0.515),
    # whole calls inside the window: the pallas call's 3 + 2 ms on the
    # main thread, the jit call's 8 ms on the other
    ("runtime.enqueue_ms_per_call", straddling, 6.5),
    # 80 ms of kernels over the one pallas call's 8 fused steps
    ("kernel.fused_ms_per_step", straddling, 10.0),
]


@pytest.mark.parametrize("name,events,want", BY_HAND,
                         ids=[f"{n}-{e.__name__}" for n, e, _w in BY_HAND])
def test_reader_by_hand(name, events, want):
    assert reader(name, events()) == pytest.approx(want)


def test_a_call_without_remainder_reads_zero_and_no_shell_reads_none():
    events = advance()
    events["spans"] = [s for s in events["spans"] if s[1] >= 100 * MS]
    assert reader("runtime.remainder_share", events) == 0.0
    assert reader("parallel.shell_ms_per_step", events) is None


def test_labels_fall_back_to_the_module_and_keep_kernels_apart():
    by = ps.reduce(advance())["by_label_ms"]
    assert by == pytest.approx({
        "yt_cube_r1_k4": 100.0,          # the custom calls alone
        "yt_cube_r1_k4 copy": 8.0,       # copies in the kernel's module
        "yt_xla_chunk": 34.0})           # the loop's body, not the loop
    events = four_chips()
    by = ps.reduce(events)["by_label_ms"]
    assert by["yt_shard_pallas"] == pytest.approx(3.0)      # the copy
    assert by["yt_shard_pad"] == pytest.approx(2.0)
    assert by["yt_shell_merge"] == pytest.approx(1.0)
    # a program that offers no HLO text: all three go by the module
    del events["scopes"]
    assert ps.reduce(events)["by_label_ms"]["yt_shard_pallas"] \
        == pytest.approx(6.0)
    assert reader("parallel.pack_ms_per_step", events) is None
    assert ps.kernel_of("yt_cube_r1_k4.1 custom-call") == "yt_cube_r1_k4"
    assert ps.kernel_of("yt_iso3dfd_r8_k2_shell.15 custom-call") \
        == "yt_iso3dfd_r8_k2_shell"
    assert ps.kernel_of("copy.1 copy") == ""
    assert ps.kernel_of("cholesky.3 custom-call") == ""
    assert ps.module_of("jit_yt_xla_chunk(123)") == "yt_xla_chunk"
    assert ps.module_of("jit_convert_element_type(7)") == ""


def test_scopes_are_joined_on_from_the_executables_text():
    assert ps.scope_map([SHARD_HLO]) == {"yt_shard_pallas": {
        "dus.1": "yt_exchange_unpack", "pad.140": "yt_shard_pad",
        "slice_dynamic-update-slice_fusion.17": "yt_shell_merge"}}
    # a second executable of the same module name that disagrees
    other = SHARD_HLO.replace("yt_shard_pad/", "yt_zero_pads/")
    both = ps.scope_map([SHARD_HLO, other])["yt_shard_pallas"]
    assert both["pad.140"] == "" and both["dus.1"] == "yt_exchange_unpack"

    def run(**kind):
        return types.SimpleNamespace(cell=types.SimpleNamespace(
            kind=types.SimpleNamespace(**kind)))

    def refuses():
        raise RuntimeError("no text kept")
    ctx = types.SimpleNamespace(compiled_texts=lambda: [SHARD_HLO])
    assert ps.compiled_texts(run(ctx=ctx)) == [SHARD_HLO]
    assert ps.compiled_texts(run(ctx=types.SimpleNamespace())) == []
    assert ps.compiled_texts(run()) == []
    assert ps.compiled_texts(run(ctx=types.SimpleNamespace(
        compiled_texts=refuses))) == []


def test_the_jit_call_and_the_straddling_call_advance_no_fused_step():
    out = ps.reduce(straddling())
    assert out["fused_steps"] == 8 and out["steps"] == 16


def test_d2h_per_request_from_the_servers_counters():
    def srv(counters):
        return types.SimpleNamespace(obs=types.SimpleNamespace(
            snapshot=lambda: {"counters": counters}))
    name = "serve.d2h_gib_per_request"
    assert reader(name, None, srv=srv(
        {"serve.d2h_bytes": 5 * 2 ** 30, "serve.requests.ok": 3,
         "serve.requests.anomaly": 1})) == pytest.approx(1.25)
    # an older program keeps no such counter; a direct cell no server
    assert reader(name, None, srv=srv({"serve.requests.ok": 3})) is None
    assert reader(name, None) is None


@pytest.mark.parametrize("name", sorted({n for n, _e, _w in BY_HAND}))
def test_a_program_without_spans_gives_nothing_and_raises_nothing(name):
    """The parent of this PR under these files: ``bench.*`` spans and
    device operations, no ``yt.*`` event, no module of the program's."""
    events = advance()
    events["spans"] = [s for s in events["spans"]
                       if s[0].startswith("bench.")]
    assert ps.reduce(events) == {}
    assert reader(name, events) is None


def test_load_finds_no_trace_and_memoises(tmp_path):
    run = types.SimpleNamespace(cell=types.SimpleNamespace(
        scratch=str(tmp_path), tiny=True))
    assert ps.load(run) == {} and run.program_spans == {}
    run.program_spans = {"x": 1}
    assert ps.load(run) == {"x": 1}


def recorded(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_recorded_cube_calls_split_kernel_and_remainder_by_name():
    """Three 10-step calls of the cube cell on a v5e (K=4: two fused
    launches and a 2-step remainder each), as the chip run printed
    them; the named parts account for the device's busy time."""
    events = recorded("cube_1chip_three_calls.program.json")
    out = ps.reduce(events)
    assert out["fused_steps"] == 24 and out["steps"] == 30
    assert out["remainder_share"] == pytest.approx(56.24986608)
    assert out["enqueue_ms_per_call"] == pytest.approx(129.10678)
    assert out["fused_ms_per_step"] == pytest.approx(22.53276542)
    assert out["idle_unspanned_share"] == pytest.approx(0.53750574)
    assert out.get("shell_ms_per_step") is None
    by = out["by_label_ms"]
    # the scopes come from the executables' text: the pad fusions beside
    # the kernel, and the step inside the remainder's loop (its copies
    # and ring updates are under no scope and go by their module)
    assert set(by) == {"yt_cube_r1_k4", "yt_zero_pads", "yt_xla_step",
                       "yt_xla_chunk"}
    assert by["yt_zero_pads"] == pytest.approx(4.510618)
    # kernel time + the remainder's module = the busy time but for the
    # pad fusions: 1275.74 ms
    busy = ps.tr.total(ps.tr.union(
        [s, s + d] for _n, s, d, _l in events["devices"]["/device:TPU:0"]
        if s + d > 0))
    named = (out["fused_ms_per_step"] * 24 + by["yt_xla_step"]
             + by["yt_xla_chunk"])
    assert named == pytest.approx(1271.234, abs=0.01)
    assert 0.95 < named * 1e6 / busy <= 1.0
    # without the text (an older program) the same time goes by module
    del events["scopes"]
    by = ps.reduce(events)["by_label_ms"]
    assert set(by) == {"yt_cube_r1_k4", "yt_cube_r1_k4 fusion",
                       "yt_xla_chunk"}
    assert by["yt_xla_chunk"] == pytest.approx(602.811939 + 127.635448)


def test_recorded_served_requests_split_by_phase():
    """Three 16-step requests of the served cell on a v5e: the three
    phases, found by ``rid`` on the worker's thread, cover the request
    the client's thread timed (``bench.request``) to within a tenth."""
    events = recorded("served_1chip_three_requests.program.json")
    out = ps.reduce(events)
    assert out["serve_snapshot_s"] == pytest.approx(1.128319534)
    assert out["serve_run_s"] == pytest.approx(0.141782068)
    assert out["serve_respond_s"] == pytest.approx(1.953991673)
    phases = (out["serve_snapshot_s"] + out["serve_run_s"]
              + out["serve_respond_s"])
    timed = ps.median([s[2] for s in events["spans"]
                       if s[0] == "bench.request"]) / 1e9
    assert phases == pytest.approx(timed, rel=0.10)
    assert len({s[3] for s in events["spans"]}) == 2    # two threads
    assert out["idle_unspanned_share"] < 10
    assert out["fused_steps"] == 48 and out["remainder_share"] == 0.0
