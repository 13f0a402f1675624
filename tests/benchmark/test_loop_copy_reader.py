"""``parallel.loop_copy_ms_per_step`` on a hand-made event list whose
answer is worked out in the comments, its place in the manifest, and
what it says where there is nothing to read."""

import os
import sys
import types

import pytest

from bench_util import BENCH, manifest

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

NAME = "parallel.loop_copy_ms_per_step"
FOUR_CHIP = ["iso3dfd-r8-4chip.advance", "awp-abc-r2-4chip.advance",
             "iso3dfd-r8-4chip-2x2.advance"]
MS = 1_000_000
MOD = "yt_shard_pallas"


def ev(name, a, b, **stats):
    return [name, a * MS, (b - a) * MS, "python3", stats]


def op(name, a, b, label=""):
    return [name, a * MS, (b - a) * MS, label]


def events():
    """One traced 100 ms call of a shard program on two devices.  On
    the busy one: a kernel, two carry copies of 3 ms each, an unpack's
    copy under a scope (not counted), a copy that began before the
    slice (1 ms of it inside) and one in a module the program did not
    name a shard's (not counted): 3 + 3 + 1 = 7 ms."""
    kc = "yt_awp_abc_r4_k1.9 custom-call"
    spans = [ev("bench.call", 10, 110),
             ev("yt.run.call", 11, 109, mode="shard_pallas", n=10)]
    d0 = [op("copy.2 copy", 8, 11),                 # 1 ms inside
          op("while.1 while", 12, 100),             # spans its body's
          op(kc, 12, 40, "yt_awp_abc_r4_k1"),
          op("copy.275 copy", 40, 43), op("copy.351 copy", 43, 46),
          op("copy.9 copy", 46, 48),                # scoped: an unpack's
          op("fusion.3 fusion", 48, 60),
          op("copy.7 copy", 104, 106)]              # another module's
    mods = [[MOD, 8 * MS, 93 * MS], ["yt_xla_chunk", 103 * MS, 4 * MS]]
    return {"spans": spans,
            "devices": {"/device:TPU:0": d0,
                        "/device:TPU:1": [op("copy.275 copy", 40, 70)]},
            "modules": {"/device:TPU:0": mods,
                        "/device:TPU:1": [[MOD, 8 * MS, 93 * MS]]}}


SCOPES = {MOD: {"copy.9": "yt_exchange_unpack_x",
                "fusion.3": "yt_zero_pads"}}


@pytest.fixture(scope="module")
def reader():
    return bench_run.load_module("metrics", NAME)


def test_unscoped_copies_of_the_shard_module_on_the_busiest_device(reader):
    assert reader.copy_ms(events(), SCOPES) == pytest.approx(7.0)
    # the busiest device is the one with the kernel (92 ms against 30)
    ev2 = events()
    ev2["devices"]["/device:TPU:1"] = [op("copy.275 copy", 10, 109)]
    assert reader.copy_ms(ev2, SCOPES) == pytest.approx(99.0)


def test_nothing_to_read_is_none_not_zero(reader):
    ev0 = events()
    ev0["modules"] = {}                     # a CPU dry run: no module line
    assert reader.copy_ms(ev0, SCOPES) is None
    ev1 = events()
    ev1["modules"] = {"/device:TPU:0": [["yt_cube_r1_k4", 8 * MS, 93 * MS]]}
    assert reader.copy_ms(ev1, SCOPES) is None
    assert reader.copy_ms({"spans": [], "devices": {}, "modules": {}},
                          SCOPES) is None
    # a copy-free shard program reads 0.0: the guard's resting value
    ev3 = events()
    ev3["devices"]["/device:TPU:0"] = [
        o for o in ev3["devices"]["/device:TPU:0"]
        if not o[0].endswith(" copy")]
    assert reader.copy_ms(ev3, SCOPES) == 0.0
    # no trace directory at all
    run = types.SimpleNamespace(
        program_spans={}, cell=types.SimpleNamespace(
            scratch=os.path.join(BENCH, "no-such-dir"), tiny=False))
    assert reader.read(run) is None


def test_the_metric_is_listed_for_the_three_four_chip_cells():
    m = manifest()
    entry = m["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "parallel",
                     "moves": "gpts_per_s", "workloads": FOUR_CHIP}
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    assert four == FOUR_CHIP
