"""``trace_reduce.reduce`` on a hand-made trace whose answers are
worked out in the comments, and on a trace cut from a chip run."""

import json
import os
import sys

import pytest

from bench_util import BENCH

sys.path.insert(0, BENCH)
import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

MS = 1_000_000


def synthetic():
    """Two devices, two calls of two steps each, window 0..100 ms.
    Device 0: kernels 0-30, 40-70 (call 1), 80-95 (call 2, one kernel
    seen); a copy 30-35; a collective-permute in flight 25-38 (hidden
    under the first kernel until 30) whose ``-done`` holds the core
    36-38.  Device 1: one kernel 0-20."""
    k = "closed_call.1_custom-call"
    d0 = [[k, 0, 30 * MS], ["copy.1", 30 * MS, 5 * MS],
          ["collective-permute-done.2", 36 * MS, 2 * MS],
          [k, 40 * MS, 30 * MS],
          ["shard_map.3_tpu_custom_call", 80 * MS, 15 * MS]]
    d1 = [[k, 0, 20 * MS]]
    spans = [["bench.call", 0, 72 * MS], ["bench.call", 78 * MS, 22 * MS]]
    flying = [["collective-permute-start.2", 25 * MS, 13 * MS],
              ["slice-start async-start", 50 * MS, 5 * MS]]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "async": {"/device:TPU:0": flying}, "spans": spans}


def test_busy_idle_kernel_and_collective_time_by_hand():
    out = tr.reduce(synthetic(), steps=4)
    assert out["window_s"] == pytest.approx(0.100)
    # the core of device 0 is busy 0-35, 36-38, 40-70 and 80-95 =
    # 82 ms (what is in flight does not occupy it); device 1: 20 ms
    assert out["busy_s"] == pytest.approx((0.082 + 0.020) / 2)
    assert out["idle_share"] == pytest.approx(18.0)
    # gaps 35-36 and 38-40 (call 1), 70-80 (midpoint 75: between the
    # calls) and 95-100 (call 2)
    assert out["longest_gap_ms"] == pytest.approx(10.0)
    assert out["longest_gap_span"] == "bench.between"
    assert dict(out["breakdown"]["idle_gaps"]) == pytest.approx(
        {"bench.between": 0.010, "bench.call": 0.008})
    # every custom call counts as kernel, whatever wraps it: 75 ms / 4
    assert out["kernel_ms_per_step"] == pytest.approx(75 / 4)
    assert out["collective_ms_per_step"] == pytest.approx(13 / 4)
    # the collective runs 25-38; a kernel covers it until 30
    assert out["exposed_share"] == pytest.approx(8.0)
    # between the two kernels of call 1 (30-40) the copy and the
    # wait keep the core busy but for 35-36 and 38-40
    assert out["call_gap_ms"] == pytest.approx(3.0)
    top = out["breakdown"]["device_ops"]
    assert top[0] == ["closed_call.1_custom-call", pytest.approx(0.060)]
    assert len(top) == 4 and out["request_busy_share"] is None


def test_the_exchange_is_read_on_the_device_that_records_it():
    ev = synthetic()
    ev["devices"]["/device:TPU:1"] = [["closed_call.1_custom-call", 0,
                                       99 * MS]]      # now the busiest
    out = tr.reduce(ev, steps=4)
    assert out["idle_share"] == pytest.approx(1.0)
    assert out["collective_ms_per_step"] == pytest.approx(13 / 4)
    assert out["exposed_share"] == pytest.approx(8.0)


def test_request_spans_give_the_busy_share_of_request_time():
    ev = synthetic()
    ev["spans"] = [["bench.request", 0, 50 * MS],
                   ["bench.request", 60 * MS, 40 * MS]]
    out = tr.reduce(ev, steps=4)
    # busy inside 0-50: 47; inside 60-100: 60-70 and 80-95 = 25
    assert out["request_busy_share"] == pytest.approx(100 * 72 / 90)
    assert out["call_gap_ms"] is None


def test_nothing_to_read_is_nothing():
    assert tr.reduce({"devices": {}, "spans": []}, 1) == {}
    assert tr.reduce({"devices": {"/device:TPU:0": []},
                      "spans": [["bench.call", 0, 10]]}, 1) == {}


def test_op_names_are_cut_from_the_instruction_text():
    kern = ("%chunk.1 = (f32[560,592,640]{2,1,0:T(8,128)}, f32[560,592,"
            "640]{2,1,0:T(8,128)}) custom-call(s32[1]{0:T(128)} %bitcast.1,"
            " f32[560,592,640]{2,1,0:T(8,128)} %state__pressure___0_.1), "
            "custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(kern) == "chunk.1 custom-call"
    assert tr.is_kernel(tr.op_name(kern))
    # an operation that only READS a custom call's result is no kernel
    copy = ("%copy.10 = f32[544,576,512]{2,1,0:T(8,128)} copy(f32[544,576,"
            "512]{2,1,0:T(8,128)} %custom-call.7)")
    assert tr.op_name(copy) == "copy.10 copy"
    assert not tr.is_kernel(tr.op_name(copy))
    perm = ("%collective-permute-start.3 = (f32[8,1024]{1,0}, f32[8,1024]"
            "{1,0}) collective-permute-start(f32[8,1024]{1,0} %x), "
            "source_target_pairs={{0,1}}")
    assert tr.is_collective(tr.op_name(perm))
    assert tr.op_name("bench.call") == "bench.call"


def test_interval_arithmetic():
    assert tr.union([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.clip([[0, 4], [6, 9]], 3, 7) == [[3, 4], [6, 7]]
    assert tr.is_kernel("closed_call.29_custom-call_tpu_custom_call_")
    assert tr.is_collective("collective-permute-done.4")
    assert not tr.is_kernel("copy.60") and not tr.is_collective("pad.1")


def test_a_trace_cut_from_a_chip_run():
    """Two 10-step calls of the flagship on one v5e (my chip run,
    PR 23): ten fused K=2 launches of ~60.4 ms, a 3.6 ms copy of vel
    after each, ~1 ms of idle device between launches."""
    with open(os.path.join(DATA,
                           "iso3dfd_1chip_two_calls.events.json")) as f:
        ev = json.load(f)
    out = tr.reduce(ev, steps=20)
    assert out["devices"] == 1 and not out["has_collectives"]
    assert out["window_s"] == pytest.approx(0.657995, abs=1e-6)
    assert out["busy_s"] == pytest.approx(0.648064, abs=1e-6)
    assert out["idle_share"] == pytest.approx(1.509, abs=1e-3)
    assert out["kernel_ms_per_step"] == pytest.approx(30.1865, abs=1e-3)
    assert out["call_gap_ms"] == pytest.approx(1.079, abs=1e-3)
    assert out["longest_gap_span"] == "bench.call"
    top = out["breakdown"]["device_ops"]
    assert top[0][0] == "chunk.1 custom-call"
    assert top[1][0] == "copy.10 copy"
    # the kernel is nine tenths of the busy time and no share passes 1
    assert 0.9 < top[0][1] / out["busy_s"] < 1.0
    need_s = 10.0 * 640 ** 3 / 819e9            # 10 B per point and step
    assert 100 * need_s / (out["kernel_ms_per_step"] / 1e3) == \
        pytest.approx(10.60, abs=0.01)


def test_a_four_chip_trace_cut_from_a_chip_run():
    """One 10-step call of the four-chip cell, devices 0 and 1 (my
    chip run, PR 23): a core kernel (``closed_call.29``), a shell
    kernel wrapped by ``shard_map`` and two small ones all count as
    kernel time; the halo exchange is in flight under the core kernel
    (``Async XLA Ops``) and the core waits for it ~1.7 ms a step."""
    with open(os.path.join(DATA,
                           "iso3dfd_4chip_one_call.events.json")) as f:
        ev = json.load(f)
    out = tr.reduce(ev, steps=10)
    assert out["devices"] == 2 and out["has_collectives"]
    assert out["kernel_ms_per_step"] == pytest.approx(66.026, abs=1e-2)
    assert out["collective_ms_per_step"] == pytest.approx(49.63, abs=1e-2)
    assert out["exposed_share"] == pytest.approx(4.37, abs=1e-2)
    assert out["idle_share"] == pytest.approx(0.55, abs=1e-2)
    assert out["busy_s"] < out["window_s"]
    names = [n for n, _s in out["breakdown"]["device_ops"]]
    assert names[:2] == ["closed_call.29 custom-call",
                         "shard_map.46 custom-call"]
    assert not any(n.endswith(" while") for n in names)
    # every custom call is kernel time, whatever wraps it
    kern = sum(s for n, s in out["breakdown"]["device_ops"]
               if tr.is_kernel(n))
    assert kern / 10 * 1e3 == pytest.approx(out["kernel_ms_per_step"],
                                            rel=1e-6)
