"""perflab unit tests: ledger schema round-trip, sentinel guard math
(breach → re-measure → noise/regression verdicts), provenance capture on
stubbed /proc//sys roots, the shared roofline model, plus slow-marked
integration rows (perfcheck end-to-end, bf16 interpret-proxy parity)."""

import json
import os

import pytest

from yask_tpu.perflab import (
    append_row, capture_provenance, make_row, read_rows, roofline,
    trailing_median, validate_row,
)
from yask_tpu.perflab.ledger import from_legacy
from yask_tpu.perflab.sentinel import (
    DEFAULT_RULES, GuardRule, check_row, guard_and_append, is_clean,
)


def _prov(load1=0.1, ncpu=8, **kw):
    return {"loadavg": [load1, 0.0, 0.0], "ncpu": ncpu,
            "cpu_model": "TestCPU", "git_sha": "abc1234", **kw}


def _row(value, key="k", guard=None, load1=0.1):
    return make_row(key, value, "GPts/s", "cpu", "test",
                    _prov(load1=load1), guard=guard)


# ---------------------------------------------------------------- ledger

def test_ledger_round_trip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    r1 = make_row("iso jit", 0.11, "GPts/s", "cpu", "test", _prov(),
                  roofline={"hbm_bytes_pp": 21.1, "hbm_gbps": 2.3,
                            "roofline_frac": None},
                  extra={"mode": "jit"})
    append_row(r1, path=path)
    append_row(make_row("iso jit", 0.12, "GPts/s", "tpu", "test",
                        _prov()), path=path)
    rows = read_rows(path)
    assert len(rows) == 2
    back = rows[0]
    assert back["key"] == "iso jit" and back["value"] == 0.11
    assert back["extra"] == {"mode": "jit"}
    assert back["provenance"]["git_sha"] == "abc1234"
    # None roofline entries are dropped, not serialized as null
    assert "roofline_frac" not in back["roofline"]
    validate_row(back)   # raises on schema violation
    # filters
    assert len(read_rows(path, platform="tpu")) == 1
    assert len(read_rows(path, key="iso jit", platform="cpu")) == 1
    assert read_rows(path, sha="abc1234")


def test_ledger_skips_malformed_lines(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    append_row(_row(0.5), path=path)
    with open(path, "a") as f:
        f.write("not json\n[1,2]\n")
    append_row(_row(0.6), path=path)
    assert [r["value"] for r in read_rows(path)] == [0.5, 0.6]


def test_validate_row_flags_missing_fields():
    with pytest.raises(ValueError, match="unit"):
        validate_row({"key": "x", "value": 1.0})
    with pytest.raises(ValueError, match="provenance missing"):
        validate_row(make_row("k", 1.0, "x", "cpu", "test",
                              {"loadavg": [0, 0, 0]}))
    validate_row(_row(1.0))


def test_from_legacy_maps_metric_and_roofline():
    rec = {"metric": "iso3dfd r=8 512^3 fp32 tpu throughput (jit)",
           "value": 31.2, "unit": "GPts/s", "platform": "tpu",
           "hbm_bytes_pp": 21.1, "hbm_roofline": 0.81,
           "vs_baseline": 0.06}
    row = from_legacy(rec, "bench", _prov())
    assert row["key"] == rec["metric"]
    assert row["roofline"]["roofline_frac"] == 0.81
    assert row["extra"]["vs_baseline"] == 0.06
    validate_row(row)


def test_trailing_median_window_and_accept():
    rows = [_row(v) for v in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0)]
    assert trailing_median(rows, n=5) == 4.0
    assert trailing_median(rows, n=3) == 5.0
    assert trailing_median([], n=5) is None
    # accept filter: drop the big values
    assert trailing_median(rows, n=5,
                           accept=lambda r: r["value"] < 4) == 2.0


# -------------------------------------------------------------- sentinel

def _hist(*vals):
    return [_row(v) for v in vals]


def test_guard_ok_within_tolerance():
    v = check_row("k", 0.10, "GPts/s", "cpu", _hist(0.11, 0.12, 0.11))
    assert v["status"] == "ok"
    assert v["baseline"] == 0.11
    assert "trailing-median" in v["rules"]


def test_guard_no_history():
    v = check_row("k", 0.10, "GPts/s", "cpu", [])
    assert v["status"] == "no_history"


def test_guard_unguarded_units_pass_through():
    assert check_row("k", 0.0, "error", "cpu", [])["status"] == "unguarded"
    assert check_row("k", 1.0, "sec", "cpu", [])["status"] == "unguarded"


def test_guard_breach_without_remeasure():
    v = check_row("k", 0.05, "GPts/s", "cpu", _hist(0.11, 0.12, 0.11))
    assert v["status"] == "breach"
    assert v["breached"] == ["trailing-median"]


def test_guard_breach_remeasure_noise_vs_regression():
    hist = _hist(0.11, 0.12, 0.11)
    v = check_row("k", 0.05, "GPts/s", "cpu", hist,
                  remeasure=lambda: 0.115)
    assert v["status"] == "noise"
    assert v["remeasured"] == 0.115
    v = check_row("k", 0.05, "GPts/s", "cpu", hist,
                  remeasure=lambda: 0.052)
    assert v["status"] == "regression"
    # a crashing re-measure still records a regression verdict
    def boom():
        raise RuntimeError("device gone")
    v = check_row("k", 0.05, "GPts/s", "cpu", hist, remeasure=boom)
    assert v["status"] == "regression"
    assert "device gone" in v["remeasure_error"]


def test_guard_dirty_rows_excluded_from_baseline():
    # overloaded-host rows and prior regressions must not set the bar
    hist = _hist(0.11, 0.11)
    hist.append(_row(0.04, load1=99.0))          # load1/ncpu >> 1.5
    hist.append(_row(0.04, guard={"status": "regression"}))
    assert not is_clean(hist[-1])
    assert not is_clean(hist[-2])
    v = check_row("k", 0.10, "GPts/s", "cpu", hist)
    assert v["status"] == "ok" and v["baseline"] == 0.11


def test_guard_absolute_floor_rules():
    # the 128^3 jit headline floor fires even with no history
    key = "iso3dfd r=8 128^3 fp32 cpu throughput (jit)"
    v = check_row(key, 0.02, "GPts/s", "cpu", [])
    assert v["status"] == "breach"
    assert "iso3dfd-128-jit-floor" in v["breached"]
    assert check_row(key, 0.09, "GPts/s", "cpu", [])["status"] == "ok"
    # the cube wavefront floor (the old ad-hoc bench_suite guard)
    cube = "cube 27pt 256^3 tpu wavefront-speedup"
    v = check_row(cube, 1.26, "x", "tpu", [])
    assert v["status"] == "breach"
    assert "cube-wavefront-floor" in v["breached"]
    assert check_row(cube, 1.82, "x", "tpu", [])["status"] == "ok"


def test_guard_rule_direction_lower():
    r = GuardRule(name="t", rel_tol=0.2, direction="lower")
    assert r.breaches(1.3, 1.0)       # 30 % above a lower-is-better base
    assert not r.breaches(1.1, 1.0)
    f = GuardRule(name="t2", floor=2.0, direction="lower")
    assert f.breaches(2.5, None) and not f.breaches(1.5, None)


def test_guard_and_append_full_cycle(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    for v in (0.11, 0.12, 0.11):
        guard_and_append("k", v, "GPts/s", "cpu", "test", _prov(),
                         path=path)
    row = guard_and_append("k", 0.05, "GPts/s", "cpu", "test", _prov(),
                           remeasure=lambda: 0.05, path=path)
    assert row["guard"]["status"] == "regression"
    rows = read_rows(path)
    assert len(rows) == 4 and rows[-1]["guard"]["status"] == "regression"
    # the regression row is dirty: it must not drag the next baseline
    row = guard_and_append("k", 0.11, "GPts/s", "cpu", "test", _prov(),
                           path=path)
    assert row["guard"]["status"] == "ok"
    assert row["guard"]["baseline"] == 0.11


def test_guard_and_append_ignores_bisect_history(tmp_path):
    # perf_bisect replays OLD revisions under the same key; they must
    # not feed the trailing median of current-code rows
    path = str(tmp_path / "ledger.jsonl")
    for v in (0.30, 0.30, 0.30):
        guard_and_append("k", v, "GPts/s", "cpu", "bisect", _prov(),
                         path=path)
    row = guard_and_append("k", 0.11, "GPts/s", "cpu", "test", _prov(),
                           path=path)
    assert row["guard"]["status"] == "no_history"


def test_seed_rows_from_bench_and_fresh_clone_guarding(tmp_path,
                                                       monkeypatch):
    # PERF_LEDGER.jsonl no longer ships in git: a fresh clone seeds its
    # baselines from the committed BENCH_*.json snapshots instead of
    # judging every first measurement as no_history
    from yask_tpu.perflab import ledger as ledger_mod
    root = tmp_path / "root"
    root.mkdir()
    (root / "BENCH_r01.json").write_text(json.dumps(
        {"platform": "cpu", "rows": [
            {"metric": "iso seed", "value": 0.10, "unit": "GPts/s",
             "provenance": {"ncpu": 1, "loadavg": [0.1, 0.1, 0.1]}},
            {"metric": "other", "value": 1.0, "unit": "GPts/s"}]}))
    (root / "BENCH_r02.json").write_text(json.dumps(
        {"platform": "tpu", "rows": [
            {"metric": "iso seed", "value": 9.9, "unit": "GPts/s"}]}))
    (root / "BENCH_junk.json").write_text("{not json")
    rows = ledger_mod.seed_rows_from_bench("iso seed", "cpu",
                                           root=str(root))
    assert len(rows) == 1        # metric-matched, cpu doc only
    assert rows[0]["source"] == "bench_seed"
    assert rows[0]["value"] == 0.10
    assert rows[0]["provenance"]["cpu_model"] == ""   # backfilled
    assert is_clean(rows[0])

    monkeypatch.setattr(ledger_mod, "repo_root", lambda: str(root))
    path = str(tmp_path / "ledger.jsonl")
    row = guard_and_append("iso seed", 0.098, "GPts/s", "cpu", "test",
                           _prov(), path=path)
    assert row["guard"]["status"] == "ok"
    assert row["guard"]["baseline"] == pytest.approx(0.10)
    # ... and a first-measurement regression is CAUGHT, not waved
    # through as no_history
    row = guard_and_append("iso seed", 0.05, "GPts/s", "cpu", "test",
                           _prov(), remeasure=lambda: 0.05,
                           path=str(tmp_path / "ledger2.jsonl"))
    assert row["guard"]["status"] == "regression"


# ------------------------------------------------------------ provenance

def test_provenance_on_stub_proc(tmp_path):
    proc = tmp_path / "proc"
    proc.mkdir()
    (proc / "cpuinfo").write_text(
        "processor\t: 0\nvendor_id\t: TestVendor\n"
        "model name\t: Test CPU @ 9.99GHz\n")
    (proc / "loadavg").write_text("1.25 0.75 0.50 2/345 6789\n")
    sysr = tmp_path / "sys"
    gov = sysr / "devices/system/cpu/cpu0/cpufreq"
    gov.mkdir(parents=True)
    (gov / "scaling_governor").write_text("performance\n")
    prov = capture_provenance(platform="cpu", device_kind="stub",
                              calibrate=False, proc_root=str(proc),
                              sys_root=str(sysr))
    assert prov["cpu_model"] == "Test CPU @ 9.99GHz"
    assert prov["loadavg"] == [1.25, 0.75, 0.5]
    assert prov["governor"] == "performance"
    assert prov["platform"] == "cpu" and prov["device_kind"] == "stub"
    assert prov["ncpu"] >= 1 and len(prov["env_fp"]) == 12
    assert "calib_gpts" not in prov
    # the real repo: git SHA is resolvable and non-empty
    assert prov["git_sha"]


def test_provenance_missing_proc_is_not_fatal(tmp_path):
    prov = capture_provenance(calibrate=False,
                              proc_root=str(tmp_path / "nope"),
                              sys_root=str(tmp_path / "nope"))
    assert prov["cpu_model"] == ""
    assert len(prov["loadavg"]) == 3   # os.getloadavg fallback


def test_calibration_rate_is_positive():
    from yask_tpu.perflab.provenance import calibration_gpts
    assert calibration_gpts(reps=1) > 0


# -------------------------------------------------------------- roofline

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
    # the exact arithmetic main.py/bench.py printed before the hoist:
    # gbps = rate × (read+write bytes/pt); frac = gbps/peak — from a
    # real prepared context so hbm_model_bytes_pp is the live model
    from yask_tpu import yk_factory
    from yask_tpu.perflab.roofline import ctx_roofline, format_roofline
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


# ------------------------------------------------- producers & CLI glue

def test_ledger_to_csv(tmp_path, capsys):
    path = str(tmp_path / "ledger.jsonl")
    guard_and_append("iso jit", 0.11, "GPts/s", "cpu", "test",
                     _prov(), roofline={"hbm_bytes_pp": 21.1,
                                        "hbm_gbps": 2.3,
                                        "roofline_frac": None},
                     path=path)
    from yask_tpu.tools.log_to_csv import ledger_to_csv
    n = ledger_to_csv(path)
    out = capsys.readouterr().out
    assert n == 1
    header, line = out.strip().splitlines()
    assert header.startswith("key,value,unit,platform,source")
    assert line.startswith("iso jit,0.11,GPts/s,cpu,test")
    assert "abc1234" in line and "TestCPU" in line


def test_ledger_to_csv_push_resident_columns(tmp_path, capsys):
    # the pipeline-push and serve-resident A/B rows flatten their extra
    # fields into dedicated columns (model B/pt, per-arm secs, achieved
    # GB/s, occupancy); other rows leave those columns empty
    import csv as _csv
    import io
    path = str(tmp_path / "ledger.jsonl")
    guard_and_append(
        "rtm3-pure r=2 32^3 cpu pipeline-push-speedup", 1.48, "x",
        "cpu", "suite", _prov(),
        extra={"push_vars": ["img__img"],
               "hbm_bytes_model": {"chained_bytes_pp": 44.0,
                                   "fused_bytes_pp": 20.0,
                                   "fused_push_bytes_pp": 16.0,
                                   "ratio": 2.2, "push_ratio": 2.75},
               "push_secs": 0.9, "achieved_gbs_push": 1.2,
               "achieved_gbs_fused": 1.0, "achieved_gbs_chained": 0.8},
        path=path)
    guard_and_append(
        "iso3dfd r=2 16^3 cpu serve-resident-speedup", 5.6, "x",
        "cpu", "suite", _prov(),
        extra={"occupancy": 4, "items": 16, "resident_secs": 0.01,
               "per_request_secs": 0.06},
        path=path)
    from yask_tpu.tools.log_to_csv import ledger_to_csv
    ledger_to_csv(path)
    rows = list(_csv.DictReader(io.StringIO(capsys.readouterr().out)))
    push, res = rows
    assert push["push_vars"] == '["img__img"]'
    assert push["push_bytes_pp"] == "16.0"
    assert push["push_ratio"] == "2.75"
    assert push["push_secs"] == "0.9"
    assert push["achieved_gbs_push"] == "1.2"
    assert push["occupancy"] == "" and push["resident_secs"] == ""
    assert res["occupancy"] == "4"
    assert res["resident_secs"] == "0.01"
    assert res["per_request_secs"] == "0.06"
    assert res["push_vars"] == "" and res["push_bytes_pp"] == ""


def test_harness_ledger_flag(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("YT_PERF_LEDGER", str(tmp_path / "led.jsonl"))
    from yask_tpu.main import run_harness
    rc = run_harness(["-stencil", "3axis", "-g", "12",
                      "-num_trials", "1", "-trial_steps", "2",
                      "-ledger"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ledger: recorded '3axis g=12x12x12 cpu harness (jit)'" in out
    rows = read_rows(str(tmp_path / "led.jsonl"))
    assert len(rows) == 1
    assert rows[0]["source"] == "harness"
    assert rows[0]["unit"] == "GPts/s"
    assert rows[0]["provenance"]["cpu_model"] != ""
    assert rows[0]["guard"]["status"] == "no_history"


def test_perf_bisect_parse_key():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perf_bisect", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "perf_bisect.py"))
    pb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pb)
    s = pb.parse_key("iso3dfd r=8 128^3 fp32 cpu throughput (jit)")
    assert s == {"kind": "throughput", "stencil": "iso3dfd",
                 "radius": 8, "g": 128, "mode": "jit", "wf": 1}
    s = pb.parse_key("cube 27pt 32^3 cpu wavefront-speedup")
    assert s["kind"] == "wavefront-speedup" and s["g"] == 32
    s = pb.parse_key("iso3dfd r=8 48^3 cpu pallas-K2 bf16")
    assert s["mode"] == "pallas" and s["wf"] == 2
    s = pb.parse_key("3axis g=16x16x16 cpu harness (jit)")
    assert s["g"] == 16 and s["mode"] == "jit"
    with pytest.raises(ValueError):
        pb.parse_key("no size here")


# ------------------------------------------- slow integration (not tier-1)

@pytest.mark.slow
def test_perfcheck_end_to_end(tmp_path, monkeypatch, capsys):
    """make perfcheck's engine: quick rows through the sentinel against
    a fresh ledger — everything is no_history/ok, exit 0."""
    monkeypatch.setenv("YT_PERF_LEDGER", str(tmp_path / "led.jsonl"))
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perfcheck", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "perfcheck.py"))
    pc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pc)
    rc = pc.run(budget_secs=240.0)
    out = capsys.readouterr().out
    assert "perfcheck:" in out
    assert rc == 0, out
    rows = read_rows(str(tmp_path / "led.jsonl"))
    assert rows, "suite rows must reach the ledger"
    for r in rows:
        assert r["provenance"]["loadavg"]
        assert r["provenance"]["git_sha"]
        assert "status" in r["guard"]


@pytest.mark.slow
@pytest.mark.xfail(reason="bf16 interpret-mode proxy is NOT ~1× fp32 at "
                   "the suite size (r6 measured: 0.84× at 32^3, 0.22× "
                   "at 48^3, K=2 r=8).  Two compounding causes, neither "
                   "a proxy-side defect: (1) bf16's sublane tile is 16, "
                   "so E_sk=32 correctly fails the skew profit gate — "
                   "bf16 keeps uniform-shrink margins (margin_overhead "
                   "1.5 vs 0.5 for skewed fp32 at 48^3), 1.67× the "
                   "work/point; (2) CPU bf16 arithmetic is software-"
                   "emulated.  On real Mosaic bf16 halves HBM traffic "
                   "and the expectation is ≥1×; re-pin from "
                   "tools/tpu_session.py's bf16_ab stage on a chip.",
                   strict=False)
def test_bf16_interpret_proxy_parity():
    """bf16 should at least match fp32 once the proxy stops emulating:
    the pinned expectation for hardware (VERDICT r5's 0.38× inversion,
    measured at the suite's 48^3 row size)."""
    import time
    from yask_tpu import yk_factory
    from yask_tpu.compiler.solution_base import create_solution
    from yask_tpu.runtime.init_utils import init_solution_vars

    def rate(elem_bytes):
        fac = yk_factory()
        env = fac.new_env()
        sb = create_solution("iso3dfd", radius=8)
        if elem_bytes:
            sb.get_soln().set_element_bytes(elem_bytes)
        ctx = fac.new_solution(env, sb)
        ctx.apply_command_line_options("-g 48 -wf_steps 2")
        ctx.get_settings().mode = "pallas"
        ctx.prepare_solution()
        init_solution_vars(ctx)
        ctx.run_solution(0, 1)          # compile
        t0 = time.perf_counter()
        ctx.run_solution(2, 5)
        return 4 * 48 ** 3 / (time.perf_counter() - t0)

    ratio = rate(2) / rate(None)
    assert ratio >= 0.9, f"bf16 at {ratio:.2f}x fp32"
