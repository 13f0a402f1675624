"""The six readers that split ``setup_s`` by the program's kept spans
(``benchmark/program_setup.py``; ``runtime.import_s``,
``runtime.prepare_s``, ``runtime.fill_s``, ``compile.build_s``,
``compile.cache_misses``, ``runtime.setup_unattributed_s``): over a
made-up record, against a program that keeps none, in the manifest, and
in the dry runs of an ``advance`` cell and the served cell, a checkout's
first run and its second."""

import importlib.util
import os
import re
import sys
import types

import pytest

from bench_util import BENCH, manifest, result_line, run_cell

import yask_tpu.obs

#: metric -> (unit, layer), in the order the manifest lists them
METRICS = {"runtime.import_s": ("s", "runtime"),
           "runtime.prepare_s": ("s", "runtime"),
           "runtime.fill_s": ("s", "runtime"),
           "compile.build_s": ("s", "compile"),
           "compile.cache_misses": ("count", "compile"),
           "runtime.setup_unattributed_s": ("s", "runtime")}
SECONDS = [m for m in METRICS if m != "compile.cache_misses"]


def reader(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # the readers' own helper
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def row(name, t0, secs, parent="", tid=1, phase=None, **attrs):
    if phase is None:
        phase = "setup" if name.startswith(("setup.", "serve.")) \
            or name == "state.fill" else "compile"
    return {"name": name, "phase": phase, "t0": t0, "secs": secs,
            "tid": tid, "parent": parent, "attrs": attrs}


#: an ``advance`` cell's set-up as the program would keep it: the
#: harness starts at 100.0, imports until 105.0, builds until 109.0
#: (1.5 s of it ``device_state``'s), warms up 109.0 - 112.5 with a probe
#: read of 0.5 s between the units, and opens its window at 113.0
ADVANCE = [
    row("setup.import", 105.0, 0.5, since_start_s=5.02),
    row("setup.env", 105.5, 0.1),
    row("setup.solution", 105.6, 0.2),
    row("setup.plan", 105.8, 0.9, parent="setup.prepare"),
    row("setup.alloc", 106.7, 0.3, parent="setup.prepare"),
    row("setup.prepare", 105.8, 1.2),
    row("state.fill", 107.0, 0.25, var="c0", via="host"),
    row("state.fill", 107.25, 0.25, var="c1", via="host"),
    # the first warm-up unit, 109.0 - 111.0: a push, a build, a run
    row("state.to_device", 109.0, 0.125, phase="dma"),
    row("cache.aot", 109.25, 1.0, parent="compile.chunk", hit="miss"),
    row("compile.chunk", 109.125, 1.25),
    row("cache.aot", 110.5, 0.125, hit="jax"),      # outside a chunk
    # a build inside the window: not set-up's
    row("compile.chunk", 114.0, 9.0),
]


def a_run(kept, setup_s=12.75, first=(2.0, 1.0), start=113.0, tiny=False):
    return types.SimpleNamespace(
        setup_s=setup_s, first_call_s=list(first),
        units=[(start, start + 0.5, 10), (start + 0.5, start + 1.0, 10)],
        cell=types.SimpleNamespace(tiny=tiny)), kept


@pytest.fixture
def record(monkeypatch):
    """Put a made-up kept record in the program's place."""
    def put(kept):
        monkeypatch.setattr(yask_tpu.obs, "kept_spans",
                            lambda: [dict(r) for r in kept])
    return put


def read_all(run):
    return {name: reader(name)(run) for name in METRICS}


def test_each_part_sums_its_top_level_rows(record, capsys):
    run, kept = a_run(ADVANCE)
    record(kept)
    got = read_all(run)
    assert got["runtime.import_s"] == 0.5
    assert got["runtime.prepare_s"] == pytest.approx(0.1 + 0.2 + 1.2)
    assert got["runtime.fill_s"] == pytest.approx(0.5 + 0.125)
    # the aot inside the chunk is counted once, the one outside too
    assert got["compile.build_s"] == pytest.approx(1.25 + 0.125)
    assert got["compile.cache_misses"] == 1
    # 12.75 s of set-up: 3.0 s of warm-up units, of which 1.5 s lie in
    # kept rows; 2.5 s of kept rows before them; the rest is no span's
    assert got["runtime.setup_unattributed_s"] == pytest.approx(
        12.75 - 3.0 - 2.5)
    said = capsys.readouterr().out
    assert said.count("set-up by the program's kept spans") == 1
    assert "since_start_s 5.02" in said and "rows 12" in said
    assert "warm_run_s 1.5" in said and "setup_s 12.75" in said


def test_the_parts_and_the_warm_up_run_time_sum_to_setup_s(record):
    import program_setup
    run, kept = a_run(ADVANCE)
    record(kept)
    got = read_all(run)
    warm = program_setup.read(run, "warm_run_s")
    assert warm == pytest.approx(3.0 - 0.125 - 1.25 - 0.125)
    assert sum(got[m] for m in SECONDS) + warm == pytest.approx(
        run.setup_s)


def test_a_span_under_another_thread_s_span_is_counted_once(record):
    # the served cell: the session's opening holds the solution and its
    # prepare; a worker's fill lies inside the client's upload
    kept = [
        row("setup.import", 105.0, 0.5, since_start_s=5.0),
        row("setup.env", 105.5, 0.25),
        row("setup.solution", 105.75, 0.25, parent="serve.open"),
        row("setup.prepare", 106.0, 1.0, parent="serve.open"),
        row("serve.open", 105.75, 1.5, sid="s0"),
        row("state.fill", 107.5, 1.75, tid=2, var="p", via="host"),
        row("serve.set_var", 107.25, 2.25, var="p", sid="s0"),
        row("compile.chunk", 110.0, 0.5, tid=2),
    ]
    run, kept = a_run(kept, setup_s=12.0, first=(1.0, 0.5))
    record(kept)
    got = read_all(run)
    assert got["runtime.prepare_s"] == pytest.approx(0.25 + 1.5)
    assert got["runtime.fill_s"] == pytest.approx(2.25)
    assert got["compile.build_s"] == pytest.approx(0.5)
    assert got["compile.cache_misses"] == 0
    assert got["runtime.setup_unattributed_s"] == pytest.approx(
        12.0 - 1.5 - (0.5 + 1.75 + 2.25))


def test_a_name_no_metric_knows_stays_unattributed(record):
    kept = [row("setup.import", 105.0, 0.5),
            row("setup.something_new", 106.0, 2.0)]
    run, kept = a_run(kept, setup_s=10.0, first=(1.0, 1.0))
    record(kept)
    got = read_all(run)
    assert got["runtime.prepare_s"] == 0.0
    assert got["runtime.setup_unattributed_s"] == pytest.approx(
        10.0 - 2.0 - 0.5)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_the_record_reads_nothing(monkeypatch, name):
    # the parent commit: ``yask_tpu.obs`` has no ``kept_spans``
    monkeypatch.delattr(yask_tpu.obs, "kept_spans")
    run, _kept = a_run(ADVANCE)
    assert reader(name)(run) is None
    assert not hasattr(yask_tpu.obs, "kept_spans")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_no_window_and_no_rows_read_nothing(record, name):
    record(ADVANCE)
    run, _kept = a_run(ADVANCE)
    run.units = []                      # the control run: no window
    assert reader(name)(run) is None
    record([])
    run, _kept = a_run([])
    assert reader(name)(run) is None


def test_the_manifest_lists_the_six_for_every_cell():
    """No ``workloads`` key: a metric without one is reported in every
    cell that reports ``setup_s``, which is every cell, a later PR's
    too (``compile.first_call_s`` is listed the same way).  An explicit
    list of the eleven cells would be a fourteenth to nineteenth list
    naming the one-chip cells, and ``test_iso3dfd_768_cell.py`` pins
    their number to thirteen."""
    m = manifest()
    setup = next(e for e in m["end_to_end"] if e["name"] == "setup_s")
    assert "workloads" not in setup
    names = [e["name"] for e in m["per_layer"]]
    # in this order, behind every metric the benchmark had (a later
    # PR's entries may follow them)
    at = [names.index(name) for name in METRICS]
    assert at == sorted(at) and at[0] > names.index(
        "kernel.hbm_moved_share")
    for name, (unit, layer) in METRICS.items():
        assert m["per_layer"][names.index(name)] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": "setup_s"}
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("cell", [w["name"]
                                  for w in manifest()["workloads"]])
def test_every_cell_reports_the_six(cell):
    """As ``run.py load_cell`` picks a cell's per-layer metrics."""
    m = manifest()
    mine = [e["name"] for e in m["per_layer"]
            if "workloads" not in e or cell in e["workloads"]]
    assert [n for n in mine if n in METRICS] == list(METRICS)


def said(stdout, key):
    """One number of the line ``program_setup.split`` prints."""
    line = next(ln for ln in stdout.splitlines()
                if "set-up by the program's kept spans" in ln)
    return float(re.search(rf"\b{key} ([-0-9.e]+)", line).group(1))


def check_a_dry_run(r, first_run):
    assert r.returncode == 0, r.stdout + r.stderr
    metrics = result_line(r.stdout)["metrics"]
    for name, (unit, _layer) in METRICS.items():
        assert metrics[name]["unit"] == unit
        assert f"metric {name} = " in r.stdout
    parts = sum(metrics[m]["value"] for m in SECONDS)
    setup_s, warm = said(r.stdout, "setup_s"), said(r.stdout, "warm_run_s")
    assert parts + warm == pytest.approx(setup_s, rel=0.01)
    for m in SECONDS[:-1]:
        assert metrics[m]["value"] >= 0.0
    assert 0.0 < metrics["runtime.import_s"]["value"] < setup_s
    assert 0.0 < metrics["runtime.prepare_s"]["value"] < setup_s
    # the harness's own imports and seeding are somebody's seconds
    assert 0.0 < metrics["runtime.setup_unattributed_s"]["value"] < setup_s
    assert 0.0 <= warm <= metrics["compile.first_call_s"]["value"]
    assert said(r.stdout, "since_start_s") > 0.0
    misses = metrics["compile.cache_misses"]["value"]
    assert misses > 0 if first_run else misses == 0
    return metrics


def test_the_dry_runs_of_an_advance_cell_cold_then_warm(tmp_path):
    """Four virtual chips: the mesh, the device fills, the calibration
    and the shard program's build are all kept.  A cache directory of
    its own that stores whatever was compiled: the first run misses,
    the second is served."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    cold = check_a_dry_run(run_cell(
        "iso3dfd-r8-4chip.advance", devices=4, trace=1, env_over=env),
        first_run=True)
    warm = check_a_dry_run(run_cell(
        "iso3dfd-r8-4chip.advance", devices=4, trace=1, env_over=env,
        seed=2147483779), first_run=False)
    # sharded: the public fills run on the devices, inside the program
    assert cold["runtime.fill_s"]["value"] > 0.0
    assert warm["runtime.fill_s"]["value"] > 0.0
    assert cold["compile.build_s"]["value"] > 0.0


def test_the_dry_run_of_the_served_cell_twice_in_one_checkout():
    for seed in (2147483777, 2147483781):
        metrics = check_a_dry_run(run_cell(
            "iso3dfd-r8-1chip.snapshots", trace=1, seed=seed),
            first_run=False)    # interpreted kernels: nothing to miss
        # the client's uploads are the state's way onto the device
        assert metrics["runtime.fill_s"]["value"] > 0.0
