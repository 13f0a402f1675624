"""The kept record (``obs/tracer.py``: ``span(..., keep=True)``,
``kept_spans()``): the tracer's third sink, and the set-up sites that
write to it -- the package's import, ``new_env``, ``new_solution``,
``prepare_solution`` and its three parts, the public fills, a served
session's opening and uploads, and the builds, pushes and derived fills
of a first call.  No test here decides by a wall clock: seconds are
only compared with one another inside one record.
"""

import io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.obs import kept_spans, tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G = 32
MODES = ("pallas", "jit", "shard_pallas")


def since(t0):
    """The kept rows that began at or after ``t0`` (the ring is the
    process's: earlier tests' rows lie before it)."""
    return [r for r in kept_spans() if r["t0"] >= t0]


def flow(mode):
    """``new_env`` -> ``new_solution`` -> ``prepare_solution`` -> one
    public fill of each kind -> two ``run_solution`` calls.  Returns
    the context, the rows of everything up to the first call's end,
    and the rows the second, steady call added."""
    import jax
    t0 = time.perf_counter()
    fac = yk_factory()
    ranks = 4 if mode == "shard_pallas" else 1
    env = fac.new_env(devices=jax.devices()[:ranks])
    ctx = fac.new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options(
        f"-g_x {G * ranks} -g_y {G} -g_z 128 -mode {mode} -wf_steps 2")
    if ranks > 1:
        ctx.set_num_ranks("x", ranks)
    ctx.prepare_solution()
    vel, p = ctx.get_var("vel"), ctx.get_var("pressure")
    vel.set_all_elements_same(0.05)
    p.set_elements_in_seq(0.01)
    p.set_element(1.0, [0, 4, 4, 4])
    p.set_elements_in_slice(np.full((2, 2, 2), 0.5, np.float32),
                            [0, 8, 8, 8], [0, 9, 9, 9])
    ctx.run_solution(0, 3)
    first = since(t0)
    t1 = time.perf_counter()
    ctx.run_solution(4, 7)
    return ctx, first, since(t1)


@pytest.fixture(scope="module", params=MODES)
def flowed(request):
    ctx, first, steady = flow(request.param)
    yield request.param, first, steady
    ctx.end_solution()


def named(rows, name):
    return [r for r in rows if r["name"] == name]


# ------------------------------------------------------------ the sites

@pytest.mark.parametrize("name", ["setup.env", "setup.solution",
                                  "setup.prepare", "setup.plan",
                                  "setup.alloc"])
def test_each_span_of_set_up_is_kept_once(flowed, name):
    _mode, first, _steady = flowed
    (row,) = named(first, name)
    assert row["phase"] == "setup" and row["secs"] >= 0.0
    assert row["tid"] == first[0]["tid"]


def test_the_mesh_is_a_span_of_the_sharded_modes_alone(flowed):
    mode, first, _steady = flowed
    assert len(named(first, "setup.mesh")) == (mode == "shard_pallas")


def test_the_parts_of_prepare_nest_in_it_and_nothing_else_nests(flowed):
    _mode, first, _steady = flowed
    for r in first:
        if r["name"] in ("setup.plan", "setup.mesh", "setup.alloc"):
            assert r["parent"] == "setup.prepare"
        elif r["name"] == "cache.aot":
            assert r["parent"] in ("compile.chunk", "")
        else:
            assert r["parent"] == "", r
    (prep,) = named(first, "setup.prepare")
    inner = [r for r in first if r["parent"] == "setup.prepare"]
    assert sum(r["secs"] for r in inner) <= prep["secs"]
    for r in inner:
        assert prep["t0"] <= r["t0"]
        assert r["t0"] + r["secs"] <= prep["t0"] + prep["secs"]


def test_the_attrs_say_what_each_span_did(flowed):
    mode, first, _steady = flowed
    (env,) = named(first, "setup.env")
    assert env["attrs"] == {"devices": 4 if mode == "shard_pallas" else 1,
                            "platform": "cpu"}
    (sol,) = named(first, "setup.solution")
    assert sol["attrs"] == {"stencil": "iso3dfd", "radius": 2}
    (prep,) = named(first, "setup.prepare")
    (alloc,) = named(first, "setup.alloc")
    assert prep["attrs"]["mode"] == mode
    assert prep["attrs"]["vars"] == alloc["attrs"]["vars"] == 2
    assert prep["attrs"]["bytes"] == alloc["attrs"]["bytes"] > 0


def test_a_public_fill_is_one_span_whichever_way_it_goes(flowed):
    mode, first, _steady = flowed
    fills = named(first, "state.fill")
    assert [r["attrs"]["var"] for r in fills] == [
        "vel", "pressure", "pressure", "pressure"]
    # sharded: written into the resident interiors; else through the host
    via = "device" if mode == "shard_pallas" else "host"
    assert {r["attrs"]["via"] for r in fills} == {via}
    whole, seq, one, box = (r["attrs"]["bytes"] for r in fills)
    assert one == 4 and box == 8 * 4
    assert seq > whole > box        # two padded ring slots against one


def test_a_first_call_keeps_its_build(flowed):
    mode, first, _steady = flowed
    chunks = named(first, "compile.chunk")
    assert chunks and all(r["phase"] == "compile" for r in chunks)
    # a shard state is padded to its program's form by programs of
    # their own, built with it in the first call
    also = {"shard_pad", "shard_strip"} if mode == "shard_pallas" \
        else set()
    assert {r["attrs"]["kind"] for r in chunks} == {mode} | also
    fills = named(first, "state.fill")
    assert min(r["t0"] for r in chunks) >= max(
        r["t0"] + r["secs"] for r in fills)


def test_top_level_rows_of_one_thread_do_not_overlap(flowed):
    _mode, first, _steady = flowed
    tops = sorted((r for r in first if not r["parent"]),
                  key=lambda r: r["t0"])
    assert len({r["tid"] for r in tops}) == 1
    for a, b in zip(tops, tops[1:]):
        assert a["t0"] + a["secs"] <= b["t0"], (a, b)


def test_a_second_steady_call_appends_no_row(flowed):
    _mode, _first, steady = flowed
    assert steady == []


def test_a_build_says_what_a_hit_would_still_cost():
    """``cache.aot`` carries ``lower_secs`` and ``load_secs`` beside
    ``compile_secs``, and ``hit`` names how the backend's part ended."""
    _ctx, first, _steady = flow("jit")
    rows = named(first, "cache.aot")
    assert rows
    for r in rows:
        a = r["attrs"]
        assert r["parent"] == "compile.chunk"
        assert a["hit"] in ("memory", "disk", "jax", "uncached", "miss")
        assert 0.0 <= a["lower_secs"] <= a["compile_secs"] <= r["secs"]
        assert a["load_secs"] >= 0.0
        assert (a["load_secs"] > 0.0) == (a["hit"] in ("jax", "disk"))


@pytest.mark.parametrize("served, tier", [(0, "uncached"), (1, "jax")])
def test_the_tier_follows_jax_own_cache(monkeypatch, served, tier):
    import jax.numpy as jnp
    from yask_tpu.cache import compile_cache
    seen = iter([0, served])
    monkeypatch.setattr(compile_cache, "_count_jax_hits",
                        lambda: next(seen))
    res = compile_cache.aot_compile(
        lambda x: x * 1.0625 + served, (jnp.ones(8),))
    assert res.cache_hit is None and res.tier == tier
    assert (res.load_secs > 0.0) == bool(served)
    assert 0.0 < res.lower_secs < res.compile_secs


def test_a_compile_over_the_storing_threshold_is_a_miss(monkeypatch):
    import jax
    import jax.numpy as jnp
    from yask_tpu.cache import compile_cache
    monkeypatch.setattr(compile_cache, "_count_jax_hits", lambda: 0)
    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    jax.config.update(name, 0.0)
    try:
        res = compile_cache.aot_compile(lambda x: x * 1.03125,
                                        (jnp.ones(8),))
    finally:
        jax.config.update(name, was)
    assert res.tier == "miss" and res.load_secs == 0.0


def test_the_listener_counts_jax_own_hits_on_the_compiling_thread():
    from jax import monitoring
    from yask_tpu.cache import compile_cache
    n = compile_cache._count_jax_hits()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert compile_cache._count_jax_hits() == n + 1


# ------------------------------------------------------- a served session

def test_a_session_keeps_its_opening_and_its_uploads():
    from yask_tpu.serve import StencilServer
    t0 = time.perf_counter()
    srv = StencilServer(preflight=False, window_secs=0.0)
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=2, g=16,
                               mode="jit", wf=2, bucket=False)
        srv.set_var(sid, "vel", 0.05)
        srv.set_var_slice(sid, "pressure", np.ones((16, 16, 16), "f4"),
                          [0, 0, 0, 0], [0, 15, 15, 15])
        rows = since(t0)
    finally:
        srv.shutdown()
    (opened,) = named(rows, "serve.open")
    assert opened["phase"] == "setup" and opened["parent"] == ""
    assert opened["attrs"]["sid"] == sid
    assert opened["attrs"]["stencil"] == "iso3dfd"
    # the profile's solution and its prepare lie inside the opening
    for name in ("setup.solution", "setup.prepare"):
        (r,) = named(rows, name)
        assert r["parent"] == "serve.open"
    ups = named(rows, "serve.set_var")
    assert [u["attrs"]["var"] for u in ups] == ["vel", "pressure"]
    assert ups[1]["attrs"]["bytes"] == 16 ** 3 * 4
    assert all(u["attrs"]["sid"] == sid and u["attrs"]["bytes"] > 0
               for u in ups)
    fills = named(rows, "state.fill")
    assert [f["parent"] for f in fills] == ["serve.set_var"] * 2
    for u, f in zip(ups, fills):
        assert u["t0"] <= f["t0"]
        assert f["t0"] + f["secs"] <= u["t0"] + u["secs"]


# ------------------------------------------------------------- the import

def test_the_import_is_the_first_kept_row_of_a_process():
    code = ("import json, yask_tpu; from yask_tpu.obs import kept_spans;"
            "print(json.dumps(kept_spans()))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("YT_TRACE", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    (row,) = json.loads(out.stdout.strip().splitlines()[-1])
    assert (row["name"], row["phase"], row["parent"]) == (
        "setup.import", "setup", "")
    assert row["attrs"]["secs"] == pytest.approx(row["secs"], abs=1e-5)
    # what ran before the import began: the interpreter's start-up
    assert 0.0 <= row["attrs"]["since_start_s"] < 60.0


def test_the_age_of_the_process_is_read_or_left_out(monkeypatch):
    age = tracer.process_age()
    assert age is not None and 0.0 < age < 24 * 3600.0
    assert tracer.process_age() >= age

    def closed(*_a, **_k):
        raise OSError("no /proc here")
    monkeypatch.setattr("builtins.open", closed)
    assert tracer.process_age() is None


# --------------------------------------------------------------- the sink

def test_setup_is_a_phase():
    assert "setup" in tracer.PHASES


def test_a_span_that_is_not_kept_is_still_the_null_handle(monkeypatch):
    monkeypatch.delenv("YT_TRACE", raising=False)
    t0 = time.perf_counter()
    with tracer.span("run.launch", phase="compute", k=2) as sp:
        assert sp is tracer._NULL
    with tracer.span("run.wait", phase="compute") as sp:
        assert sp is tracer._NULL
    assert since(t0) == []


def test_a_kept_span_keeps_its_scalar_attrs_and_those_set_later(
        monkeypatch):
    monkeypatch.delenv("YT_TRACE", raising=False)
    t0 = time.perf_counter()
    with tracer.span("t.outer", phase="setup", keep=True, a=1,
                     box=[1, 2]) as sp:
        assert sp is not tracer._NULL
        assert sp.span == "" and sp.trace == ""     # no ids made
        sp.set(b="two", c=None, d={"no": 1})
        with tracer.span("t.unkept", phase="compute"):
            with tracer.span("t.inner", phase="dma", keep=True):
                pass
    t1 = time.perf_counter()
    inner, outer = since(t0)
    assert (outer["name"], outer["phase"], outer["parent"]) == (
        "t.outer", "setup", "")
    assert outer["attrs"] == {"a": 1, "b": "two", "c": None}
    # the parent is the enclosing KEPT span, whatever lies between
    assert (inner["name"], inner["parent"]) == ("t.inner", "t.outer")
    assert t0 <= outer["t0"] <= inner["t0"]
    assert inner["t0"] + inner["secs"] <= outer["t0"] + outer["secs"] <= t1
    assert tracer.current_trace_id() == ""


def test_a_kept_span_that_raises_still_leaves_its_row_and_its_stack():
    t0 = time.perf_counter()
    with pytest.raises(KeyError):
        with tracer.span("t.raises", phase="setup", keep=True):
            raise KeyError("x")
    with tracer.span("t.after", phase="setup", keep=True):
        pass
    assert [(r["name"], r["parent"]) for r in since(t0)] == [
        ("t.raises", ""), ("t.after", "")]


def test_threads_keep_their_own_nesting():
    import threading
    t0 = time.perf_counter()

    def worker():
        with tracer.span("t.worker", phase="setup", keep=True):
            pass
    with tracer.span("t.client", phase="setup", keep=True):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    worker_row, client = since(t0)
    assert worker_row["parent"] == "" and client["parent"] == ""
    assert worker_row["tid"] != client["tid"]


def test_a_retroactive_span_takes_the_same_flag(monkeypatch):
    monkeypatch.delenv("YT_TRACE", raising=False)
    now = time.perf_counter()
    tracer.record_span("t.retro", "setup", time.time(), 0.25, keep=True,
                       t0=now, n=3)
    tracer.record_span("t.unkept", "queue", time.time(), 0.25)
    tracer.record_span("t.ended_now", "setup", time.time(), 0.5,
                       keep=True)
    retro, ended = since(now - 1.0)[-2:]
    assert (retro["name"], retro["t0"], retro["secs"], retro["parent"],
            retro["attrs"]) == ("t.retro", now, 0.25, "", {"n": 3})
    assert ended["name"] == "t.ended_now"
    assert ended["t0"] + 0.5 == pytest.approx(time.perf_counter(),
                                              abs=5.0)


def test_the_record_and_the_jsonl_speak_of_one_span(tmp_path,
                                                    monkeypatch):
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE", "1")
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    t0 = time.perf_counter()
    with tracer.span("t.both", phase="setup", keep=True, a=1) as sp:
        sp.set(b=2.5)
        with tracer.span("t.jsonl_only", phase="compute"):
            pass
    (kept,) = since(t0)
    written = {r["name"]: r for r in tracer.read_spans(str(path))}
    assert set(written) == {"t.both", "t.jsonl_only"}
    row = written["t.both"]
    assert (row["name"], row["dur"], row["phase"]) == (
        kept["name"], kept["secs"], kept["phase"])
    assert row["attrs"] == kept["attrs"] == {"a": 1, "b": 2.5}
    assert written["t.jsonl_only"]["parent"] == row["span"]


def test_the_ring_drops_the_oldest():
    assert tracer.KEPT_MAX == 1024
    for i in range(tracer.KEPT_MAX + 10):
        tracer.record_span("t.ring", "setup", 0.0, 0.0, keep=True,
                           t0=float(i))
    rows = kept_spans()
    assert len(rows) == tracer.KEPT_MAX
    assert [r["t0"] for r in rows[:2]] == [10.0, 11.0]
    assert rows[-1]["t0"] == float(tracer.KEPT_MAX + 9)
    # a copy: the caller's edits stay the caller's
    rows[0]["attrs"]["x"] = 1
    assert "x" not in kept_spans()[0]["attrs"]


# ------------------------------------------------------- the hot path

#: where a steady call and a request run: no kept span may open there
HOT = {"yask_tpu/serve/scheduler.py": None,
       "yask_tpu/runtime/run_state.py": None,
       "yask_tpu/runtime/context.py": ('span("run.call"',
                                       'span("run.launch"',
                                       'span("run.wait"'),
       "yask_tpu/parallel/shard_step.py": ('span("run.launch"',
                                           'span("run.wait"',
                                           'span("run.repad"')}


@pytest.mark.parametrize("path", sorted(HOT))
def test_no_span_of_the_hot_path_is_kept(path):
    with open(os.path.join(ROOT, path)) as f:
        text = f.read()
    if HOT[path] is None:
        assert "keep=True" not in text
        return
    for opener in HOT[path]:
        assert opener in text, opener
        for site in text.split(opener)[1:]:
            assert "keep=True" not in site.split(")")[0], opener


# ---------------------------------------------------------- the operator

def test_obs_report_prints_set_up_as_a_tree(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE", "1")
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    ctx, _first, _steady = flow("jit")
    ctx.end_solution()
    monkeypatch.delenv("YT_TRACE")
    out = io.StringIO()
    trees = obs_report.setup_report(tracer.read_spans(str(path)), out)
    lines = out.getvalue().splitlines()
    spans = [ln.split()[2] for ln in lines[1:-1]]
    assert spans[:6] == ["yt.setup.env", "yt.setup.solution",
                         "yt.setup.prepare", "yt.setup.plan",
                         "yt.setup.alloc", "yt.state.fill"]
    # children are indented under their parent; a build inside a call
    # is a tree of its own that says where it ran
    plan = next(ln for ln in lines if "yt.setup.plan" in ln)
    assert plan.index("yt.") > lines[3].index("yt.")
    chunk = next(ln for ln in lines if "yt.compile.chunk" in ln)
    assert "(in run.call)" in chunk and "kind=jit" in chunk
    aot = next(ln for ln in lines if "yt.cache.aot" in ln)
    assert "hit=" in aot and "lower_secs=" in aot
    assert "run.launch" not in out.getvalue()
    assert lines[-1].endswith(f"in {trees} trees")
    assert obs_report.main(["--setup", "--path", str(path)]) == 0
    empty = tmp_path / "none.jsonl"
    empty.write_text("")
    assert obs_report.main(["--setup", "--path", str(empty)]) == 1
