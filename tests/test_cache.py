"""yask_tpu.cache (persistent AOT compile cache): the trace counter
(`stats()["lowerings"]`) is the ground truth — a warm path must show
ZERO lowerings, and every failure path (corrupt entry, injected
load/store fault, eviction) must cost at most a compile, never a run.
`make cachecheck` runs this file; the cross-process test is the
acceptance criterion: a second process reuses the first's executable
without compiling once."""

import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from yask_tpu import cache as ccache
from yask_tpu.cache.compile_cache import (SCHEMA, _SUFFIX,
                                          args_signature,
                                          backend_fingerprint,
                                          entry_path, key_digest)
from yask_tpu.resilience import reset_faults

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Each test gets a clean memo/stats/fault plan; the disk dir is
    per-test via tmp_path where persistence is wanted."""
    monkeypatch.delenv("YT_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("YT_COMPILE_CACHE_MAX", raising=False)
    monkeypatch.delenv("YT_FAULT_PLAN", raising=False)
    ccache.clear_memo()
    ccache.reset_stats()
    reset_faults()
    yield
    ccache.clear_memo()
    ccache.reset_stats()
    reset_faults()


def add3(x):
    return x + 3.0


def example():
    import jax.numpy as jnp
    return (jnp.ones((8,), dtype=jnp.float32),)


# ---------------------------------------------------------------- digests

def test_digest_covers_key_and_fingerprint():
    fp = {"jax": "1", "jaxlib": "2", "code": "abc", "platform": "cpu"}
    d1 = key_digest(("k", 1), fp)
    assert d1 == key_digest(("k", 1), dict(fp))          # stable
    assert d1 != key_digest(("k", 2), fp)                # key sensitivity
    assert d1 != key_digest(("k", 1), dict(fp, jax="9"))  # fp sensitivity
    assert len(d1) == 40


def test_fingerprint_carries_code_identity():
    fp = backend_fingerprint("tpu")
    assert fp["platform"] == "tpu"
    assert set(fp) == {"jax", "jaxlib", "code", "platform"}
    # memoized statics: a second call agrees
    assert backend_fingerprint("tpu") == fp


def test_same_key_different_shapes_do_not_collide():
    """The executable is shape-specialized: an identical caller key
    over different example shapes must be a different entry, or the
    second call would hand back an executable that raises."""
    import jax.numpy as jnp
    a = (jnp.ones((8,), dtype=jnp.float32),)
    b = (jnp.ones((16,), dtype=jnp.float32),)
    r1 = ccache.aot_compile(add3, a, key=("t", "sig"))
    r2 = ccache.aot_compile(add3, b, key=("t", "sig"))
    assert r1.digest != r2.digest
    assert r2.cache_hit is None and ccache.stats()["lowerings"] == 2
    assert float(r2.fn(*b)[0]) == 4.0


def test_same_key_different_placement_does_not_collide():
    """The round-13 regression class: a jit-oracle chunk and a
    sharded-mode chunk over identically-padded state share the caller
    key but compile sharding-incompatible executables — the args
    signature (which includes each leaf's sharding) must keep them
    apart."""
    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs the multi-device CPU mesh (tests/conftest)")
    x0 = jax.device_put(jnp.ones((8,), dtype=jnp.float32), devs[0])
    x1 = jax.device_put(jnp.ones((8,), dtype=jnp.float32), devs[1])
    assert args_signature((x0,)) != args_signature((x1,))
    r1 = ccache.aot_compile(add3, (x0,), key=("t", "place"))
    r2 = ccache.aot_compile(add3, (x1,), key=("t", "place"))
    assert r1.digest != r2.digest
    assert float(r2.fn(x1)[0]) == 4.0


# ---------------------------------------------------------------- memo

def test_unkeyed_compile_counts_lowering():
    res = ccache.aot_compile(add3, example())
    assert res.cache_hit is None and res.digest is None
    assert ccache.stats()["lowerings"] == 1
    assert float(res.fn(*example())[0]) == 4.0


def test_keyed_memo_hit_is_zero_lowerings():
    r1 = ccache.aot_compile(add3, example(), key=("t", "memo"))
    r2 = ccache.aot_compile(add3, example(), key=("t", "memo"))
    assert r1.cache_hit is None and r2.cache_hit == "memory"
    assert r2.compile_secs == 0.0 and r2.fn is r1.fn
    assert ccache.stats()["lowerings"] == 1
    assert ccache.stats()["memory_hits"] == 1


def test_prejitted_callable_not_rewrapped():
    import jax
    jitted = jax.jit(add3, donate_argnums=0)
    res = ccache.aot_compile(jitted, example())
    assert float(res.fn(*example())[0]) == 4.0
    assert ccache.stats()["lowerings"] == 1


# ------------------------------------------------- cpu donation guard

def test_keyed_cpu_compile_strips_donation():
    # XLA:CPU deserialize-as-recompile mishandles donated aliased
    # buffers (freed-buffer scribble in passthrough outputs), so keyed
    # (persistable) cpu executables must be built WITHOUT donation:
    # the input survives the call.
    import jax.numpy as jnp
    x = jnp.ones((8,), jnp.float32)
    r = ccache.aot_compile(add3, (x,), key=("t", "dono"),
                           platform="cpu", donate_argnums=0)
    float(r.fn(x)[0])
    assert not x.is_deleted()
    r2 = ccache.aot_compile(add3, (x,), key=("t", "dono"), platform="cpu")
    assert r2.cache_hit == "memory"   # donation is not part of the digest


def test_unkeyed_compile_keeps_donation():
    import jax.numpy as jnp
    x = jnp.ones((8,), jnp.float32)
    r = ccache.aot_compile(add3, (x,), donate_argnums=0)
    float(r.fn(x)[0])
    assert x.is_deleted()


# ---------------------------------------------------------------- disk

def test_disk_roundtrip_within_process(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    r1 = ccache.aot_compile(add3, example(), key=("t", "disk"),
                            platform="cpu")
    assert r1.cache_hit is None and ccache.stats()["stores"] == 1
    assert os.path.exists(entry_path(r1.digest, str(tmp_path)))
    ccache.clear_memo()   # force the DISK path
    r2 = ccache.aot_compile(add3, example(), key=("t", "disk"),
                            platform="cpu")
    assert r2.cache_hit == "disk"
    assert ccache.stats()["lowerings"] == 1   # no second lowering
    assert float(r2.fn(*example())[0]) == 4.0


def test_corrupt_entry_falls_back_and_is_removed(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    r1 = ccache.aot_compile(add3, example(), key=("t", "corrupt"),
                            platform="cpu")
    path = entry_path(r1.digest, str(tmp_path))
    with open(path, "wb") as f:
        f.write(b"truncated garbage, not a pickle")
    ccache.clear_memo()
    r2 = ccache.aot_compile(add3, example(), key=("t", "corrupt"),
                            platform="cpu")
    assert r2.cache_hit is None              # fell back to a compile
    assert ccache.stats()["load_failures"] == 1
    assert ccache.stats()["lowerings"] == 2
    assert float(r2.fn(*example())[0]) == 4.0
    # the fresh result was re-stored over the corpse
    assert ccache.stats()["stores"] == 2
    with open(path, "rb") as f:
        assert pickle.load(f)["schema"] == SCHEMA


def test_stale_schema_entry_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    r1 = ccache.aot_compile(add3, example(), key=("t", "schema"),
                            platform="cpu")
    path = entry_path(r1.digest, str(tmp_path))
    entry = pickle.load(open(path, "rb"))
    entry["schema"] = "yask_tpu.compile_cache/0"
    pickle.dump(entry, open(path, "wb"))
    ccache.clear_memo()
    r2 = ccache.aot_compile(add3, example(), key=("t", "schema"),
                            platform="cpu")
    assert r2.cache_hit is None
    assert ccache.stats()["load_failures"] == 1


def test_eviction_bounds_directory(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    monkeypatch.setenv("YT_COMPILE_CACHE_MAX", "2")
    for i in range(4):
        ccache.aot_compile(add3, example(), key=("t", "evict", i),
                           platform="cpu")
    names = [n for n in os.listdir(tmp_path) if n.endswith(_SUFFIX)]
    assert len(names) <= 2
    assert ccache.stats()["evictions"] >= 2
    assert ccache.stats()["stores"] == 4


def test_iter_entries_reports_meta_and_junk(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    ccache.aot_compile(add3, example(), key=("t", "iter"),
                       platform="cpu")
    (tmp_path / ("deadbeef" + _SUFFIX)).write_bytes(b"junk")
    (tmp_path / "ignored.txt").write_text("not an entry")
    metas = list(ccache.iter_entries(str(tmp_path)))
    assert len(metas) == 2
    good = [m for _, m in metas if "unreadable" not in m]
    bad = [m for _, m in metas if "unreadable" in m]
    assert len(good) == 1 and good[0]["schema"] == SCHEMA
    assert len(bad) == 1


# ------------------------------------------------------- fault injection

def test_injected_load_fault_degrades_to_compile(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    ccache.aot_compile(add3, example(), key=("t", "lf"), platform="cpu")
    ccache.clear_memo()
    monkeypatch.setenv("YT_FAULT_PLAN", "cache.load:compile_failed")
    reset_faults()
    r = ccache.aot_compile(add3, example(), key=("t", "lf"),
                           platform="cpu")
    assert r.cache_hit is None               # fault → fresh compile
    assert ccache.stats()["load_failures"] == 1
    assert float(r.fn(*example())[0]) == 4.0


def test_injected_store_fault_never_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    monkeypatch.setenv("YT_FAULT_PLAN", "cache.store:compile_failed")
    reset_faults()
    r = ccache.aot_compile(add3, example(), key=("t", "sf"),
                           platform="cpu")
    assert float(r.fn(*example())[0]) == 4.0
    assert ccache.stats()["store_failures"] == 1
    assert ccache.stats()["stores"] == 0
    assert not [n for n in os.listdir(tmp_path) if n.endswith(_SUFFIX)]


# ------------------------------------------------- cross-process reuse

CHILD = r"""
import json, os, sys
sys.path.insert(0, {root!r})
from yask_tpu import cache as ccache
from yask_tpu import yk_factory
from yask_tpu.runtime.init_utils import init_solution_vars

fac = yk_factory()
env = fac.new_env()
ctx = fac.new_solution(env, stencil="iso3dfd", radius=2)
ctx.apply_command_line_options("-g 16 -wf_steps 2")
ctx.get_settings().mode = "jit"
ctx.prepare_solution()
init_solution_vars(ctx)
ctx.run_solution(0, 1)
mid = float(ctx.get_var("pressure").get_element([2, 8, 8, 8]))
print("STATS " + json.dumps(dict(ccache.stats(), probe=mid)))
"""


def test_cross_process_warm_cache_compiles_zero_times(tmp_path):
    """THE acceptance criterion: process 2 re-running process 1's
    config must deserialize the persisted executable and lower 0
    times (trace counter, not wall-clock)."""
    script = tmp_path / "child.py"
    script.write_text(CHILD.format(root=ROOT))
    env = dict(os.environ,
               YT_COMPILE_CACHE=str(tmp_path / "cache"),
               JAX_PLATFORMS="cpu")
    env.pop("YT_FAULT_PLAN", None)

    def run_child():
        out = subprocess.run(
            [sys.executable, str(script)], env=env, timeout=300,
            capture_output=True, text=True)
        assert out.returncode == 0, out.stderr[-2000:]
        line = [l for l in out.stdout.splitlines()
                if l.startswith("STATS ")][-1]
        return json.loads(line[len("STATS "):])

    cold = run_child()
    assert cold["lowerings"] >= 1 and cold["stores"] >= 1
    assert cold["disk_hits"] == 0
    warm = run_child()
    assert warm["lowerings"] == 0, warm
    assert warm["disk_hits"] >= 1 and warm["stores"] == 0
    # same executable → same numbers
    assert warm["probe"] == cold["probe"]
    entries = os.listdir(tmp_path / "cache")
    assert [n for n in entries if n.endswith(_SUFFIX)]
