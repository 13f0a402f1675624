"""Persistent AOT compile cache: THE chokepoint every executable
build goes through.

The reference ships its stencils as build-once-run-many kernel
libraries (``libyask_kernel.<stencil>.<arch>.so``): compiling is a
*build step*, running is a *link*.  Here the analog was missing —
every ``(stencil, geometry, variant)`` point paid a full
trace+lower+compile on each process start, and the auto-tuner alone
re-compiles dozens of variants per session.  This module centralizes
executable construction (the Titanax chokepoint shape) and persists
compiled executables on disk so the second process start is a cache
lookup:

* :func:`aot_compile` — the one function allowed to call
  ``jax.jit(...).lower(...).compile()`` (``tools/repo_lint.py``'s
  COMPILE-DIRECT rule fails any chain outside this package).  Returns
  an :class:`AotResult` carrying the executable plus the cache verdict
  (``cache_hit``/``compile_secs``) that ``get_stats()`` reports.
* Persistence: when ``key`` is given and ``YT_COMPILE_CACHE`` names a
  directory, executables are serialized via
  ``jax.experimental.serialize_executable`` into content-addressed
  entries (sha-256 of the schema + caller key + backend fingerprint).
  Writes are atomic (tmp + ``os.replace``); entries are versioned
  (:data:`SCHEMA`) and carry the fingerprint in the body too, so the
  checker's CACHE-STALE pass can tell "stale for this jax" from
  "corrupt".  Any load/deserialize failure falls back to a fresh
  compile — a corrupt cache entry must never break a run.
* The **trace counter**: ``stats()["lowerings"]`` counts actual
  trace+lower+compile executions.  A warm process re-running a cached
  variant must show 0 — ``tests/test_cache.py`` asserts on the
  counter, not on wall-clock.
* Fault sites: disk I/O routes through ``guarded_call`` at
  ``cache.load`` / ``cache.store`` so ``YT_FAULT_PLAN`` injection can
  drive both failure paths from fast CPU tests (docs/resilience.md).

The fingerprint (jax/jaxlib versions, backend platform, the repo's
git SHA) is part of the content address: a jax upgrade
changes every digest, so stale entries become unreachable rather than
deserialize hazards.  Eviction keeps the directory bounded
(``YT_COMPILE_CACHE_MAX`` entries, oldest-mtime first).

Platform note: keyed compiles on ``cpu`` are built WITHOUT donation
(see the comment in :func:`aot_compile`) — XLA:CPU's
deserialize-as-recompile path mishandles donated aliased buffers, so
persistable executables use an alias-free convention there.  Keyed
callers must therefore pass plain functions plus ``donate_argnums``,
never a pre-jitted callable with donation baked in.
"""

from __future__ import annotations

import os
import pickle
import subprocess
import threading
import time
from dataclasses import dataclass
from hashlib import sha256
from typing import Any, Dict, Iterator, Optional, Tuple

#: entry format version; bump on any layout change so old files read
#: as stale (schema mismatch → fresh compile), never as garbage.
SCHEMA = "yask_tpu.compile_cache/1"

#: default bound on on-disk entries (override: YT_COMPILE_CACHE_MAX).
DEFAULT_MAX_ENTRIES = 64

_SUFFIX = ".aotc"

#: in-process memo (digest → executable): one compile serves every
#: context in the process, not just the one that built it.
_memo: Dict[str, Any] = {}

_STATS_KEYS = ("lowerings", "memory_hits", "disk_hits", "misses",
               "stores", "load_failures", "store_failures", "evictions")
_stats: Dict[str, int] = {k: 0 for k in _STATS_KEYS}


class CacheEntryError(Exception):
    """A persisted entry is unusable (bad schema, wrong fingerprint,
    truncated pickle).  Internal: always handled by falling back to a
    fresh compile."""


@dataclass
class AotResult:
    """What :func:`aot_compile` hands back: the runnable executable
    plus the cache verdict."""
    fn: Any                      # the compiled executable (callable)
    cache_hit: Optional[str]     # None | "memory" | "disk"
    compile_secs: float          # 0.0 on any hit
    digest: Optional[str]        # content address (None when unkeyed)
    #: what ``cache_hit`` cannot say of a build that was lowered: how
    #: the backend's part ended (:func:`_fresh_compile`), the seconds of
    #: tracing and lowering (paid hit or miss), and the seconds a hit
    #: still cost in reading and deserialising
    tier: str = "miss"           # "jax" | "uncached" | "miss"
    lower_secs: float = 0.0
    load_secs: float = 0.0


def stats() -> Dict[str, int]:
    """Snapshot of the process-wide counters.  ``lowerings`` is the
    trace counter: actual ``jit→lower→compile`` executions."""
    return dict(_stats)


def reset_stats() -> None:
    for k in _STATS_KEYS:
        _stats[k] = 0


def clear_memo() -> None:
    """Drop the in-process memo (test isolation; disk entries stay)."""
    _memo.clear()


def cache_dir() -> Optional[str]:
    """The persistent cache directory (``YT_COMPILE_CACHE``), or None
    when persistence is off (unset/empty)."""
    d = os.environ.get("YT_COMPILE_CACHE", "").strip()
    return d or None


def max_entries() -> int:
    try:
        return max(int(os.environ.get("YT_COMPILE_CACHE_MAX",
                                      str(DEFAULT_MAX_ENTRIES))), 1)
    except ValueError:
        return DEFAULT_MAX_ENTRIES


_fp_static: Dict[str, str] = {}


def git_sha() -> str:
    """Short HEAD SHA (+ '-dirty' when the tree differs), '' off-repo."""
    from yask_tpu.resilience.journal import repo_root
    root = repo_root()
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, timeout=10).stdout.strip()
        if not sha:
            return ""
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=root, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, timeout=10).stdout.strip()
        return sha + ("-dirty" if dirty else "")
    except Exception:
        return ""


def _pkg_version(name: str) -> str:
    try:
        from importlib.metadata import version
        return version(name)
    except Exception:
        return ""


def backend_fingerprint(platform: str = "") -> Dict[str, str]:
    """The jax/backend + code identity an executable is only valid
    under.  Versions come from importlib.metadata — no jax import, so
    fingerprinting never opens the backend; ``platform`` is the
    caller's ``yk_env``
    platform for the same reason; ``code`` is the repo's git SHA so a
    kernel-code change invalidates persisted executables (sessions on
    the same commit still share)."""
    if not _fp_static:
        _fp_static.update(jax=_pkg_version("jax"),
                          jaxlib=_pkg_version("jaxlib"),
                          code=git_sha() or "")
    return dict(_fp_static, platform=platform or "")


def key_digest(key, fingerprint: Dict[str, str]) -> str:
    """Content address: schema + caller key + fingerprint.  The
    fingerprint being part of the address makes a jax upgrade a clean
    miss (stale entries become unreachable, not deserialize hazards)."""
    blob = repr((SCHEMA, key, tuple(sorted(fingerprint.items()))))
    return sha256(blob.encode()).hexdigest()[:40]


def args_signature(example_args) -> Tuple:
    """Shape/dtype/SHARDING of every example-arg leaf.  An AOT
    executable is specialized to its input shardings and shapes —
    calling it with others raises — so they must be part of the
    content address alongside the caller's key: a jit-oracle chunk
    and a sharded-mode chunk over identically-padded state trace the
    same program text but compile incompatible executables."""
    from jax import tree_util

    def leaf(x):
        shp = getattr(x, "shape", None)
        if shp is not None:
            return ("arr", tuple(shp), str(getattr(x, "dtype", "")),
                    repr(getattr(x, "sharding", None)))
        return ("lit", type(x).__name__,
                repr(x) if isinstance(x, (int, float, bool, str,
                                          type(None))) else "")

    leaves, treedef = tree_util.tree_flatten(example_args)
    return (repr(treedef), tuple(leaf(v) for v in leaves))


def _execution_devices(example_args) -> list:
    """The devices a disk-loaded executable must be bound to: those of
    the example args' sharding (mesh order for a NamedSharding), else
    the default device.  Without them ``deserialize_and_load`` binds
    the executable to EVERY local device and it fails at call time."""
    import jax
    for v in jax.tree_util.tree_leaves(example_args):
        sh = getattr(v, "sharding", None)
        if sh is None:
            continue
        mesh = getattr(sh, "mesh", None)
        if mesh is not None:
            return list(mesh.devices.flat)
        return sorted(sh.device_set, key=lambda d: d.id)
    return [jax.devices()[0]]


def entry_path(digest: str, directory: Optional[str] = None) -> str:
    return os.path.join(directory or cache_dir() or ".", digest + _SUFFIX)


# ---------------------------------------------------------------------------
# disk layer (guarded: cache.load / cache.store fault sites)

def _read_entry(path: str) -> Dict:
    with open(path, "rb") as f:
        entry = pickle.load(f)
    if not isinstance(entry, dict) or entry.get("schema") != SCHEMA:
        raise CacheEntryError(
            f"bad schema in {os.path.basename(path)}: "
            f"{entry.get('schema') if isinstance(entry, dict) else type(entry)}")
    return entry


def _load_entry(path: str, fingerprint: Dict[str, str]) -> Dict:
    entry = _read_entry(path)
    if entry.get("fingerprint") != fingerprint:
        # unreachable through the content address in normal operation
        # (the fingerprint is hashed into the digest) — this guards a
        # hand-copied or tampered entry
        raise CacheEntryError(
            f"fingerprint mismatch in {os.path.basename(path)}")
    return entry


def _write_atomic(path: str, blob: bytes) -> None:
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".tmp.{os.getpid()}.{os.path.basename(path)}")
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


def _remove_quietly(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


def _evict(directory: str) -> None:
    """Drop oldest-mtime entries beyond the bound.  Best-effort: a
    racing process deleting the same file is fine."""
    try:
        names = [n for n in os.listdir(directory) if n.endswith(_SUFFIX)]
    except OSError:
        return
    cap = max_entries()
    if len(names) <= cap:
        return
    def mtime(n):
        try:
            return os.path.getmtime(os.path.join(directory, n))
        except OSError:
            return 0.0
    for n in sorted(names, key=mtime)[:len(names) - cap]:
        _remove_quietly(os.path.join(directory, n))
        _stats["evictions"] += 1


def iter_entries(directory: Optional[str] = None
                 ) -> Iterator[Tuple[str, Dict]]:
    """Yield ``(path, meta)`` for every persisted entry — meta carries
    ``schema``/``key``/``fingerprint`` (payload omitted) or
    ``{"unreadable": <why>}`` for corrupt files.  The checker's
    CACHE-STALE pass scans this; it must never raise."""
    d = directory or cache_dir()
    if not d or not os.path.isdir(d):
        return
    for n in sorted(os.listdir(d)):
        if not n.endswith(_SUFFIX):
            continue
        path = os.path.join(d, n)
        try:
            e = _read_entry(path)
            yield path, {"schema": e.get("schema"),
                         "key": e.get("key"),
                         "fingerprint": e.get("fingerprint", {})}
        except Exception as e:  # noqa: BLE001 - scan must survive junk
            yield path, {"unreadable": f"{type(e).__name__}: {e}"}


# ---------------------------------------------------------------------------
# the chokepoint

_jax_hits = threading.local()     # .n: persistent-cache hits seen
_listening = False


def _count_jax_hits() -> int:
    """This thread's count of executables JAX's own persistent cache
    has served (its ``cache_hits`` event, raised on the compiling
    thread); the listener is registered at the first call."""
    global _listening
    if not _listening:
        _listening = True
        from jax import monitoring

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                _jax_hits.n = getattr(_jax_hits, "n", 0) + 1
        monitoring.register_event_listener(on_event)
    return getattr(_jax_hits, "n", 0)


def _fresh_compile(fn, example_args, jit_kwargs) -> AotResult:
    """Lower and compile; ``tier`` says how the backend's part ended:
    ``jax`` where JAX's persistent cache served the executable
    (``load_secs``: reading and deserialising it), ``uncached`` where
    the backend compiled in less than that cache's storing threshold
    (``jax_persistent_cache_min_compile_time_secs``: no entry is kept,
    so a warm run pays it again), else ``miss``."""
    import jax
    t0 = time.perf_counter()
    # Accept pre-jitted callables (the shard builders return jax.jit
    # objects carrying their own donate_argnums): re-wrapping would
    # nest jits and silently drop inner donation.
    if not jit_kwargs and hasattr(fn, "lower"):
        lowered = fn.lower(*example_args)
    else:
        lowered = jax.jit(fn, **jit_kwargs).lower(*example_args)
    _stats["lowerings"] += 1
    t1 = time.perf_counter()
    hits = _count_jax_hits()
    exe = lowered.compile()
    t2 = time.perf_counter()
    if _count_jax_hits() > hits:
        tier, load = "jax", t2 - t1
    elif t2 - t1 < jax.config.jax_persistent_cache_min_compile_time_secs:
        tier, load = "uncached", 0.0
    else:
        tier, load = "miss", 0.0
    return AotResult(fn=exe, cache_hit=None, compile_secs=t2 - t0,
                     digest=None, tier=tier, lower_secs=t1 - t0,
                     load_secs=load)


def aot_compile(fn, example_args, *, key=None, platform: str = "",
                donate_argnums=None, static_argnums=None) -> AotResult:
    """Build (or fetch) the executable for ``fn`` at the shapes of
    ``example_args`` — the one sanctioned ``jit→lower→compile`` site.
    Every call opens a ``cache.aot`` span (phase ``compile``) whose
    attrs record the hit tier — the trace answers "did this request
    pay a lowering" without grepping stats."""
    from yask_tpu.obs.tracer import span
    with span("cache.aot", phase="compile", keep=True,
              keyed=key is not None) as sp:
        res = _aot_compile(fn, example_args, key=key,
                           platform=platform,
                           donate_argnums=donate_argnums,
                           static_argnums=static_argnums)
        # ``hit``: this cache's ``memory`` / ``disk``, else how the
        # backend's part of the build ended (``jax``: served by JAX's
        # persistent cache; ``uncached``; ``miss``)
        sp.set(hit=res.cache_hit or res.tier,
               compile_secs=round(res.compile_secs, 6),
               lower_secs=round(res.lower_secs, 6),
               load_secs=round(res.load_secs, 6),
               digest=res.digest or "")
        return res


def _aot_compile(fn, example_args, *, key=None, platform: str = "",
                 donate_argnums=None, static_argnums=None) -> AotResult:
    """The uninstrumented chokepoint (see :func:`aot_compile`).

    ``key=None``: no persistence — a plain AOT compile that still
    feeds the trace counter (per-call shapes like the shard twins,
    where the caller's own memo is the right cache).  With ``key``,
    the executable is memoized in-process and (when
    ``YT_COMPILE_CACHE`` is set) persisted across processes.  ``key``
    must fully determine the lowered program TEXT: the callers' keys
    combine stencil identity, padded state geometry, dtype, step
    count/fusion depth, mode, and the pallas variant tuple — anything
    they bake into the trace.  ``args_signature(example_args)``
    (shape/dtype/sharding per leaf) is hashed in here, so two calls
    under the same key whose inputs are placed differently can never
    share an executable.

    Every failure path (missing entry, corrupt pickle, deserialize
    error, store I/O) degrades to a fresh compile / a skipped store;
    the cache can only ever cost a compile, never a run."""
    jit_kwargs = {}
    if donate_argnums is not None:
        jit_kwargs["donate_argnums"] = donate_argnums
    if static_argnums is not None:
        jit_kwargs["static_argnums"] = static_argnums

    # XLA:CPU deserializes an executable by RECOMPILING its serialized
    # HLO, and the recompiled binary mishandles ownership of donated
    # aliased buffers: a donated passthrough output (e.g. a read-only
    # var forwarded through a scan) can alias a buffer the runtime has
    # already returned to the allocator, which then scribbles its
    # free-list header over the first bytes (probed: 8 garbage floats
    # at offset 0, nondeterministic, needs a fresh-compiled twin in
    # the same process).  Donation is a device-memory optimization
    # with no semantic effect, so every KEYED compile on cpu — the
    # ones a later process may serve from disk — drops it; fresh and
    # disk-loaded twins then share one safe, alias-free convention.
    # Unkeyed compiles are never serialized and keep their donation.
    if key is not None and platform == "cpu":
        jit_kwargs.pop("donate_argnums", None)

    if key is None:
        _stats["misses"] += 1
        return _fresh_compile(fn, example_args, jit_kwargs)

    fp = backend_fingerprint(platform)
    digest = key_digest((key, args_signature(example_args)), fp)

    if digest in _memo:
        _stats["memory_hits"] += 1
        return AotResult(fn=_memo[digest], cache_hit="memory",
                         compile_secs=0.0, digest=digest)

    d = cache_dir()
    from yask_tpu.resilience import guarded_call
    if d is not None:
        path = entry_path(digest, d)
        if os.path.exists(path):
            try:
                t0 = time.perf_counter()
                entry = guarded_call(_load_entry, path, fp,
                                     site="cache.load")
                from jax.experimental.serialize_executable import \
                    deserialize_and_load
                exe = deserialize_and_load(
                    entry["payload"], entry["in_tree"],
                    entry["out_tree"],
                    execution_devices=_execution_devices(example_args))
                _memo[digest] = exe
                _stats["disk_hits"] += 1
                return AotResult(fn=exe, cache_hit="disk",
                                 compile_secs=0.0, digest=digest,
                                 load_secs=time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 - any bad entry → recompile
                # classified faults included: a cache problem must never
                # break (or retry-loop) the run it was meant to speed up
                _stats["load_failures"] += 1
                _remove_quietly(path)

    res = _fresh_compile(fn, example_args, jit_kwargs)
    res.digest = digest
    exe = _memo[digest] = res.fn
    _stats["misses"] += 1

    if d is not None:
        try:
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(exe)
            blob = pickle.dumps({
                "schema": SCHEMA, "key": repr(key), "fingerprint": fp,
                "payload": payload, "in_tree": in_tree,
                "out_tree": out_tree})
            guarded_call(_write_atomic, entry_path(digest, d), blob,
                         site="cache.store")
            _stats["stores"] += 1
            _evict(d)
        except Exception:  # noqa: BLE001 - persistence is best-effort
            _stats["store_failures"] += 1

    return res
