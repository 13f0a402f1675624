"""Portable, atomic solution checkpoints + the mode-degradation ladder.

A checkpoint is a schema-versioned snapshot (``yask_tpu.checkpoint/1``)
of the FULL ring state of a prepared solution, saved by INTERIOR
coordinates.  That rides the same invariant ``set_elements_in_seq`` /
``init_solution_vars`` ride: physical-boundary ghost cells are
identically ZERO in every execution mode, and differently-padded
contexts that agree on interiors are the same simulation.  So a
snapshot taken under one mode/padding restores bit-identically into
any other — save under ``jit``, resume under ``shard_pallas`` — which
is what makes the in-run degradation ladder
(:meth:`StencilContext._run_supervised`) and cross-process kill-resume
possible at all.

Two layers:

* in-memory: :func:`extract_snapshot` / :func:`apply_snapshot` — the
  supervision loop's rollback target (no disk I/O on the fault path
  beyond what the cadence already paid);
* on disk: :func:`save_checkpoint` / :func:`restore_checkpoint` — an
  atomic ``.npz`` (written to a tmp file + ``os.replace``, so a dying
  process can only ever leave the previous complete checkpoint or a
  stray tmp, never a torn one under the real name).  ``restore``
  returns ``False`` on ANY problem — missing, torn, corrupt, stale
  schema, wrong solution/geometry — because the fallback is always a
  fresh run, never a crash (fault sites ``ckpt.save`` /
  ``ckpt.restore`` inject exactly these failures in tests).

The npz payload is one array per ring slot (``{var}__slot{i}``,
oldest→newest) plus ``__meta__``, the JSON header as uint8 bytes.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

from yask_tpu.resilience.faults import CompilerOOM, classify, fault_point

__all__ = [
    "CKPT_SCHEMA", "extract_snapshot", "apply_snapshot",
    "pull_interiors", "save_checkpoint", "restore_checkpoint",
    "peek_checkpoint", "snapshot_mismatches", "default_ckpt_dir",
    "degradation_ladder",
]

CKPT_SCHEMA = "yask_tpu.checkpoint/1"

#: On a classified fault, retry the run under progressively simpler
#: modes: the distributed-fused flagship first sheds fusion, then the
#: mesh; single-device pallas sheds Mosaic for plain XLA.  ``jit`` is
#: the floor (and ``ref`` is an oracle, not a production mode — it
#: never degrades).
_LADDER = {
    "shard_pallas": ("shard_map", "jit"),
    "shard_map": ("jit",),
    "sharded": ("jit",),
    "pallas": ("jit",),
}


def degradation_ladder(mode: str) -> List[str]:
    """Fallback modes to try, in order, when ``mode`` faults mid-run."""
    return list(_LADDER.get(mode, ()))


def default_ckpt_dir() -> str:
    """Checkpoint directory from ``YT_CKPT_DIR`` ("" = no default)."""
    return os.environ.get("YT_CKPT_DIR", "")


def _interior_index(g, gsz):
    """Index tuple selecting the interior of one padded slot array
    (domain axes clipped to the global sizes, misc/step axes whole) —
    the same geometry walk ``compare_data`` and the trace dumps use."""
    return tuple(
        slice(g.origin[dn], g.origin[dn] + gsz[dn])
        if kind == "domain" else slice(None)
        for dn, kind in g.axes)


def _device_cut(a, idx):
    """The interior of one device array as a contiguous device array:
    the slice ``extract_outputs`` runs, one small program per array
    shape."""
    return a[idx]


def pull_interiors(run, name, ring, slots, idx):
    """Host copies of the interiors ``idx`` of slots ``slots`` of the
    device ring of ``name``, through ``run``'s record of what was
    pulled from which array (``RunState.pulled``): ``(host arrays,
    bytes that crossed, bytes reused)``.  A slot that still holds the
    array OBJECT an interior was pulled from gives that host array
    back and crosses nothing.  Every other slot is cut on the device,
    its pull started before any is awaited, and enters the record.
    Where the device has no room for the interior-sized temporaries
    (an allocation failure, at the cut or at the wait) those slots
    cross padded and are cut by a strided host copy instead.  The
    arrays are not writable: a snapshot, the next one and a response
    may hold the same one."""
    import jax
    held = {i: run.recall_pull(name, i, ring[i]) for i in slots}
    reused = sum(int(h.nbytes) for h in held.values() if h is not None)
    todo = [i for i in slots if held[i] is None]
    try:
        cuts = [_device_cut(ring[i], idx) for i in todo]
        for c in cuts:
            c.copy_to_host_async()
        pulled = [np.asarray(c) for c in cuts]
        crossed = sum(int(h.nbytes) for h in pulled)
    except jax.errors.JaxRuntimeError as e:
        if not isinstance(classify(e), CompilerOOM):
            raise
        pulled = [np.ascontiguousarray(np.asarray(ring[i])[idx])
                  for i in todo]
        crossed = sum(int(ring[i].nbytes) for i in todo)
    for i, h in zip(todo, pulled):
        h.flags.writeable = False
        run.remember_pull(name, i, ring[i], h)
        held[i] = h
    return [held[i] for i in slots], crossed, reused


def extract_snapshot(ctx) -> Dict:
    """Host-side snapshot of ``ctx``'s full ring state by interior
    coordinates: ``{"meta": {...}, "state": {var: [slot, ...]},
    "d2h_bytes": n, "reused_bytes": m}``.  The context must be
    prepared; device/resident state is materialized first.  Complete
    on return: every slot is a host array, immutable and possibly
    SHARED: the interior of a device array the run state remembers a
    pull of (:func:`pull_interiors`: the answer the last request
    returned, a read-only var, an untouched state's last snapshot) is
    that pull's array, held by both.  Every other slot is cut to the
    interior on the device and the contiguous result pulled (a sharded
    array gathers on the pull), so ``d2h_bytes``, what crossed device
    to host, is the bytes of the interiors that had to be pulled, and
    ``reused_bytes`` those of the ones that had not; a slot the device
    had no room to cut crosses padded and counts whole.  Host-resident
    state is cut in place (both 0).  A hoisted scratch var's array
    (``VarGeom.is_derived``) is no part of it: a restore leaves it
    stale and the next run rebuilds it from the restored sources."""
    ctx._check_prepared()
    ctx._materialize_state()
    gsz = ctx._opts.global_domain_sizes
    meta = {
        "schema": CKPT_SCHEMA,
        "solution": ctx.get_name(),
        "dtype": str(np.dtype(ctx._program.dtype).name),
        "domain": {d: int(gsz[d]) for d in ctx.get_domain_dim_names()},
        "rings": {},
        "axes": {},
        "cur_step": int(ctx._cur_step),
        "steps_done": int(ctx._steps_done),
    }
    state = {}
    d2h_bytes = reused_bytes = 0
    for name, ring in ctx._state.items():
        g = ctx._program.geoms[name]
        if g.is_derived:
            continue    # rebuilt from its sources, never carried
        idx = _interior_index(g, gsz)
        meta["rings"][name] = len(ring)
        meta["axes"][name] = [dn for dn, _ in g.axes]
        if ctx._state_on_device:
            state[name], crossed, reused = pull_interiors(
                ctx.get_run_state(), name, ring, range(len(ring)), idx)
            d2h_bytes += crossed
            reused_bytes += reused
        else:
            state[name] = [np.ascontiguousarray(np.asarray(a)[idx])
                           for a in ring]
            for a in state[name]:
                a.flags.writeable = False
    return {"meta": meta, "state": state, "d2h_bytes": d2h_bytes,
            "reused_bytes": reused_bytes}


def apply_snapshot(ctx, snap: Dict) -> bool:
    """Restore a snapshot into a prepared context — possibly one with a
    DIFFERENT mode/padding than the snapshot was taken under.  Each
    slot is rebuilt as a zero padded array (the ghost-zero invariant)
    with the snapshot interior set against the context's CURRENT
    geometry, then pushed to device through the normal path (shardings
    apply automatically).  Returns ``False`` — never raises — on any
    identity mismatch (schema, solution, dtype, domain sizes, ring
    depths, axis order): the caller's fallback is a fresh run."""
    try:
        meta, state = snap["meta"], snap["state"]
        if meta.get("schema") != CKPT_SCHEMA:
            return False
        ctx._check_prepared()
        if meta.get("solution") != ctx.get_name():
            return False
        dtype = ctx._program.dtype
        if meta.get("dtype") != str(np.dtype(dtype).name):
            return False
        gsz = ctx._opts.global_domain_sizes
        dom = meta.get("domain", {})
        for d in ctx.get_domain_dim_names():
            if int(dom.get(d, -1)) != int(gsz[d]):
                return False
        ctx._materialize_state()
        rings = meta.get("rings", {})
        derived = {name for name in ctx._state
                   if ctx._program.geoms[name].is_derived}
        if set(rings) != set(ctx._state) - derived:
            return False
        new_state = {}
        for name, ring in ctx._state.items():
            g = ctx._program.geoms[name]
            if name in derived:
                # stale from here on (its sources are new arrays): the
                # next run refills it
                new_state[name] = ring
                continue
            if int(rings[name]) != len(ring):
                return False
            if meta.get("axes", {}).get(name) != [dn for dn, _ in g.axes]:
                return False
            idx = _interior_index(g, gsz)
            slots = []
            for i in range(len(ring)):
                a = np.asarray(state[name][i])
                dst = np.zeros(tuple(g.shape), dtype=dtype)
                if a.shape != dst[idx].shape:
                    return False
                dst[idx] = a
                slots.append(dst)
            new_state[name] = slots
    except Exception:  # noqa: BLE001 - any malformed snapshot → False
        return False
    ctx._state = new_state
    ctx._state_on_device = False
    ctx._state_to_device()
    ctx._cur_step = int(meta.get("cur_step", 0))
    ctx._steps_done = int(meta.get("steps_done", 0))
    return True


def save_checkpoint(ctx, path: str) -> str:
    """Atomically write ``ctx``'s snapshot to ``path``.  The npz is
    written to ``path + ".tmp"`` through an open file object (so numpy
    cannot append ``.npz`` and break atomicity) and renamed into place.
    Fault site ``ckpt.save``; span ``ckpt.save`` (phase
    ``checkpoint``)."""
    from yask_tpu.obs.tracer import span
    with span("ckpt.save", phase="checkpoint", path=path) as sp:
        fault_point("ckpt.save")
        snap = extract_snapshot(ctx)
        payload = {"__meta__": np.frombuffer(
            json.dumps(snap["meta"], sort_keys=True).encode(),
            dtype=np.uint8)}
        for name, ring in snap["state"].items():
            for i, a in enumerate(ring):
                payload[f"{name}__slot{i}"] = a
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        sp.set(step=int(snap["meta"].get("cur_step", 0)),
               nvars=len(snap["state"]))
    return path


def peek_checkpoint(path: str) -> Optional[Dict]:
    """Read just the meta header of a checkpoint file; ``None`` when the
    file is missing, unreadable, or not this schema."""
    try:
        with np.load(path) as data:
            meta = json.loads(bytes(data["__meta__"]).decode())
    except Exception:  # noqa: BLE001
        return None
    if not isinstance(meta, dict) or meta.get("schema") != CKPT_SCHEMA:
        return None
    return meta


def restore_checkpoint(ctx, path: str) -> bool:
    """Load ``path`` and apply it to ``ctx``.  Returns ``False`` — never
    raises — when the file is missing/torn/corrupt, carries a stale
    schema, or does not match the context's identity: the caller falls
    back to a fresh run.  Fault site ``ckpt.restore``; span
    ``ckpt.restore`` (phase ``checkpoint``)."""
    from yask_tpu.obs.tracer import span
    with span("ckpt.restore", phase="checkpoint", path=path) as sp:
        fault_point("ckpt.restore")
        try:
            with np.load(path) as data:
                meta = json.loads(bytes(data["__meta__"]).decode())
                if not isinstance(meta, dict) \
                        or meta.get("schema") != CKPT_SCHEMA:
                    sp.set(ok=False)
                    return False
                state = {}
                for name, nslots in meta.get("rings", {}).items():
                    state[name] = [np.array(data[f"{name}__slot{i}"])
                                   for i in range(int(nslots))]
        except Exception:  # noqa: BLE001 - torn/corrupt → fresh run
            sp.set(ok=False)
            return False
        ok = apply_snapshot(ctx, {"meta": meta, "state": state})
        sp.set(ok=bool(ok))
        return ok


def snapshot_mismatches(a: Dict, b: Dict, epsilon: float = 1e-4,
                        abs_epsilon: float = 1e-7) -> int:
    """Count mismatching interior points between two snapshots with
    ``compare_data``'s mixed tolerance (|x−y| > abs_eps +
    eps·max(|x|,|y|)); shape/var-set disagreements count every point.
    The cross-process acceptance tests compare a resumed child run's
    final snapshot against an uninterrupted twin with this."""
    bad = 0
    sa, sb = a.get("state", {}), b.get("state", {})
    for name in set(sa) | set(sb):
        ra, rb = sa.get(name, []), sb.get(name, [])
        if len(ra) != len(rb):
            bad += sum(int(np.asarray(x).size) for x in ra + rb)
            continue
        for x, y in zip(ra, rb):
            x = np.asarray(x, dtype=np.float64)
            y = np.asarray(y, dtype=np.float64)
            if x.shape != y.shape:
                bad += x.size + y.size
                continue
            tol = abs_epsilon + epsilon * np.maximum(np.abs(x), np.abs(y))
            bad += int((np.abs(x - y) > tol).sum())
    return bad
