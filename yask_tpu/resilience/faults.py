"""Fault classes, classification, outage breaker, and fault injection.

Device work fails in a handful of ways (a backend that drops mid-run,
a Mosaic OOM under the joint tuner).  This module is the one policy
every device-facing caller shares (the run loop, the auto-tuner, the
serve scheduler, the checker's journal):

* a small closed **set** of :class:`Fault` subclasses
  (:class:`BackendUnavailable`, :class:`DeviceHang`, :class:`CompilerOOM`,
  :class:`CompileFailed`, :class:`ResultAnomaly`);
* :func:`classify` mapping raw backend exceptions onto it (the message
  signatures were probed on real v5e sessions — see the auto-tuner's
  round-3 OOM postmortem);
* :class:`Breaker` — the consecutive-failure circuit breaker (a dead
  backend makes EVERY attempt fail; three in a row must stay loud
  instead of silently striking out the whole walk/matrix);
* **fault injection** via the ``YT_FAULT_PLAN`` environment variable:
  named call sites invoke :func:`fault_point` / :func:`maybe_corrupt`
  so hangs, backend drops, compiler OOMs, and corrupted (all-zero/NaN)
  outputs can be driven by fast CPU tests — the machinery that guards
  hardware runs must itself be testable without hardware.

``YT_FAULT_PLAN`` accepts JSON (``[{"site": "session.validate.*",
"kind": "backend_unavailable", "after": 2, "times": 99}]``) or the compact form
``site:kind[:times[:after]]`` with ``;`` between entries.  ``site``
patterns are :mod:`fnmatch` globs against the site names listed in
``docs/resilience.md``.  Each entry fires on hits ``after < n <=
after + times`` of a matching site, counted per process.
"""

from __future__ import annotations

import fnmatch
import json
import os
import time
from typing import Dict, List, Optional

__all__ = [
    "Fault", "BackendUnavailable", "DeviceHang", "CompilerOOM", "CompileFailed",
    "ResultAnomaly", "WorkerDead", "WorkerUnhealthy", "LoadSpike",
    "FAULT_KINDS",
    "classify", "classify_message", "Breaker", "default_breaker_path",
    "fault_point", "maybe_corrupt", "reset_faults", "active_plan",
]


class Fault(Exception):
    """Base of the closed set of fault classes.  Carries the site that raised
    it and (when classified from a raw exception) the original cause."""

    kind = "fault"

    def __init__(self, msg: str, site: Optional[str] = None,
                 cause: Optional[BaseException] = None):
        super().__init__(msg)
        self.site = site
        self.cause = cause


class BackendUnavailable(Fault):
    """The backend (or the transport to it) is unreachable: connection
    resets, RST_STREAM terminations, gRPC UNAVAILABLE/DEADLINE errors.
    Retryable.  Whether a directly attached chip can raise it at all
    is an open question (ROADMAP D5)."""
    kind = "backend_unavailable"


class DeviceHang(Fault):
    """Work exceeded its deadline (backend init or a compile/dispatch
    that never returns).  Retryable once; repeated hangs mean the
    window is gone."""
    kind = "device_hang"


class CompilerOOM(Fault):
    """The candidate does not fit the chip.  Mosaic VMEM exhaustion
    (scoped allocations over ``vmem_limit_bytes`` — the round-3 crash
    class; libtpu 0.0.34 words it "RESOURCE_EXHAUSTED: XLA:TPU compile
    permanent error. Ran out of memory in memory space vmem. Used
    136.76M of 128.00M vmem"), and equally a program whose temporaries
    do not fit HBM at load ("RESOURCE_EXHAUSTED: Error loading program
    …: Attempting to reserve 11.21G at the bottom of memory").  NOT
    retryable and never an outage signal: genuinely infeasible."""
    kind = "compiler_oom"


class CompileFailed(Fault):
    """Backend/Mosaic compile failure without a VMEM signature.  Not
    retryable per-candidate, but consecutive failures feed the outage
    breaker (a dead backend surfaces as INTERNAL compile errors)."""
    kind = "compile_failed"


class ResultAnomaly(Fault):
    """Device work returned values that fail the sanity guards
    (all-zero field, NaN/Inf, oracle mismatch — the round-3 all-zero
    quick-matrix incident)."""
    kind = "result_anomaly"


class WorkerDead(Fault):
    """A serve-fleet worker process exited (crash, OOM-kill, injected
    chaos kill).  Not retryable against the dead worker; the fleet
    supervisor fails the routed sessions over to a replacement."""
    kind = "worker_dead"


class WorkerUnhealthy(Fault):
    """A serve-fleet worker missed its heartbeat/liveness deadline
    (hung pipe, wedged backend) without exiting.  The supervisor
    SIGKILLs the process group and treats it as :class:`WorkerDead`."""
    kind = "worker_unhealthy"


class LoadSpike(Fault):
    """An injected traffic burst: the load harness probes
    ``fault_point("load.arrival")`` before each open-loop arrival and
    answers a raised LoadSpike with an immediate burst of extra
    requests.  Unlike the device faults this is demand-side chaos —
    nothing is broken, the offered load just jumped — so it is never
    retryable and never feeds the outage breaker."""
    kind = "load_spike"


FAULT_KINDS = {cls.kind: cls for cls in
               (BackendUnavailable, DeviceHang, CompilerOOM, CompileFailed,
                ResultAnomaly, WorkerDead, WorkerUnhealthy, LoadSpike)}

# Message signatures, most specific first.  A Mosaic OOM message also
# matches the INTERNAL/compile signs, so the OOM test must win (the
# auto-tuner's round-3 postmortem ordering).  The OOM signs were
# re-checked against libtpu 0.0.34 on a v5e (PR 21): both messages
# quoted on CompilerOOM classify.
_OOM_SIGNS = ("RESOURCE_EXHAUSTED",)
_OOM_SIGNS_LOWER = ("vmem",)
_UNAVAILABLE_SIGNS = ("DEADLINE_EXCEEDED", "UNAVAILABLE", "RST_STREAM",
                "stream terminated", "failed to connect",
                "Connection reset", "Socket closed", "socket closed")
_COMPILE_SIGNS = ("Mosaic", "INTERNAL", "tpu_compile")


def classify_message(msg: str) -> Optional[type]:
    """Map an exception message onto a Fault class (None = unknown)."""
    low = msg.lower()
    if any(s in msg for s in _OOM_SIGNS) \
            or any(s in low for s in _OOM_SIGNS_LOWER):
        return CompilerOOM
    if any(s in msg for s in _UNAVAILABLE_SIGNS):
        return BackendUnavailable
    if any(s in msg for s in _COMPILE_SIGNS):
        return CompileFailed
    return None


def classify(exc: BaseException,
             site: Optional[str] = None) -> Optional[Fault]:
    """Classify a raw exception into the fault classes.

    Fault instances pass through unchanged (injection raises them
    directly); anything else is classified by message signature.
    Returns None for exceptions that are not a device/backend failure —
    callers must re-raise those (a ``KeyError`` in our own code must
    never be retried as if the backend blinked)."""
    if isinstance(exc, Fault):
        return exc
    cls = classify_message(f"{type(exc).__name__}: {exc}")
    if cls is None:
        return None
    f = cls(f"{type(exc).__name__}: {exc}", site=site, cause=exc)
    return f


def default_breaker_path() -> str:
    """Sidecar file for persistent breaker state (``YT_BREAKER_STATE``
    overrides; default ``BREAKER_STATE.json`` next to the journal)."""
    explicit = os.environ.get("YT_BREAKER_STATE")
    if explicit:
        return explicit
    from yask_tpu.resilience.journal import repo_root
    return os.path.join(repo_root(), "BREAKER_STATE.json")


class Breaker:
    """Consecutive-failure circuit breaker (the auto-tuner's 3-failure
    rule, hoisted to one shared definition).  ``record`` faults as they
    happen and ``reset`` on any success; once ``tripped``, the caller
    should abort the enclosing walk/session — every further attempt is
    burning chip time against a dead backend.

    With ``path`` set, state (count + last fault kind) persists to an
    atomic JSON sidecar and is reloaded on construction, so a
    restarted session does not reset an open breaker; any success is
    the legitimate reset.  Sidecar I/O failures are swallowed: persistence is a convenience,
    never a new failure mode."""

    def __init__(self, threshold: int = 3, path: Optional[str] = None):
        self.threshold = threshold
        self.path = path
        self.consecutive = 0
        self.last: Optional[Fault] = None
        if path:
            self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                d = json.load(f)
            self.consecutive = max(0, int(d.get("consecutive", 0)))
            cls = FAULT_KINDS.get(d.get("last_kind", ""))
            if cls is not None:
                self.last = cls(str(d.get("last_msg", "")))
        except (OSError, ValueError, TypeError):
            pass

    def _persist(self) -> None:
        if not self.path:
            return
        try:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"consecutive": self.consecutive,
                           "threshold": self.threshold,
                           "tripped": self.tripped,
                           "last_kind": getattr(self.last, "kind", None),
                           "last_msg": (str(self.last)[:200]
                                        if self.last else ""),
                           "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                               time.gmtime())}, f)
            os.replace(tmp, self.path)
        except OSError:
            pass

    def record(self, fault: Fault) -> bool:
        """Count one fault; returns whether the breaker is now open."""
        self.consecutive += 1
        self.last = fault
        self._persist()
        return self.tripped

    def reset(self) -> None:
        self.consecutive = 0
        self._persist()

    @property
    def tripped(self) -> bool:
        return self.consecutive >= self.threshold


# ---------------------------------------------------------------------------
# fault injection (YT_FAULT_PLAN)

#: corruption kinds understood by maybe_corrupt (everything else raises
#: at fault_point).
_CORRUPT_KINDS = ("zero_output", "nan_output")

_STATE: Dict = {"raw": None, "entries": []}


def _parse_plan(raw: str) -> List[Dict]:
    raw = raw.strip()
    if not raw:
        return []
    if raw.startswith("["):
        entries = json.loads(raw)
    else:
        entries = []
        for part in raw.split(";"):
            part = part.strip()
            if not part:
                continue
            bits = part.split(":")
            if len(bits) < 2:
                raise ValueError(
                    f"YT_FAULT_PLAN entry {part!r}: want site:kind"
                    "[:times[:after]]")
            e = {"site": bits[0], "kind": bits[1]}
            if len(bits) > 2:
                e["times"] = int(bits[2])
            if len(bits) > 3:
                e["after"] = int(bits[3])
            entries.append(e)
    out = []
    for e in entries:
        kind = e.get("kind", "")
        if kind not in FAULT_KINDS and kind not in _CORRUPT_KINDS \
                and kind not in ("exception", "hang"):
            raise ValueError(f"YT_FAULT_PLAN: unknown fault kind "
                             f"{kind!r}")
        out.append({"site": e.get("site", "*"), "kind": kind,
                    "times": int(e.get("times", 1)),
                    "after": int(e.get("after", 0)),
                    "secs": float(e.get("secs", 3600.0)),
                    "_seen": 0})
    return out


def _entries() -> List[Dict]:
    raw = os.environ.get("YT_FAULT_PLAN", "")
    if raw != _STATE["raw"]:
        _STATE["raw"] = raw
        _STATE["entries"] = _parse_plan(raw)
    return _STATE["entries"]


def reset_faults() -> None:
    """Forget parsed plan + hit counters (test isolation helper)."""
    _STATE["raw"] = None
    _STATE["entries"] = []


def active_plan() -> List[Dict]:
    """The parsed injection entries (empty without YT_FAULT_PLAN)."""
    return list(_entries())


def _firing(site: str, kinds=None) -> Optional[Dict]:
    for e in _entries():
        if kinds is not None and e["kind"] not in kinds:
            continue
        if not fnmatch.fnmatch(site, e["site"]):
            continue
        e["_seen"] += 1
        if e["after"] < e["_seen"] <= e["after"] + e["times"]:
            return e
    return None


def fault_point(site: str) -> None:
    """Raise (or hang on) the planned fault at a named site.  A no-op
    without a matching ``YT_FAULT_PLAN`` entry — every call is cheap
    enough to leave in production paths."""
    e = _firing(site, kinds=set(FAULT_KINDS) | {"exception", "hang"})
    if e is None:
        return
    kind = e["kind"]
    if kind == "hang":
        # an interruptible stall: the deadline machinery (guard.py)
        # must convert this into a DeviceHang
        time.sleep(e["secs"])
        return
    if kind == "exception":
        raise RuntimeError(f"injected exception at {site}")
    if kind == "backend_unavailable":
        raise BackendUnavailable(
            f"injected backend drop at {site} "
            "(UNAVAILABLE: failed to connect)", site=site)
    if kind == "device_hang":
        raise DeviceHang(f"injected hang at {site}", site=site)
    if kind == "compiler_oom":
        raise CompilerOOM(
            f"injected OOM at {site} (RESOURCE_EXHAUSTED: Ran out of "
            "memory in memory space vmem)", site=site)
    if kind == "compile_failed":
        raise CompileFailed(f"injected Mosaic compile failure at "
                            f"{site}", site=site)
    if kind == "result_anomaly":
        raise ResultAnomaly(f"injected result anomaly at {site}",
                            site=site)
    if kind == "worker_dead":
        raise WorkerDead(f"injected worker death at {site}", site=site)
    if kind == "worker_unhealthy":
        raise WorkerUnhealthy(f"injected unhealthy worker at {site}",
                              site=site)
    if kind == "load_spike":
        raise LoadSpike(f"injected load spike at {site}", site=site)


def maybe_corrupt(site: str, value):
    """Return ``value`` (an ndarray, or a var→ring-of-arrays state
    dict) corrupted per the plan — all-zero or NaN — or unchanged.
    Producers call this on outputs right before the sanity guards, so
    the round-3 all-zero incident is replayable end to end."""
    e = _firing(site, kinds=set(_CORRUPT_KINDS))
    if e is None:
        return value
    import numpy as np

    def corrupt(a):
        a = np.array(a, copy=True)
        a[...] = 0.0 if e["kind"] == "zero_output" else np.nan
        return a

    if isinstance(value, dict):
        return {k: [corrupt(a) for a in ring]
                for k, ring in value.items()}
    return corrupt(value)
