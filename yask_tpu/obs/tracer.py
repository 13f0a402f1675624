"""Span tracer: the repo's correlation spine, one call, three sinks.

:func:`span` is the one call a site makes.  It feeds

* **the profiler's clock, always**: every span enters a
  ``jax.profiler.TraceAnnotation("yt.<name>", **scalar attrs)``.  With
  no profiler session open that costs a few microseconds (no clock
  read, no I/O); the moment any session runs (``jax.profiler.
  start_trace``, the benchmark's ``--trace 1``) the span is an event of
  the host plane, on the same clock as the device planes.  No gate, no
  environment variable.
* **``TRACE_EVENTS.jsonl``, behind ``YT_TRACE``**: schema
  ``yask_tpu.trace/1`` -- one row per completed span (repo root,
  ``YT_TRACE_EVENTS`` override)::

    {"v": "yask_tpu.trace/1",
     "trace":  "t4f2...",          # trace id — one per request/run
     "span":   "s07ab...",         # this span
     "parent": "s0000...",         # "" at the root
     "name":   "run.chunk",
     "phase":  "compute",          # compile|exchange|compute|dma|
                                   # checkpoint|queue|front|tune|guard|
                                   # setup
     "ts":     1754486400.123,     # wall-clock epoch seconds (cross-
                                   # process placement; monotonic bases
                                   # differ between processes)
     "dur":    0.0123,             # perf_counter-measured seconds
     "pid":    1234, "tid": 5678,
     "attrs":  {...}}              # structured, producer-specific

  Unless ``YT_TRACE`` is truthy :func:`span` yields a shared null
  handle: no id generation, no clock reads, no file I/O, and no file is
  ever created (asserted by test).
* **the kept record, where the site says ``keep=True``**: the set-up
  sites (import, env, solution, prepare, fills, builds: tens a run,
  none on the path of a steady call).  A kept span always reads
  ``time.perf_counter()`` at entry and exit -- the clock of
  ``StencilContext.call_log()``'s ``t0`` and of whoever times the
  program from outside -- and on exit appends one plain row to a ring
  of the process (:data:`KEPT_MAX` rows, oldest dropped), read with
  :func:`kept_spans`::

    {"name": "setup.prepare", "phase": "setup",
     "t0": 1234.5,                 # perf_counter at entry
     "secs": 0.81,                 # perf_counter-measured, = "dur"
     "tid": 5678,
     "parent": "",                 # name of the enclosing KEPT span on
                                   # that thread, "" at the top
     "attrs": {...}}               # the scalar attrs, Span.set's too

  No profiler session and no ``YT_TRACE`` needed: it is how set-up,
  which runs before any profiler is started, is read.  A span that is
  not kept never touches the record.

The rule for call sites: at most one span per device launch, never
inside traced/jitted code (there, ``jax.named_scope`` and
``pl.pallas_call(name=)`` name the device side).  Only scalar attrs
(str/int/float/bool) reach the annotation and the kept record;
``Span.set`` and :func:`record_span` (retroactive: an annotation
cannot be back-dated) reach the JSONL and the kept record alone.

Trace *ids* are independent of the enable gate: :func:`activate`
installs an upstream id (e.g. one stamped on a wire message by the
fleet front) in thread-local state so :func:`stamp_trace` can join
journal rows to the trace even in processes that do not write
spans themselves.

I/O discipline mirrors the serve journal: append-only, never raises
(an answer must not depend on evidence I/O), malformed lines skipped
on read, and :func:`compact_if_large` bounds growth
(``YT_TRACE_MAX_MB``, bad values fall back to the default, never
raises) by atomically keeping the newest tail of whole lines.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import uuid
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

TRACE_SCHEMA = "yask_tpu.trace/1"
TRACE_BASENAME = "TRACE_EVENTS.jsonl"

#: canonical phase vocabulary — the obs_report breakdown groups on it.
PHASES = ("compile", "exchange", "compute", "dma", "checkpoint",
          "queue", "front", "tune", "guard", "setup")

_TRUTHY = ("1", "on", "true", "yes")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def trace_enabled() -> bool:
    """True when span *writing* is on (``YT_TRACE`` truthy).  Read
    from the environment on every call so tests can monkeypatch."""
    return os.environ.get("YT_TRACE", "").strip().lower() in _TRUTHY


def default_trace_path() -> str:
    return os.environ.get("YT_TRACE_EVENTS") or os.path.join(
        _repo_root(), TRACE_BASENAME)


def trace_max_bytes() -> int:
    """Compaction threshold (``YT_TRACE_MAX_MB``, default 64 MiB).
    Bad values fall back to the default — same contract as the
    journals' ``compact_if_large``."""
    try:
        mb = float(os.environ.get("YT_TRACE_MAX_MB", "") or 64.0)
        if mb <= 0:
            mb = 64.0
    except ValueError:
        mb = 64.0
    return int(mb * (1 << 20))


def new_trace_id() -> str:
    return "t" + uuid.uuid4().hex[:15]


def _new_span_id() -> str:
    return "s" + uuid.uuid4().hex[:15]


# ------------------------------------------------------------- context
# Thread-local: the active trace id plus the open-span stack.  Worker
# threads/processes join an upstream trace via activate(); nothing is
# inherited implicitly (the scheduler's device thread activates the
# request's id explicitly around each batch).
_tls = threading.local()


def _stack() -> List[str]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_trace_id() -> str:
    """The active trace id ("" when none)."""
    return getattr(_tls, "trace", "") or ""


def current_span_id() -> str:
    st = _stack()
    return st[-1] if st else ""


def set_trace(trace_id: str) -> None:
    _tls.trace = trace_id or ""


@contextmanager
def activate(trace_id: str) -> Iterator[str]:
    """Install ``trace_id`` as the thread's active trace for the
    duration (no-op passthrough on an empty id).  This is how an id
    stamped on a wire message by the fleet front propagates into a
    worker's journal rows via :func:`stamp_trace`."""
    if not trace_id:
        yield ""
        return
    prev = current_trace_id()
    _tls.trace = trace_id
    try:
        yield trace_id
    finally:
        _tls.trace = prev


def stamp_trace(row: Dict) -> Dict:
    """Set ``row["trace_id"]`` when a trace id is active; returns the
    row either way.  Journal append sites call this so every
    artifact joins against TRACE_EVENTS — repo_lint's TRACE-ID rule
    checks the call is present."""
    tid = current_trace_id()
    if tid:
        row["trace_id"] = tid
    return row


# --------------------------------------------------------------- spans
class Span:
    """A live span handle; ``set()`` merges attrs before close."""

    __slots__ = ("trace", "span", "parent", "name", "phase", "attrs",
                 "_t_wall", "_t0")

    def __init__(self, trace: str, parent: str, name: str, phase: str,
                 attrs: Dict, ids: bool = True):
        self.trace = trace
        self.span = _new_span_id() if ids else ""
        self.parent = parent
        self.name = name
        self.phase = phase
        self.attrs = attrs
        self._t_wall = time.time()
        self._t0 = time.perf_counter()

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self


class _NullSpan:
    """Shared no-op handle yielded when tracing is off."""

    __slots__ = ()
    trace = span = parent = name = phase = ""
    attrs: Dict = {}

    def set(self, **attrs) -> "_NullSpan":
        return self


_NULL = _NullSpan()

_compact_checked = False


#: the attr types an annotation and the kept record take as they are
_SCALARS = (str, int, float, bool)


def _jsonable(v):
    if isinstance(v, _SCALARS) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return repr(v)


def _write_row(row: Dict) -> None:
    """Append one span row; never raises (evidence I/O must not cost
    an answer).  First write per process checks the size bound."""
    global _compact_checked
    path = default_trace_path()
    try:
        if not _compact_checked:
            _compact_checked = True
            compact_if_large(path)
        with open(path, "a") as f:  # lint: trace-id-ok
            f.write(json.dumps(row, sort_keys=True) + "\n")
    except (OSError, ValueError, TypeError):
        pass


#: prefix of every span's name on the profiler's host plane
ANNOTATION_PREFIX = "yt."

_annotation_cls = None


class _NoAnnotation:
    """Stands in where ``jax.profiler`` cannot be imported (a reader
    tool in an environment without jax): spans still reach the JSONL
    sink."""

    __slots__ = ()

    def __init__(self, _name, **_attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False


def _annotation(name: str, attrs: Dict):
    """``TraceAnnotation("yt.<name>", **scalar attrs)``, resolved on
    first use so that importing the tracer does not import jax."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation_cls = TraceAnnotation
        except ImportError:     # a reader tool without jax installed
            _annotation_cls = _NoAnnotation
    return _annotation_cls(
        ANNOTATION_PREFIX + name,
        **{k: v for k, v in attrs.items() if isinstance(v, _SCALARS)})


# ------------------------------------------------------ the kept record
#: rows the process keeps; the oldest goes when one more arrives
KEPT_MAX = 1024

_kept = collections.deque(maxlen=KEPT_MAX)
_kept_lock = threading.Lock()


def _kept_stack() -> List[str]:
    """Names of the kept spans open on this thread, outermost first."""
    st = getattr(_tls, "kept", None)
    if st is None:
        st = _tls.kept = []
    return st


def _keep_row(name: str, phase: str, t0: float, secs: float,
              parent: str, attrs: Dict) -> None:
    row = {"name": name, "phase": phase, "t0": float(t0),
           "secs": float(secs), "tid": threading.get_ident(),
           "parent": parent,
           "attrs": {k: v for k, v in attrs.items()
                     if isinstance(v, _SCALARS) or v is None}}
    with _kept_lock:
        _kept.append(row)


def kept_spans() -> List[Dict]:
    """The kept record, oldest row first (copies: a reader may edit
    them): every ``keep=True`` span that has ended, the newest
    :data:`KEPT_MAX` of them."""
    with _kept_lock:
        return [dict(r, attrs=dict(r["attrs"])) for r in _kept]


def process_age() -> Optional[float]:
    """Seconds since this process started, by the kernel's clock
    (``/proc/self/stat`` field 22 against ``/proc/uptime``, to a clock
    tick); None where they cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the command (field 2) may hold spaces: count from its ")"
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class span:  # noqa: N801 - a context manager used like a function
    """Open a span: always a profiler annotation ``yt.<name>`` (a few
    microseconds while no profiler session is open), a JSONL row
    when ``YT_TRACE`` is set, and a row of the kept record where the
    site says ``keep=True`` -- otherwise one env lookup and the
    shared null handle: no clocks, ids, or I/O."""

    __slots__ = ("_ann", "_args", "_sp", "_prev_trace", "_keep",
                 "_tracing", "_kept_parent")

    def __init__(self, name: str, phase: str = "", trace: str = "",
                 keep: bool = False, **attrs):
        self._ann = _annotation(name, attrs)
        self._args = (name, phase, trace, attrs)
        self._sp = None
        self._keep = keep

    def __enter__(self):
        self._ann.__enter__()
        tracing = self._tracing = trace_enabled()
        if not (tracing or self._keep):
            return _NULL
        name, phase, trace, attrs = self._args
        tid = parent = ""
        if tracing:
            tid = trace or current_trace_id() or new_trace_id()
            parent = current_span_id()
        sp = self._sp = Span(
            tid, parent, name, phase,
            {k: _jsonable(v) for k, v in attrs.items()}, ids=tracing)
        if tracing:
            self._prev_trace = current_trace_id()
            _tls.trace = tid
            _stack().append(sp.span)
        if self._keep:
            kept = _kept_stack()
            self._kept_parent = kept[-1] if kept else ""
            kept.append(name)
        return sp

    def __exit__(self, *exc):
        sp = self._sp
        if sp is not None:
            dur = time.perf_counter() - sp._t0
            if self._tracing:
                _stack().pop()
                _tls.trace = self._prev_trace
                _write_row({"v": TRACE_SCHEMA, "trace": sp.trace,
                            "span": sp.span, "parent": sp.parent,
                            "name": sp.name, "phase": sp.phase,
                            "ts": sp._t_wall, "dur": dur,
                            "pid": os.getpid(),
                            "tid": threading.get_ident(),
                            "attrs": {k: _jsonable(v)
                                      for k, v in sp.attrs.items()}})
            if self._keep:
                _kept_stack().pop()
                _keep_row(sp.name, sp.phase, sp._t0, dur,
                          self._kept_parent, sp.attrs)
        self._ann.__exit__(*exc)
        return False


def record_span(name: str, phase: str, start_wall: float, dur: float,
                trace: str = "", parent: str = "", keep: bool = False,
                t0: Optional[float] = None, **attrs) -> None:
    """Record a retroactive span from already-measured times (e.g. the
    queue-wait interval computed at release).  No annotation (it cannot be back-dated); the
    JSONL row under the same gate and I/O discipline as live spans;
    with ``keep`` a row of the kept record at the top of its thread,
    ``t0`` being the span's ``perf_counter`` start as ``start_wall``
    is its wall-clock one (left out: it ended now)."""
    if keep:
        _keep_row(name, phase,
                  time.perf_counter() - dur if t0 is None else t0, dur,
                  "", attrs)
    if not trace_enabled():
        return
    _write_row({"v": TRACE_SCHEMA,
                "trace": trace or current_trace_id() or new_trace_id(),
                "span": _new_span_id(), "parent": parent,
                "name": name, "phase": phase,
                "ts": float(start_wall), "dur": float(dur),
                "pid": os.getpid(), "tid": threading.get_ident(),
                "attrs": {k: _jsonable(v) for k, v in attrs.items()}})


#: site-prefix → phase, for spans named after guarded_call sites.
_SITE_PHASES = (("ckpt.", "checkpoint"), ("cache.", "compile"),
                ("compile", "compile"), ("exchange", "exchange"),
                ("halo", "exchange"), ("comm", "exchange"),
                ("tuner.", "tune"), ("tune", "tune"),
                ("fleet.", "front"), ("serve.flush", "front"),
                ("state.", "dma"), ("dma", "dma"),
                ("serve.", "compute"), ("run.", "compute"),
                ("bench.", "compute"), ("session.", "compute"),
                ("multihost.", "compute"), ("pipeline.", "compute"),
                ("suite.", "compute"), ("watch.", "front"),
                ("load.", "front"))


def phase_for_site(site: str) -> str:
    for prefix, phase in _SITE_PHASES:
        if site.startswith(prefix):
            return phase
    return "guard"


# ---------------------------------------------------------------- read
def read_spans(path: Optional[str] = None) -> List[Dict]:
    """All span rows, file order; malformed lines skipped, never
    fatal (a producer may have crashed mid-write)."""
    path = path or default_trace_path()
    out: List[Dict] = []
    try:
        with open(path) as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    row = json.loads(ln)
                except ValueError:
                    continue
                if isinstance(row, dict) \
                        and row.get("v") == TRACE_SCHEMA:
                    out.append(row)
    except OSError:
        pass
    return out


def compact_if_large(path: Optional[str] = None,
                     max_bytes: Optional[int] = None) -> bool:
    """Bound file growth: when over the limit, atomically keep the
    newest tail of whole lines that fits half the limit (spans have no
    per-key identity to dedupe on — recency is the value).  Never
    raises; bad ``YT_TRACE_MAX_MB`` values use the default."""
    path = path or default_trace_path()
    try:
        limit = trace_max_bytes() if max_bytes is None \
            else int(max_bytes)
        if limit <= 0 or os.path.getsize(path) <= limit:
            return False
        with open(path, "rb") as f:
            lines = f.readlines()
        budget = limit // 2
        kept: List[bytes] = []
        total = 0
        for ln in reversed(lines):
            if total + len(ln) > budget and kept:
                break
            total += len(ln)
            kept.append(ln)
        kept.reverse()
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.writelines(kept)
        os.replace(tmp, path)
        return True
    except (OSError, ValueError):
        return False
