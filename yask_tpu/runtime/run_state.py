"""Per-run mutable state, hoisted out of :class:`StencilContext`.

A prepared context owns two kinds of state with different lifetimes:

* the *solution* side — compiled program, geometry plan, jit cache,
  tiling records — built once by ``prepare_solution`` and valid for
  any number of runs;
* the *run* side — the var rings in the one form they rest in (global
  padded arrays; a shard mode's sharded interiors, or the padded
  shards its program left), the step position, and the run timer —
  one instance per live simulation.

This module is the run side.  ``StencilContext`` keeps its historical
attribute names (``_state``, ``_resident``, ``_cur_step``, …) as
delegating properties onto the active :class:`RunState`, so the var
APIs and every execution path read/write through it unchanged — but
the whole bundle can now be swapped: one prepared+compiled solution
serves many ensemble members (``yask_tpu.runtime.ensemble``) and
repeated runs without re-preparing.  The reference's analog is one
``yk_solution`` per simulation instance sharing a linked kernel
library; here the "library" is the AOT compile cache
(``yask_tpu.cache``) plus the context's plan.
"""

from __future__ import annotations

import gc
import resource
import statistics
import time
import weakref
from collections import deque
from typing import (Deque, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

from yask_tpu.obs.metrics import get_registry
from yask_tpu.obs.tracer import span
from yask_tpu.utils.timer import YaskTimer

#: rows a run keeps of its leaf calls, newest last (the call record)
CALL_LOG_LEN = 4096
#: a call is slow when it takes more than this many times the median
#: of the (up to) ``SLOW_LOOKBACK`` calls before it of its own
#: ``(mode, n)``: the flagship's mild stall is 1.45-1.6 x, a steady
#: window's calls differ by under 1 %.  Constants, not knobs.
SLOW_FACTOR = 1.25
SLOW_LOOKBACK = 32

_RUSAGE_WHO = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)

# What the collector cost this process so far: one ``gc.callbacks``
# hook, installed with the first call record.
_gc_total = [0.0, 0, 0.0]       # seconds, runs, start of the open run


def _on_gc(phase: str, _info: Dict) -> None:
    if phase == "start":
        _gc_total[2] = time.perf_counter()
    else:
        _gc_total[0] += time.perf_counter() - _gc_total[2]
        _gc_total[1] += 1


class CallRecord:
    """One leaf ``run_solution`` call while it runs: where the work
    happens tells it each launch's enqueue seconds, the final wait and
    a compile; :meth:`row` closes it into the plain dict the run keeps.
    ``clock`` is the harness's ``time.perf_counter`` (tests put their
    own in its place: no test of the record reads a wall clock)."""

    __slots__ = ("mode", "first", "n", "t0", "launches", "onto",
                 "wait_secs", "compiles", "_gc", "_cpu", "_ru")

    clock = staticmethod(time.perf_counter)

    def __init__(self, mode: str, first: int, n: int):
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        self.mode, self.first, self.n = mode, first, n
        self.launches: List[Tuple[int, float]] = []
        self.onto: List[int] = []
        self.wait_secs = 0.0
        self.compiles = 0
        self._gc = (_gc_total[0], _gc_total[1])
        self._ru = resource.getrusage(_RUSAGE_WHO)
        self._cpu = time.thread_time()
        self.t0 = self.clock()

    def launch(self, k: int, secs: float, onto: int = 0) -> None:
        """One ``yt.run.launch``: ``k`` steps, ``secs`` the host spent
        inside the enqueue, ``onto`` the outputs it wrote onto arrays
        an earlier launch gave up."""
        self.launches.append((k, secs))
        self.onto.append(onto)

    def row(self, device=None) -> Dict:
        """The call's row, taken at its end.  What the host did
        meanwhile is the difference between the call's two ends: a
        collector pause (``gc_secs``, ``gc_runs``), a descheduled host
        (``secs`` well above ``cpu_secs``, ``nivcsw``) and a page-in
        (``majflt``) each leave a different mark.  With ``device``,
        what its allocator holds at the call's end."""
        secs = self.clock() - self.t0
        ru = resource.getrusage(_RUSAGE_WHO)
        row = {"t0": self.t0, "secs": secs, "mode": self.mode,
               "first": self.first, "n": self.n,
               "launches": self.launches, "onto": self.onto,
               "wait_secs": self.wait_secs,
               "compiles": self.compiles,
               "gc_secs": _gc_total[0] - self._gc[0],
               "gc_runs": _gc_total[1] - self._gc[1],
               "cpu_secs": time.thread_time() - self._cpu,
               "nivcsw": ru.ru_nivcsw - self._ru.ru_nivcsw,
               "nvcsw": ru.ru_nvcsw - self._ru.ru_nvcsw,
               "majflt": ru.ru_majflt - self._ru.ru_majflt}
        stats = device.memory_stats() if device is not None else None
        if stats:
            row["bytes_in_use"] = stats.get("bytes_in_use", 0)
            if "largest_free_block_bytes" in stats:
                row["largest_free_block_bytes"] = \
                    stats["largest_free_block_bytes"]
        return row


def judge_call(before: Sequence[Dict], row: Dict) -> Dict:
    """The slow-call rule for one row against ``before``, the (up to
    ``SLOW_LOOKBACK``) earlier rows of its ``(mode, n)`` that compiled
    nothing: ``median`` of
    their seconds (``None`` with no call before it) and ``slow``.  A
    slow row is also told where its excess lies: ``worst_launch``, the
    index of the launch whose enqueue rose most over the median enqueue
    of that index (-1 without launches), its ``worst_enqueue_secs``,
    and ``held_by``: ``"launch"`` if that rise, ``"wait"`` if the
    final wait's, is the larger part of ``secs - median``, else
    ``"host"`` (the time was spent outside every launch and the
    wait)."""
    if not before:
        return {"median": None, "slow": False}
    median = statistics.median(r["secs"] for r in before)
    if row["secs"] <= SLOW_FACTOR * median:
        return {"median": median, "slow": False}

    def rise(now: float, then: List[float]) -> float:
        return now - (statistics.median(then) if then else 0.0)

    rises = [rise(secs, [r["launches"][i][1] for r in before
                         if i < len(r["launches"])])
             for i, (_k, secs) in enumerate(row["launches"])]
    worst = max(range(len(rises)), key=rises.__getitem__, default=-1)
    wait_rise = rise(row["wait_secs"], [r["wait_secs"] for r in before])
    rest = row["secs"] - median - wait_rise - sum(rises)
    held_by = max((rises[worst] if rises else 0.0, "launch"),
                  (wait_rise, "wait"), (rest, "host"))[1]
    return {"median": median, "slow": True, "worst_launch": worst,
            "worst_enqueue_secs":
                row["launches"][worst][1] if worst >= 0 else 0.0,
            "held_by": held_by}


def _judge_next(recent: Dict[Tuple, Deque[Dict]], row: Dict) -> Dict:
    """:func:`judge_call` for the next row of a log whose history by
    ``(mode, n)`` is ``recent``, which then takes the row in."""
    before = recent.setdefault((row["mode"], row["n"]),
                               deque(maxlen=SLOW_LOOKBACK))
    verdict = judge_call(before, row)
    if not row["compiles"]:     # a call that compiled is no yardstick
        before.append(row)
    return verdict


def judge_calls(rows: Iterable[Dict]) -> List[Dict]:
    """:func:`judge_call` over a whole log, oldest first: one verdict
    a row, each against the rows before it of its own class.  Pure:
    the rule the runtime applies call by call."""
    recent: Dict[Tuple, Deque[Dict]] = {}
    return [_judge_next(recent, row) for row in rows]


class RunState:
    """One live simulation's mutable state.

    Fields mirror the context attributes they replaced.  A state rests
    in exactly one of three forms (the other two are None; all three
    None: unallocated, or lost to a failed donated run):

    * ``state`` — dict var → ring (list) of padded device arrays,
      oldest→newest, the global arrays every mode but the shard modes
      computes on;
    * ``resident`` — sharded INTERIORS (pads stripped), the form a
      shard mode is allocated in and every reader outside a launch
      reads (``StencilContext._resident``); host access that needs the
      global pads materializes lazily via
      ``ctx._materialize_state()``;
    * ``padded`` — the padded SHARDS a shard program took and handed
      back (``parallel/shard_step.py RestGeom``): under the same
      ``NamedSharding``s, each global array ranks × the padded local
      extent in a split dim, with ``padded_geom`` the geometry they
      were padded to.  The next launch takes them as they lie where
      its program's geometry is that one; anything else asks
      ``ctx._resident``, which strips them once (``run.state_strips``)
      and leaves ``resident``;
    * ``state_on_device`` — whether ``state`` arrays are device
      arrays (vs host numpy);
    * ``cur_step`` — the next step index a ``run_solution`` continues
      from (var element APIs resolve ring slots against it);
    * ``steps_done`` — steps accumulated since the last
      ``clear_stats`` (the stats denominator);

    A checkpoint restore (``resilience.checkpoint.apply_snapshot``)
    rewinds ``cur_step``/``steps_done`` to the snapshot's values, but
    steps a supervised run REDOES after a rollback keep accumulating
    in ``steps_done`` and ``run_timer`` once re-run — throughput stats
    honestly charge the redone work instead of hiding it.
    * ``run_timer`` — elapsed wall-clock accounting (compile stays
      excluded).
    * ``calls`` — the call record: one row a leaf ``run_solution``
      call, the newest ``CALL_LOG_LEN`` (``StencilContext.call_log``);
      ``call`` is the record of the call that is running, for the
      launch loops to report to.  Always on, like the timers.
    * ``derived_from`` — the source ARRAYS (the objects) the hoisted
      scratch vars' arrays in ``state`` were last filled from, None
      before the first fill (``StencilContext._refresh_derived``: a
      source that is another object since makes them stale).
    * ``pulled`` — for a ``(var, slot)``, the device ARRAY (the object)
      an interior was last pulled from and the host array that came
      back (:meth:`remember_pull` / :meth:`recall_pull`).  A device
      array is immutable, so while the ring's slot still holds that
      object the host array is, byte for byte, what another pull would
      return; every write puts another object there (a run, a public
      fill, a restore).  The device side is a weak reference: the
      record never keeps alive an array the state has let go of.
    * ``spare`` — for a written var, the ring slots the one-chip Pallas
      launches gave up (``min(K, slots)`` a launch: what it evicted),
      dead since the launch that read them last was enqueued, their
      pad bands zero like every state array's.  The next launch is
      DONATED them and writes its outputs onto them
      (``context._PallasLaunch``), so a loop of launches allocates
      nothing; whoever still holds one (a ``fuse_vars`` peer) finds it
      deleted.  They are of the state's geometry and go with it:
      whatever puts another state in place drops them
      (``StencilContext._state``'s setter, :meth:`reset`).
      Where the pool is short (a run state's first launch) the launch
      makes zeroed arrays, counted in ``run.spare_made``.
    """

    def __init__(self):
        self.state: Optional[Dict[str, List]] = None
        self.resident: Optional[Dict[str, List]] = None
        self.padded: Optional[Dict[str, List]] = None
        self.padded_geom = None     # the RestGeom ``padded`` lies in
        self.state_on_device = False
        self.cur_step = 0
        self.steps_done = 0
        self.run_timer = YaskTimer()
        self.calls: Deque[Dict] = deque(maxlen=CALL_LOG_LEN)
        self.call: Optional[CallRecord] = None
        self._recent: Dict[Tuple, Deque[Dict]] = {}
        self.derived_from: Optional[Tuple] = None
        self.pulled: Dict[Tuple[str, int], Tuple] = {}
        self.spare: Dict[str, List] = {}

    def remember_pull(self, name: str, slot: int, device_array,
                      host) -> None:
        """``host`` is the interior pulled from ``device_array``, the
        object slot ``slot`` of ``name``'s ring holds.  The one entry a
        ``(var, slot)`` replaces the one before it."""
        self.pulled[(name, slot)] = (weakref.ref(device_array), host)

    def recall_pull(self, name: str, slot: int, device_array):
        """The host interior remembered for ``device_array`` in that
        slot, or None.  An entry made from another object is stale and
        leaves the record here; so does one whose array was deleted
        since (donated to a run that failed: the state is lost, and a
        pull of it raises as it always did)."""
        entry = self.pulled.get((name, slot))
        if entry is None:
            return None
        if entry[0]() is device_array and not device_array.is_deleted():
            return entry[1]
        del self.pulled[(name, slot)]
        return None

    def begin_call(self, mode: str, first: int, n: int) -> CallRecord:
        """Open the record of one leaf call."""
        self.call = rec = CallRecord(mode, first, n)
        return rec

    def end_call(self, rec: CallRecord, device=None) -> Dict:
        """Close ``rec`` into its row, judge it (:func:`judge_call`)
        and keep it.  Counted in the process registry (``run.calls``,
        ``run.call_ms``); a slow call also bumps ``run.slow_calls`` and
        leaves the marker span ``yt.run.slow`` right behind the call:
        free while no profiler session runs, an event of the host
        plane the moment one does, a JSONL row under ``YT_TRACE``."""
        self.call = None
        row = rec.row(device)
        row.update(_judge_next(self._recent, row))
        self.calls.append(row)
        reg = get_registry()
        reg.counter("run.calls").inc()
        reg.histogram("run.call_ms").observe(row["secs"] * 1e3)
        if row["slow"]:
            reg.counter("run.slow_calls").inc()
            with span("run.slow", phase="compute", first=row["first"],
                      n=row["n"], secs=row["secs"],
                      median=row["median"],
                      worst_launch=row["worst_launch"],
                      worst_enqueue_secs=row["worst_enqueue_secs"],
                      wait_secs=row["wait_secs"],
                      gc_secs=row["gc_secs"], nivcsw=row["nivcsw"],
                      compiles=row["compiles"], held_by=row["held_by"]):
                pass
        return row

    def reset(self) -> None:
        """Back to the just-prepared shape (timers/step counters keep
        accumulating — ``clear_stats`` is the explicit reset, exactly
        as on the pre-hoist context)."""
        self.state = None
        self.resident = None
        self.padded = self.padded_geom = None
        self.state_on_device = False
        self.cur_step = 0
        self.derived_from = None
        self.pulled.clear()
        self.spare.clear()

    def __repr__(self):
        return (f"<RunState step={self.cur_step} "
                f"alloc={self.state is not None} "
                f"resident={self.resident is not None} "
                f"padded={self.padded is not None}>")
