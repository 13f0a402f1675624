"""Deadlines + retry: ``guarded_call`` and the killable subprocess.

Two enforcement shapes, matching how device work actually hangs here:

* :func:`deadline` / :func:`guarded_call` — in-process work under a
  SIGALRM deadline.  Interrupts Python-level stalls (injected hangs,
  polling loops, interruptible waits); a hang inside a C extension
  that never re-enters the interpreter cannot be preempted this way —
  that is what the subprocess shape is for.
* :func:`run_deadlined` — the killable subprocess: ``Popen`` in its
  own process group,
  SIGKILL the *group* on deadline (the backend plugin spawns
  grandchildren that keep pipes open after the child dies), then drain
  whatever partial output survived.

``guarded_call`` composes the whole policy: fault injection at the
named site, the deadline, classification (:func:`~yask_tpu.resilience.
faults.classify`), bounded retry with exponential backoff + jitter for
the retryable kinds, and an optional shared :class:`~yask_tpu.
resilience.faults.Breaker` so repeated failures across calls stay
loud.
"""

from __future__ import annotations

import os
import random
import signal
import subprocess
import sys
import threading
import time
from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

from yask_tpu.resilience.faults import (Breaker, DeviceHang, classify,
                                        fault_point)

__all__ = ["deadline", "guarded_call", "run_deadlined"]

#: fault kinds retried by default: the transient ones.  Compiler
#: OOM/failures are per-candidate verdicts (retrying re-runs the same
#: doomed compile), anomalies are data bugs.
RETRYABLE = ("backend_unavailable", "device_hang")


def _can_alarm() -> bool:
    return (hasattr(signal, "SIGALRM")
            and threading.current_thread() is threading.main_thread())


@contextmanager
def deadline(secs: Optional[float], site: str = "call"):
    """Hard in-process deadline: raises :class:`DeviceHang` when the
    block runs longer than ``secs``.  No-op when ``secs`` is falsy, off
    the main thread, or without SIGALRM (non-Unix) — callers that must
    not hang even then should use :func:`run_deadlined`."""
    if not secs or not _can_alarm():
        yield
        return

    def _on_alarm(signum, frame):
        raise DeviceHang(f"deadline of {secs:g}s exceeded at {site}",
                         site=site)

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    old_timer = signal.setitimer(signal.ITIMER_REAL, secs)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *(old_timer or (0.0, 0.0)))
        signal.signal(signal.SIGALRM, old_handler)


def guarded_call(fn, *args, site: str = "call",
                 deadline_secs: Optional[float] = None,
                 retries: int = 0, backoff: float = 0.5,
                 max_backoff: float = 8.0, jitter: float = 0.25,
                 retry_on: Sequence[str] = RETRYABLE,
                 breaker: Optional[Breaker] = None, **kwargs):
    """Run ``fn(*args, **kwargs)`` under the shared fault policy.

    Exceptions are classified into the fault classes; unclassified
    exceptions propagate untouched (a bug in our own code must never
    look like a backend blink).  Classified faults whose kind is in
    ``retry_on`` are retried up to ``retries`` times with exponential
    backoff (+ up to ``jitter`` relative randomization, so a fleet of
    callers does not retry in lockstep); the final fault
    is raised as its fault class with ``.cause`` holding the
    original.  ``breaker`` (when shared across calls) records every
    fault and suppresses further retries once tripped."""
    from yask_tpu.obs.tracer import phase_for_site, span
    attempt = 0
    while True:
        fault = None
        # one span per attempt (named by the fault site, phase derived
        # from it) — retries show as sibling spans, and a classified
        # fault lands in the span's attrs; unclassified exceptions
        # propagate through the span close untouched
        with span(f"guard:{site}", phase=phase_for_site(site),
                  attempt=attempt) as sp:
            try:
                with deadline(deadline_secs, site=site):
                    # inside the deadline: an injected "hang" must be
                    # converted to DeviceHang exactly like a real
                    # stall
                    fault_point(site)
                    out = fn(*args, **kwargs)
            except Exception as e:  # noqa: BLE001 - classified below
                fault = classify(e, site=site)
                if fault is None:
                    raise
                sp.set(fault=fault.kind)
        if fault is not None:
            tripped = breaker.record(fault) if breaker is not None \
                else False
            if fault.kind in retry_on and attempt < retries \
                    and not tripped:
                delay = min(backoff * (2 ** attempt), max_backoff)
                time.sleep(delay * (1.0 + jitter * random.random()))
                attempt += 1
                continue
            raise fault from (fault.cause or None)
        if breaker is not None:
            breaker.reset()
        return out


def run_deadlined(cmd: Sequence[str], deadline_secs: float,
                  site: str = "subprocess",
                  env: Optional[dict] = None,
                  stderr=subprocess.DEVNULL) -> Tuple[int, str]:
    """Run ``cmd`` in its own process group with a hard deadline.

    Returns ``(returncode, stdout)``.  On deadline the whole group is
    SIGKILLed (grandchildren included), already-produced stdout is
    drained, and a :class:`DeviceHang` carrying it as
    ``.partial_stdout`` is raised — a partial suite beats losing
    everything to the kill."""
    fault_point(site)
    proc = subprocess.Popen(
        list(cmd), stdout=subprocess.PIPE, stderr=stderr, text=True,
        start_new_session=True, env=env)
    try:
        out, _ = proc.communicate(timeout=deadline_secs)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()  # reap; cannot block after SIGKILL of the group
        try:
            out, _ = proc.communicate(timeout=5)
        except Exception:  # noqa: BLE001
            out = ""
        hang = DeviceHang(
            f"subprocess exceeded {deadline_secs:g}s deadline at "
            f"{site}: {' '.join(cmd[:3])}...", site=site)
        hang.partial_stdout = out or ""
        raise hang
    return proc.returncode, out or ""


def python_cmd(code: str) -> list:
    """``[sys.executable, "-c", code]`` — the probe-subprocess shape."""
    return [sys.executable, "-c", code]
