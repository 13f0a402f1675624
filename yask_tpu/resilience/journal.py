"""Journaled resume: the append-only ``SESSION_JOURNAL.jsonl``.

Round 3 lost a 26-case validation matrix when the backend dropped
mid-session — the next session restarted from stage 1 and re-ran the
banked cases.  The journal makes session progress durable: every stage
/ case appends one row the moment its outcome is known, and the next
session resumes from the first incomplete case.

Row schema (``yask_tpu.session/1``)::

    {"v": "yask_tpu.session/1",
     "stage":   "validate",            # stage name
     "case":    "iso3dfd.K2",          # "" for stage-level rows
     "attempt": 1,
     "outcome": "started|ok|anomaly|skip|fault|aborted",
     "ts":      "2026-08-05T12:00:00Z",
     "detail":  {...}}                 # outcome-specific (mismatches,
                                       # fault kind, gpts, ...)

``ok``/``anomaly``/``skip`` are terminal (``anomaly`` = the case ran to
completion but its output was quarantined — rerunning it burns a
window for data another guard already rejected); ``started``/``fault``
mean the case still needs hardware.  The file is append-only during a
session; :meth:`SessionJournal.compact` (run between windows by the
watcher) atomically rewrites it to one row per (stage, case).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

SCHEMA = "yask_tpu.session/1"
JOURNAL_BASENAME = "SESSION_JOURNAL.jsonl"

#: outcomes after which a case need not rerun.
TERMINAL_OUTCOMES = ("ok", "anomaly", "skip")

#: growth bound for month-long watch loops (YT_JOURNAL_MAX_BYTES
#: overrides): past this, session open compacts before appending.
DEFAULT_MAX_BYTES = 8 * 2 ** 20


def max_journal_bytes() -> int:
    try:
        return int(os.environ.get("YT_JOURNAL_MAX_BYTES", "")
                   or DEFAULT_MAX_BYTES)
    except ValueError:
        return DEFAULT_MAX_BYTES


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def default_journal_path() -> str:
    return os.environ.get("YT_SESSION_JOURNAL") or os.path.join(
        repo_root(), JOURNAL_BASENAME)


def _utc_now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


class SessionJournal:
    def __init__(self, path: Optional[str] = None):
        self.path = path or default_journal_path()

    # ---------------------------------------------------------- write
    def record(self, stage: str, case: str = "", outcome: str = "ok",
               attempt: int = 1, **detail) -> Dict:
        """Append one row; never fatal to the caller's own work is NOT
        the contract here — journal I/O failures raise, because a
        session that cannot journal cannot promise resume.  Rows
        inherit the thread's active trace id (``stamp_trace``) so a
        traced run's journal evidence joins TRACE_EVENTS.jsonl."""
        from yask_tpu.obs.tracer import stamp_trace
        row = {"v": SCHEMA, "stage": str(stage), "case": str(case),
               "attempt": int(attempt), "outcome": str(outcome),
               "ts": _utc_now()}
        stamp_trace(row)
        if detail:
            row["detail"] = detail
        with open(self.path, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
        return row

    # ----------------------------------------------------------- read
    def rows(self) -> List[Dict]:
        """All rows, file order == time order; malformed lines are
        skipped (a kill mid-write must not poison resume)."""
        out: List[Dict] = []
        try:
            with open(self.path) as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        row = json.loads(ln)
                    except ValueError:
                        continue
                    if isinstance(row, dict) and row.get("v") == SCHEMA:
                        out.append(row)
        except OSError:
            pass
        return out

    def last_outcomes(self) -> Dict[Tuple[str, str], Dict]:
        """Latest row per (stage, case)."""
        out: Dict[Tuple[str, str], Dict] = {}
        for row in self.rows():
            out[(row["stage"], row["case"])] = row
        return out

    def completed(self, stage: str, case: str = "") -> bool:
        row = self.last_outcomes().get((str(stage), str(case)))
        return row is not None and row["outcome"] in TERMINAL_OUTCOMES

    def attempts(self, stage: str, case: str = "") -> int:
        """Highest attempt number journaled for this case (0 = never
        started)."""
        best = 0
        for row in self.rows():
            if row["stage"] == stage and row["case"] == case:
                best = max(best, int(row.get("attempt", 1)))
        return best

    def pending(self, stage: str, cases: List[str]) -> List[str]:
        """The resume point: cases (in given order) without a terminal
        outcome — what the next session still owes."""
        done = self.last_outcomes()
        return [c for c in cases
                if done.get((stage, c), {}).get("outcome")
                not in TERMINAL_OUTCOMES]

    def session_count(self) -> int:
        """Sessions started so far (stage="session" outcome="started"
        marker rows) — the watcher's quick-vs-full window counter."""
        return sum(1 for r in self.rows()
                   if r["stage"] == "session"
                   and r["outcome"] == "started")

    # ----------------------------------------------------------- admin
    def compact(self) -> int:
        """Atomically rewrite to the latest row per (stage, case),
        preserving first-seen order; returns the number of rows
        dropped.  Run between sessions (the watcher), never during one
        — in-session the file is append-only."""
        rows = self.rows()
        latest = self.last_outcomes()
        seen = set()
        keep: List[Dict] = []
        for row in rows:
            key = (row["stage"], row["case"])
            if key in seen:
                continue
            seen.add(key)
            keep.append(latest[key])
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            for row in keep:
                f.write(json.dumps(row, sort_keys=True) + "\n")
        os.replace(tmp, self.path)
        return len(rows) - len(keep)

    def compact_if_large(self, max_bytes: Optional[int] = None) -> int:
        """Compact only when the file exceeds the growth bound
        (``YT_JOURNAL_MAX_BYTES``, default 8 MiB) — the session-open
        guard that keeps repeated sessions from growing
        the journal unboundedly.  Returns rows dropped (0 when under
        the bound or the file is missing)."""
        limit = max_journal_bytes() if max_bytes is None else max_bytes
        try:
            if os.path.getsize(self.path) <= limit:
                return 0
        except OSError:
            return 0
        return self.compact()
