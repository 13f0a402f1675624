"""Scrape harness logs into CSV.

Counterpart of ``utils/bin/yask_log_to_csv.pl`` + ``utils/lib/YaskUtils.pm``
(reference :33-58): extract the named metrics from one or more run logs into
a CSV for performance tracking, throughput keys first (the reference ranks
"mid" throughput as the primary fitness key).

Usage::

    python -m yask_tpu.tools.log_to_csv run1.log run2.log > perf.csv
    python -m yask_tpu.tools.log_to_csv --traces [TRACE_EVENTS.jsonl] > spans.csv
"""

from __future__ import annotations

import csv
import re
import sys
from typing import Dict, List

#: Metric keys in priority order (mirrors YaskUtils.pm:40-58 ordering:
#: mid/best throughput first).
KEYS = [
    "mid-throughput (num-points/sec)",
    "best-throughput (num-points/sec)",
    "min-throughput (num-points/sec)",
    "ave-throughput (num-points/sec)",
    "stddev-throughput (num-points/sec)",
    "mid-throughput (GPts/s)",
    "throughput (num-points/sec)",
    "throughput (est-FLOPS)",
    "num-steps-done",
    "elapsed-time (sec)",
    "compile-time (sec)",
    "hbm-bytes-per-point (read+write)",
    "achieved-HBM (GB/s)",
    "hbm-roofline-fraction (%)",
    "pallas-tiling",
    "num-points-per-step",
    "domain",
]

_LINE = re.compile(r"^\s*([\w\- ()/+%]+?):\s*(.+?)\s*$")


def scrape(text: str) -> Dict[str, str]:
    """Pull the last value for each known key out of a log."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _LINE.match(line)
        if not m:
            continue
        key, val = m.group(1).strip(), m.group(2)
        if key in KEYS:
            out[key] = val
    return out


def logs_to_csv(paths: List[str], out=None) -> None:
    out = out or sys.stdout
    rows = []
    for path in paths:
        with open(path) as f:
            row = scrape(f.read())
        row["log"] = path
        rows.append(row)
    cols = ["log"] + [k for k in KEYS if any(k in r for r in rows)]
    w = csv.DictWriter(out, fieldnames=cols, extrasaction="ignore")
    w.writeheader()
    for r in rows:
        w.writerow(r)


#: Trace columns, identity → placement → timing → payload.
TRACE_COLS = [
    "trace", "span", "parent", "name", "phase",
    "ts", "dur", "pid", "tid", "attrs",
]


def traces_to_csv(path: str = "", out=None) -> int:
    """Flatten obs span rows (``TRACE_EVENTS.jsonl``, schema
    ``yask_tpu.trace/1``) to CSV — attrs as one JSON column; returns
    the number of rows written.  The spreadsheet analog of
    ``tools/obs_report.py``."""
    import json

    from yask_tpu.obs.tracer import default_trace_path, read_spans
    out = out or sys.stdout
    rows = read_spans(path or default_trace_path())
    w = csv.DictWriter(out, fieldnames=TRACE_COLS, extrasaction="ignore")
    w.writeheader()
    for r in rows:
        w.writerow({**{k: r.get(k) for k in TRACE_COLS if k != "attrs"},
                    "attrs": json.dumps(r.get("attrs", {}),
                                        sort_keys=True)})
    return len(rows)


def main() -> None:  # pragma: no cover - thin wrapper
    args = sys.argv[1:]
    if args and args[0] == "--traces":
        traces_to_csv(args[1] if len(args) > 1 else "")
        return
    if not args:
        sys.stderr.write(
            "usage: log_to_csv <log> [log...] | --traces [path]\n")
        sys.exit(2)
    logs_to_csv(args)


if __name__ == "__main__":
    main()
