#!/usr/bin/env python
"""JSON-lines front for the stencil server (stdio or TCP socket).

One long-lived process hosts a :class:`yask_tpu.serve.StencilServer`;
clients speak newline-delimited JSON.  Every request line is an object
with an ``op`` and an optional client-chosen ``id`` echoed back on the
response line; responses carry ``ok: true`` or ``ok: false`` +
``error``.

Ops::

    {"op": "open", "stencil": "iso3dfd", "radius": 2, "g": 16,
     "mode": "jit", "wf": 2, "options": "", "session": null}
        -> {"ok": true, "sid": "s0000"}
    {"op": "fill", "sid": ..., "var": "vel", "value": 0.5}
    {"op": "fill", "sid": ..., "var": "pressure",
     "first": [0,0,0,0], "last": [0,15,15,15],
     "data": [...flat...], "shape": [1,16,16,16], "dtype": "float32"}
    {"op": "read", "sid": ..., "var": ..., "first": [...], "last": [...]}
    {"op": "init", "sid": ...}          # init_solution_vars
    {"op": "prewarm", "sid": ..., "steps": 8}
    {"op": "run", "sid": ..., "first": 0, "last": 3, "outputs": [],
     "flush_every": 0, "stream_outputs": false}
    {"op": "run_many", "requests": [{"sid":..., "first":..., "last":...,
                                     "outputs": []}, ...]}
        # submit-all-then-wait-all: the shape that actually exercises
        # the micro-batching window
    {"op": "metrics"} / {"op": "cache_stats"}
    {"op": "ping"}                      # liveness heartbeat (fleet
                                        # supervision; cheap, no device
                                        # work)
    {"op": "snapshot", "sid": ...}      # interior-coordinate checkpoint
        -> {"ok": true, "meta": {...}, "state": {var: [slot...]}}
    {"op": "restore", "sid": ..., "meta": {...}, "state": {...}}
    {"op": "close", "sid": ...}
    {"op": "shutdown"}

``open`` takes an optional ``bucket`` (true/false/null = the
``YT_SERVE_BUCKETING`` default) — shape-bucket co-batching per
``yask_tpu/serve/buckets.py``.

**Streaming**: a ``run``/``run_many`` with ``flush_every > 0`` emits
interleaved ``{"stream": true, "id": ..., "sid": ..., "step": ...}``
lines on the SAME connection as each chunk boundary flushes (with the
partial interiors when ``stream_outputs`` is set), BEFORE the final
response line.  Clients must collect/skip ``stream`` lines until a
line without ``"stream"`` arrives — ``tools/serve_client.py`` does.

Arrays cross the wire as ``{"shape": [...], "dtype": "float32",
"data": [flat row-major floats]}``.  float32 values round-trip EXACTLY
through JSON doubles, so the bit-identity self-checks in
``tools/serve_client.py`` / ``examples/serve_sweep_main.py`` hold
across the process boundary.

This front performs no device work itself — every op is a
``StencilServer`` method call (the guarded sites live inside the
serve package), which is also what keeps the BARE-DEVICE-CALL lint
closure clean here.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _encode_array(a) -> dict:
    a = np.asarray(a)
    return {"shape": list(a.shape), "dtype": str(a.dtype),
            "data": [float(x) for x in a.ravel().tolist()]}


def _decode_array(d: dict):
    return np.asarray(d["data"],
                      dtype=np.dtype(d.get("dtype", "float32"))
                      ).reshape(d.get("shape", [-1]))


def _worker_chaos() -> None:
    """YT_FAULT_PLAN chaos hooks for the fleet supervision tests.  An
    injected ``worker_dead`` at site ``fleet.kill_worker`` hard-exits
    the worker process (SIGKILL semantics: no cleanup, no reply on the
    pipe — exactly what a crashed worker looks like to the front); a
    ``hang`` at ``fleet.hang_worker`` stalls it past the front's
    liveness deadline.  Probed at op entry, at every chunk-boundary
    stream flush (so a kill can land MID-run), and on ``ping``."""
    from yask_tpu.resilience.faults import WorkerDead, fault_point
    try:
        fault_point("fleet.kill_worker")
    except WorkerDead:
        os._exit(17)
    fault_point("fleet.hang_worker")


def _encode_stream_event(ev: dict) -> dict:
    out = {"step": ev.get("step")}
    if "outputs" in ev:
        out["outputs"] = {k: _encode_array(v)
                          for k, v in ev["outputs"].items()}
    return out


def _encode_response(resp) -> dict:
    out = {"ok": resp.ok, "rid": resp.rid, "session": resp.session,
           "status": resp.status, "batch": resp.batch,
           "batched": resp.batched, "mode": resp.mode,
           "degraded": resp.degraded,
           "queue_secs": resp.queue_secs, "run_secs": resp.run_secs,
           "respond_secs": resp.respond_secs,
           "compile_secs": resp.compile_secs,
           "cache_hit": resp.cache_hit,
           "outputs": {k: _encode_array(v)
                       for k, v in resp.outputs.items()}}
    if resp.error:
        out["error"] = resp.error
    if resp.anomaly:
        out["anomaly"] = resp.anomaly
    if resp.bucket:
        out["bucket"] = resp.bucket
    if resp.preempted:
        out["preempted"] = int(resp.preempted)
    if resp.streams:
        out["streams"] = [_encode_stream_event(e) for e in resp.streams]
    if resp.trace:
        out["trace"] = resp.trace
    return out


class ServeFront:
    """Dispatch table from wire ops to server methods."""

    #: ops that may emit interleaved ``{"stream": true}`` lines.
    _STREAMING_OPS = ("run", "run_many")

    def __init__(self, server):
        self.server = server
        self.closing = threading.Event()

    def handle(self, msg: dict, emit=None) -> dict:
        op = msg.get("op")
        fn = getattr(self, f"op_{op}", None)
        if fn is None:
            return {"ok": False, "error": f"unknown op {op!r}"}
        from yask_tpu.obs.tracer import activate
        try:
            # a front-stamped trace id rides the wire msg; activating it
            # here makes every journal row / span this op produces join
            # the SAME end-to-end trace ("" = no-op passthrough)
            with activate(msg.get("trace", "")):
                out = fn(msg, emit) if op in self._STREAMING_OPS \
                    else fn(msg)
        except Exception as e:  # noqa: BLE001 - the front must answer
            out = {"ok": False,
                   "error": f"{type(e).__name__}: {e}"}
        if "id" in msg:
            out["id"] = msg["id"]
        return out

    def op_open(self, msg):
        from yask_tpu.serve.api import Overloaded
        try:
            sid = self.server.open_session(
                stencil=msg["stencil"], radius=msg.get("radius"),
                g=msg.get("g", 16), mode=msg.get("mode", "jit"),
                wf=int(msg.get("wf", 2)),
                options=msg.get("options", ""),
                session=msg.get("session"), bucket=msg.get("bucket"))
        except Overloaded as e:
            # brownout tier 2 / saturation: a STRUCTURED rejection —
            # clients key on "overloaded" and honor the Retry-After
            # hint instead of parsing the error string
            return {"ok": False, "error": f"Overloaded: {e}",
                    "overloaded": True,
                    "retry_after": float(e.retry_after)}
        return {"ok": True, "sid": sid}

    def op_fill(self, msg):
        if "value" in msg:
            self.server.set_var(msg["sid"], msg["var"],
                                float(msg["value"]))
            return {"ok": True}
        n = self.server.set_var_slice(
            msg["sid"], msg["var"], _decode_array(msg),
            msg["first"], msg["last"])
        return {"ok": True, "elements": int(n)}

    def op_read(self, msg):
        buf = self.server.get_var_slice(msg["sid"], msg["var"],
                                        msg["first"], msg["last"])
        return {"ok": True, **_encode_array(buf)}

    def op_init(self, msg):
        self.server.init_vars(msg["sid"])
        return {"ok": True}

    def op_prewarm(self, msg):
        n = self.server.prewarm(msg["sid"], int(msg.get("steps", 1)))
        return {"ok": True, "chunks": int(n)}

    def _req(self, m):
        from yask_tpu.obs.tracer import current_trace_id
        from yask_tpu.serve import ServeRequest
        return ServeRequest(session=m["sid"],
                            first_step=int(m["first"]),
                            last_step=(None if m.get("last") is None
                                       else int(m["last"])),
                            outputs=tuple(m.get("outputs", ())),
                            deadline_secs=float(m.get("deadline", 0.0)),
                            flush_every=int(m.get("flush_every", 0)),
                            stream_outputs=bool(
                                m.get("stream_outputs", False)),
                            trace=m.get("trace")
                            or current_trace_id())

    @staticmethod
    def _stream_hook(emit, sid, rid):
        """The per-request flush hook: push one ``{"stream": true}``
        line.  Defensive — a dropped client must cost the beacon, not
        the run (the scheduler's flush policy, extended to the wire)."""
        def push(ev):
            _worker_chaos()  # a chaos kill lands at a chunk boundary
            line = {"stream": True, "sid": sid,
                    **_encode_stream_event(ev)}
            if rid is not None:
                line["id"] = rid
            try:
                emit(line)
            except Exception:  # noqa: BLE001
                pass
        return push

    def op_run(self, msg, emit=None):
        _worker_chaos()
        req = self._req(msg)
        hook = None
        if emit is not None and req.flush_every > 0:
            hook = self._stream_hook(emit, req.session, msg.get("id"))
        h = self.server.submit(req, on_stream=hook)
        return _encode_response(
            self.server.wait(h, timeout=msg.get("timeout")))

    def op_run_many(self, msg, emit=None):
        # submit EVERYTHING before waiting on anything — this is what
        # lands compatible requests inside one batching window
        handles = []
        for m in msg["requests"]:
            req = self._req(m)
            hook = None
            if emit is not None and req.flush_every > 0:
                hook = self._stream_hook(emit, req.session,
                                         msg.get("id"))
            handles.append(self.server.submit(req, on_stream=hook))
        resps = [self.server.wait(h, timeout=msg.get("timeout"))
                 for h in handles]
        return {"ok": True,
                "responses": [_encode_response(r) for r in resps]}

    def op_ping(self, msg):
        _worker_chaos()
        return {"ok": True, "pid": os.getpid(),
                "sessions": len(self.server.registry.sessions())}

    def op_snapshot(self, msg):
        snap = self.server.snapshot(msg["sid"])
        return {"ok": True, "meta": snap["meta"],
                "state": {k: [_encode_array(a) for a in ring]
                          for k, ring in snap["state"].items()}}

    def op_restore(self, msg):
        snap = {"meta": msg["meta"],
                "state": {k: [_decode_array(d) for d in ring]
                          for k, ring in msg["state"].items()}}
        ok = self.server.restore(msg["sid"], snap)
        out = {"ok": bool(ok)}
        if not ok:
            out["error"] = "snapshot did not apply (identity mismatch)"
        return out

    def op_metrics(self, msg):
        return {"ok": True, "metrics": self.server.metrics()}

    def op_metrics_snapshot(self, msg):
        # the fleet front's telemetry poll: the full registry snapshot
        # (raw histogram windows included — the merge pools samples,
        # it never averages percentiles) + occupancy/cache/journal/SLO
        return {"ok": True, "snapshot": self.server.metrics_snapshot()}

    def op_cache_stats(self, msg):
        from yask_tpu.cache import cache_dir, stats
        return {"ok": True, "stats": stats(),
                "cache_dir": cache_dir()}

    def op_close(self, msg):
        self.server.close_session(msg["sid"])
        return {"ok": True}

    def op_shutdown(self, msg):
        self.closing.set()
        return {"ok": True}


def _serve_stream(front: ServeFront, rfile, wfile) -> None:
    """One JSON-lines conversation (stdio, or one socket client).
    Stream events fire from the scheduler's worker thread while this
    thread blocks in ``wait``, so all writes go through one lock."""
    wlock = threading.Lock()

    def emit(obj: dict) -> None:
        with wlock:
            wfile.write(json.dumps(obj, sort_keys=True) + "\n")
            wfile.flush()

    for line in rfile:
        line = line.strip()
        if not line:
            continue
        try:
            msg = json.loads(line)
        except ValueError as e:
            out = {"ok": False, "error": f"bad JSON: {e}"}
        else:
            out = front.handle(msg, emit=emit)
        emit(out)
        if front.closing.is_set():
            return


def _serve_socket(front: ServeFront, host: str, port: int) -> None:
    srv = socket.create_server((host, port))
    srv.settimeout(0.5)
    sys.stderr.write(f"serve: listening on {host}:{srv.getsockname()[1]}\n")
    sys.stderr.flush()
    threads = []
    try:
        while not front.closing.is_set():
            try:
                conn, _addr = srv.accept()
            except socket.timeout:
                continue
            rfile = conn.makefile("r", encoding="utf-8")
            wfile = conn.makefile("w", encoding="utf-8")
            t = threading.Thread(target=_serve_stream,
                                 args=(front, rfile, wfile),
                                 daemon=True)
            t.start()
            threads.append(t)
    finally:
        srv.close()
        for t in threads:
            t.join(timeout=2.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="JSON-lines stencil-serving front")
    ap.add_argument("--port", type=int, default=None,
                    help="listen on a TCP port (default: stdio)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--window_ms", type=float, default=None,
                    help="batching window override (YT_SERVE_WINDOW_MS)")
    ap.add_argument("--max_batch", type=int, default=None,
                    help="occupancy cap override (YT_SERVE_MAX_BATCH)")
    ap.add_argument("--journal", default=None,
                    help="serve journal path (YT_SERVE_JOURNAL)")
    ap.add_argument("--no-preflight", action="store_true",
                    help="skip the checker's serve pass on open_session")
    args = ap.parse_args(argv)

    from yask_tpu.serve import StencilServer
    server = StencilServer(
        journal_path=args.journal,
        window_secs=(None if args.window_ms is None
                     else args.window_ms / 1000.0),
        max_batch=args.max_batch,
        preflight=not args.no_preflight)
    front = ServeFront(server)
    try:
        if args.port is not None:
            _serve_socket(front, args.host, args.port)
        else:
            sys.stderr.write("serve: ready (stdio)\n")
            sys.stderr.flush()
            _serve_stream(front, sys.stdin, sys.stdout)
    finally:
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
