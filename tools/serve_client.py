#!/usr/bin/env python
"""Client for the JSON-lines serving front (``tools/serve.py``).

Two transports:

* :meth:`ServeClient.spawn` — launch ``tools/serve.py`` as a child
  process and talk over its stdio pipes (the examples' shape: no
  ports, dies with the parent);
* :meth:`ServeClient.connect` — TCP to a ``--port`` server.

Arrays cross the wire as flat float lists + shape + dtype
(float32 round-trips exactly through JSON doubles), so a client-side
comparison against a local oracle can demand bit-identity.

Usage::

    with ServeClient.spawn() as c:
        sid = c.open(stencil="iso3dfd", radius=2, g=16, mode="jit")
        c.fill(sid, "vel", 0.5)
        c.fill_slice(sid, "pressure", arr, [0,0,0,0], [0,15,15,15])
        resps = c.run_many([(sid, 0, 3)])     # batches on the server
        out = resps[0]["outputs"]["pressure"] # numpy, decoded
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
SERVE_PY = os.path.join(_HERE, "serve.py")


def encode_array(a) -> Dict:
    a = np.asarray(a)
    return {"shape": list(a.shape), "dtype": str(a.dtype),
            "data": [float(x) for x in a.ravel().tolist()]}


def decode_array(d: Dict):
    return np.asarray(d["data"],
                      dtype=np.dtype(d.get("dtype", "float32"))
                      ).reshape(d.get("shape", [-1]))


class ServeClientError(RuntimeError):
    """Raised on a transport drop or an ``ok: false`` answer.  When
    the server ANSWERED (as opposed to dying mid-op), the structured
    response dict rides on :attr:`response` so callers — the fleet
    front in particular — can pass status/anomaly fields through
    instead of flattening them into an error string."""
    response: Optional[Dict] = None


class ServeClient:
    def __init__(self, rfile, wfile, proc: Optional[subprocess.Popen] = None,
                 sock: Optional[socket.socket] = None):
        self._r = rfile
        self._w = wfile
        self._proc = proc
        self._sock = sock
        self._next_id = 0
        #: interleaved ``{"stream": true}`` lines collected during
        #: streaming run/run_many calls (decoded), oldest first.
        self.stream_events: List[Dict] = []
        #: optional callable(event) fired as each stream line arrives.
        self.on_stream = None

    # ------------------------------------------------------ transports

    @classmethod
    def spawn(cls, extra_args: Sequence[str] = (),
              env: Optional[Dict[str, str]] = None,
              stderr=None, start_new_session: bool = False
              ) -> "ServeClient":
        """Launch ``tools/serve.py`` as a stdio child.  The child
        inherits this interpreter and environment (callers set
        ``JAX_PLATFORMS`` as the situation demands — the examples name
        the CPU path).  The fleet
        supervisor passes ``start_new_session=True`` so an unhealthy
        worker can be taken down whole with ``os.killpg`` — the
        ``run_deadlined`` SIGKILL semantics, applied to workers."""
        e = dict(os.environ if env is None else env)
        proc = subprocess.Popen(
            [sys.executable, SERVE_PY, *extra_args],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=stderr, env=e, text=True,
            start_new_session=bool(start_new_session))
        return cls(proc.stdout, proc.stdin, proc=proc)

    @classmethod
    def connect(cls, host: str = "127.0.0.1",
                port: int = 0) -> "ServeClient":
        sock = socket.create_connection((host, port))
        return cls(sock.makefile("r", encoding="utf-8"),
                   sock.makefile("w", encoding="utf-8"), sock=sock)

    # ------------------------------------------------------------ wire

    def call(self, op: str, **fields) -> Dict:
        """One op round-trip; raises :class:`ServeClientError` on a
        transport drop or an ``ok: false`` answer.  Interleaved
        ``{"stream": true}`` lines (streaming runs) are collected
        onto :attr:`stream_events` — and forwarded to
        :attr:`on_stream` — until the final response line arrives."""
        msg = {"op": op, "id": self._next_id, **fields}
        self._next_id += 1
        self._w.write(json.dumps(msg) + "\n")
        self._w.flush()
        while True:
            line = self._r.readline()
            if not line:
                raise ServeClientError(
                    f"server closed the stream during op {op!r}")
            out = json.loads(line)
            if out.get("stream"):
                if "outputs" in out:
                    out["outputs"] = {k: decode_array(v)
                                      for k, v in out["outputs"].items()}
                self.stream_events.append(out)
                cb = self.on_stream
                if cb is not None:
                    cb(out)
                continue
            break
        if not out.get("ok"):
            err = ServeClientError(
                out.get("error") or f"op {op!r} failed: {out}")
            err.response = out
            raise err
        return out

    # ------------------------------------------------------------- ops

    def open(self, stencil: str, radius: Optional[int] = None, g=16,
             mode: str = "jit", wf: int = 2, options: str = "",
             session: Optional[str] = None,
             bucket: Optional[bool] = None) -> str:
        return self.call("open", stencil=stencil, radius=radius, g=g,
                         mode=mode, wf=wf, options=options,
                         session=session, bucket=bucket)["sid"]

    def fill(self, sid: str, var: str, value: float) -> None:
        self.call("fill", sid=sid, var=var, value=float(value))

    def fill_slice(self, sid: str, var: str, buf, first, last) -> int:
        return self.call("fill", sid=sid, var=var,
                         first=list(first), last=list(last),
                         **encode_array(buf))["elements"]

    def read_slice(self, sid: str, var: str, first, last):
        return decode_array(self.call("read", sid=sid, var=var,
                                      first=list(first),
                                      last=list(last)))

    def init_vars(self, sid: str) -> None:
        self.call("init", sid=sid)

    def prewarm(self, sid: str, steps: int) -> int:
        return self.call("prewarm", sid=sid, steps=steps)["chunks"]

    def run(self, sid: str, first: int, last: Optional[int] = None,
            outputs: Sequence[str] = (),
            timeout: Optional[float] = None,
            flush_every: int = 0, stream_outputs: bool = False) -> Dict:
        out = self.call("run", sid=sid, first=first, last=last,
                        outputs=list(outputs), timeout=timeout,
                        flush_every=int(flush_every),
                        stream_outputs=bool(stream_outputs))
        return self._decode_resp(out)

    def run_many(self, requests: Sequence[Tuple],
                 outputs: Sequence[str] = (),
                 timeout: Optional[float] = None) -> List[Dict]:
        """Submit-all-then-wait-all; ``requests`` is a sequence of
        ``(sid, first, last)`` or ``(sid, first, last, extra)``
        tuples (``extra`` = dict of per-request fields like
        ``flush_every`` / ``stream_outputs``).  Compatible requests
        co-batch inside the server's window."""
        reqs = []
        for r in requests:
            sid, first, last = r[0], r[1], r[2]
            m = {"sid": sid, "first": first, "last": last,
                 "outputs": list(outputs)}
            if len(r) > 3 and r[3]:
                m.update(r[3])
            reqs.append(m)
        out = self.call("run_many", requests=reqs, timeout=timeout)
        return [self._decode_resp(r) for r in out["responses"]]

    @staticmethod
    def _decode_resp(out: Dict) -> Dict:
        out["outputs"] = {k: decode_array(v)
                          for k, v in out.get("outputs", {}).items()}
        for ev in out.get("streams", ()):
            if "outputs" in ev:
                ev["outputs"] = {k: decode_array(v)
                                 for k, v in ev["outputs"].items()}
        return out

    def ping(self) -> Dict:
        """Liveness heartbeat (fleet supervision)."""
        return self.call("ping")

    def metrics(self) -> Dict:
        return self.call("metrics")["metrics"]

    def cache_stats(self) -> Dict:
        """The worker's process-wide compile-cache counters
        (``yask_tpu.cache.stats()``) — ``lowerings == 0`` on a
        warm-started worker is the fleet acceptance probe."""
        return self.call("cache_stats")

    def close_session(self, sid: str) -> None:
        self.call("close", sid=sid)

    # ------------------------------------------------------- lifecycle

    def shutdown(self) -> None:
        try:
            self.call("shutdown")
        except ServeClientError:
            pass  # already gone

    def close(self) -> None:
        try:
            self.shutdown()
        finally:
            for f in (self._w, self._r):
                try:
                    f.close()
                except Exception:  # noqa: BLE001
                    pass
            if self._sock is not None:
                self._sock.close()
            if self._proc is not None:
                try:
                    self._proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
                    self._proc.wait()

    def __enter__(self) -> "ServeClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
