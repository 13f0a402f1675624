"""Traffic kind ``served``: closed loop, one client, one session of an
in-process ``StencilServer`` opened in set-up; back-to-back
``srv.run(sid, t, t + steps - 1, outputs=(var,))``, the whole newest
field returned each time.  A unit is one whole request, timed at the
client.  A request that is not ``ok``, or that the server answered in
another mode than the cell's, fails.
"""

import os

import numpy as np

import check


class Kind:
    SPAN = "bench.request"    # the span a traced unit runs in

    def __init__(self, cell):
        self.cell = cell
        self.steps = int(cell.traffic["steps_per_request"])
        self.t = 0
        self.last = None

    def build(self):
        from yask_tpu import yk_factory
        from yask_tpu.serve import StencilServer
        cell, cfg = self.cell, self.cell.config
        dom = cell.domain
        if len(set(dom)) != 1:
            raise SystemExit("benchmark: a served session is cubic")
        fac = yk_factory()
        self.srv = StencilServer(
            env=fac.new_env(), factory=fac, journal_path=os.path.join(
                cell.scratch, "SERVE_JOURNAL.jsonl"))
        self.mode = cell.traffic["mode"]
        self.sid = self.srv.open_session(
            stencil=cfg["stencil"], radius=int(cfg["radius"]), g=dom[0],
            mode=self.mode, wf=int(cell.traffic["wf_steps"]), bucket=False)
        for name, value in cfg.get("consts", {}).items():
            self.srv.set_var(self.sid, name, float(value))
        # the client uploads its seeded wavefield, level by level
        self.name = cell.stencil.STATE_VAR
        zero, top = [0, 0, 0], list(dom)
        levels = check.initial_levels(cell.stencil, dom, zero, top,
                                      cell.fill)
        for back, level in enumerate(reversed(levels)):
            self.srv.set_var_slice(self.sid, self.name, level,
                                   [-back] + zero,
                                   [-back] + [d - 1 for d in dom])

    def run_unit(self) -> int:
        resp = self.srv.run(self.sid, self.t, self.t + self.steps - 1,
                            outputs=(self.name,))
        if resp.status != "ok" or resp.mode != self.mode or resp.degraded:
            raise RuntimeError(
                f"served request {self.t}..{self.t + self.steps - 1}: "
                f"status={resp.status} mode={resp.mode} "
                f"degraded={resp.degraded} error={resp.error!r} "
                f"anomaly={resp.anomaly}")
        self.t += self.steps
        self.last = resp.outputs[self.name]
        return self.steps

    def read_box(self, lo, hi):
        """Rows ``[lo, hi)`` of the snapshot the last request
        returned."""
        return np.asarray(self.last[tuple(slice(a, b)
                                          for a, b in zip(lo, hi))])

    def plan(self):
        return None

    def close(self):
        self.srv.shutdown()
