"""Traffic kind ``advance``: closed loop, one caller, back-to-back
``run_solution(t, t + steps - 1)`` on a directly prepared solution,
each call ending in ``block_until_ready``.  A unit is one whole call.
"""

import numpy as np

import device_state


class Kind:
    SPAN = "bench.call"    # the span a traced unit runs in

    def __init__(self, cell):
        self.cell = cell
        self.steps = int(cell.traffic["steps_per_call"])
        self.t = 0

    def build(self):
        """The README's own flow (``yk_factory`` -> ``new_solution`` ->
        ``apply_command_line_options`` -> ``prepare_solution``), then
        the seeded state: through the public var API where that fills on
        the devices (sharded modes), else by ``device_state``."""
        from yask_tpu import yk_factory
        cell, cfg = self.cell, self.cell.config
        fac = yk_factory()
        env = fac.new_env()
        ctx = fac.new_solution(env, stencil=cfg["stencil"],
                               radius=int(cfg["radius"]))
        dom = cell.domain
        ctx.apply_command_line_options(
            f"-g_x {dom[0]} -g_y {dom[1]} -g_z {dom[2]} "
            f"-mode {cfg['mode']} -wf_steps {int(cfg['wf_steps'])}")
        for dim, n in zip("xyz", cfg["ranks"]):
            if int(n) > 1:
                ctx.set_num_ranks(dim, int(n))
        ctx.prepare_solution()
        var = ctx.get_var(cell.stencil.STATE_VAR)
        slots = (var.get_last_valid_step_index()
                 - var.get_first_valid_step_index() + 1)
        if slots != cell.stencil.SLOTS:
            raise SystemExit(
                f"benchmark: {cfg['stencil']} keeps {slots} ring slots, "
                f"the reference's seeding law states "
                f"{cell.stencil.SLOTS}")
        consts = {k: float(v) for k, v in cfg.get("consts", {}).items()}
        if ctx._resident is not None:
            # sharded modes: the public fills run on the devices
            for name, value in consts.items():
                ctx.get_var(name).set_all_elements_same(value)
            var.set_elements_in_seq(cell.fill["scale"])
            var.set_element(cell.fill["amplitude"],
                            [0] + cell.fill["source"])
        else:
            for name, value in consts.items():
                device_state.install(ctx, name, dom, cell.fill, 1, value)
            device_state.install(ctx, cell.stencil.STATE_VAR, dom,
                                cell.fill, slots)
        self.ctx, self.var = ctx, var

    def run_unit(self) -> int:
        """One whole call; returns the steps it advanced."""
        self.ctx.run_solution(self.t, self.t + self.steps - 1)
        self.t += self.steps
        return self.steps

    def read_box(self, lo, hi):
        """The newest time level in rows ``[lo, hi)``."""
        if self.ctx._resident is None and self.ctx._state_on_device:
            return device_state.read_box(self.ctx, self.var.get_name(),
                                         lo, hi)
        t = self.var.get_last_valid_step_index()
        return np.asarray(self.var.get_elements_in_slice(
            [t] + list(lo), [t] + [h - 1 for h in hi]))

    def plan(self):
        """The plan that ran, for the log: the built kernel's tiling
        record where the mode has one."""
        til = self.ctx._built_pallas_tiling()
        if til is None:
            return None
        keys = ("interpret", "fuse_steps", "block", "skew", "skew_dims",
                "pipeline_dmas", "pipeline_out", "tile_bytes",
                "margin_overhead", "overlap_exchange")
        return {k: til[k] for k in keys if k in til}

    def close(self):
        self.ctx.end_solution()
