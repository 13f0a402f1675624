"""VMEM-feasibility pass: the static budget model, per ladder rung.

Runs the REAL pallas planner (``build_pallas_chunk(plan_only=True)``)
for each VMEM-budget rung the configuration may use and applies the
live-value model on top: Mosaic holds live SSA values and spill slots
beside the tiles, so a kernel whose tiles fit the planning budget can
still die in compile when its scoped need exceeds the limit the runtime
passes (``vmem_limit_bytes``, 128 MiB at every default budget) — the
register-spill OOM that cost a round-3 hardware session at 512³ r=8
K=2.  The need is the capability table's, by the plan's fuse depth and
the program's stage count (``BackendCapability.vmem_need_bytes``: each
row names the chip's acceptance/refusal pair it came from; a class
without a row needs twice its tiles) — the SAME model the build tests
its own candidates with, so a planner-chosen plan of a measured class
cannot spill and an explicit block that would is refused by the build
(``VMEM-SPILL`` either way).  That class is flagged ``error`` here,
statically, before any launch.

The plan dict already accounts for input rings, workspace, scratch,
skew carry rings, and pipeline parity staging (input prefetch doubling
+ parity-doubled output tiles), because it comes from the planner
itself — the model cannot drift from the code it predicts.
"""

from __future__ import annotations

from yask_tpu.backend import get_capability
from yask_tpu.checker.diagnostics import CheckReport
from yask_tpu.utils.exceptions import YaskException

PASS = "vmem"

#: spill-headroom fraction: live ≥ this share of the limit gets a warn
#: even when it still fits, where the budget was raised above the
#: class's default (leave margin for the model's own error).
_NEAR_LIMIT = 0.9


# THE limit formula the kernel's CompilerParams uses — not a mirror,
# the same function (hoisted into pallas_stencil so the model cannot
# drift from the runtime)
from yask_tpu.ops.pallas_stencil import vmem_limit_bytes  # noqa: F401,E402


def checker_budget(ctx) -> int:
    """The budget the static model evaluates: the explicit ``-vmem_mb``
    knob, else the REAL-TPU default — the checker answers Mosaic
    feasibility, so the CPU-interpret planning budget (a loose 100 MiB,
    VMEM emulated) must not leak in when the check runs on a CPU
    host."""
    opts = ctx._opts
    if opts.vmem_budget_mb > 0:
        return opts.vmem_budget_mb * 2 ** 20
    return checker_default_budget(ctx, max(opts.wf_steps, 1))


def checker_default_budget(ctx, fuse_steps: int) -> int:
    """The TPU's default budget for a ``fuse_steps``-deep kernel of
    this solution (no knob): the bar above which a near-limit plan
    earns the margin warning."""
    return get_capability().plan_budget_bytes(
        fuse_steps, len(ctx._ana.stages),
        len(ctx._ana.tile_scratch))


def budget_rungs(ctx) -> list:
    """The VMEM budgets (bytes) this configuration may plan with: the
    explicit ``-vmem_mb`` knob, else the auto-tuner's ladder when it
    will sweep one, else the TPU default."""
    opts = ctx._opts
    if opts.vmem_budget_mb > 0:
        return [opts.vmem_budget_mb * 2 ** 20]
    if opts.do_auto_tune and getattr(opts, "tune_vmem_ladder", False):
        from yask_tpu.runtime.auto_tuner import AutoTuner
        return [mb * 2 ** 20 for mb in AutoTuner.VMEM_LADDER_MIB]
    return [checker_budget(ctx)]


def plan_pallas(ctx, program, budget: int):
    """One plan_only planner run at the context's configured (K, block,
    skew) for ``budget`` — shared by this pass and the explain pass.
    For shard_pallas the PER-SHARD program is planned (rank domain +
    radius×K ghost pads, skew restricted to unsharded dims), mirroring
    ``_prep_shard_pallas`` — the global program is not what the inner
    kernel tiles."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    opts = ctx._opts
    K = max(opts.wf_steps, 1)
    # what _get_pallas_chunk passes (block, skew, the push_memory
    # knob's resolution): the plan must reflect the
    # tiling the runtime would actually choose
    args = dict(ctx._pallas_build_args(K), vmem_budget=budget,
                plan_only=True)
    if ctx._mode == "shard_pallas":
        ana = ctx._ana
        dims = ana.domain_dims
        nr = {d: opts.num_ranks[d] for d in dims}
        rad = ana.fused_step_radius()
        hK = {d: rad.get(d, 0) * K for d in dims}
        local_prog = ctx._csol.plan(
            opts.rank_domain_sizes, global_sizes=opts.global_domain_sizes,
            extra_pad={d: (hK[d], hK[d]) for d in dims})
        unsh = tuple(d for d in dims[:-1] if nr.get(d, 1) == 1)
        return build_pallas_chunk(local_prog, distributed=True,
                                  unsharded_dims=unsh, **args)
    return build_pallas_chunk(program, **args)


def _classify_plan_error(msg: str) -> str:
    if msg.startswith("pallas fuse_steps"):
        return "PAD-COVERAGE"
    if msg.startswith("no feasible pallas block"):
        return "PALLAS-BLOCK-FIT"
    if msg.startswith("pallas pipelined tiles need"):
        return "VMEM-PIPE-OVER-BUDGET"
    if msg.startswith("pallas tile needs"):
        return "VMEM-TILE-OVER-BUDGET"
    if "skewed wavefront needs" in msg:
        return "SKEW-INFEASIBLE"
    if msg.startswith("push-memory fusion infeasible"):
        return "PIPELINE-PUSH-INFEASIBLE"
    return "PLAN-FAILED"


def check_vmem(report: CheckReport, ctx, program) -> None:
    report.ran(PASS)
    mode = ctx._mode
    if mode not in ("pallas", "shard_pallas"):
        report.add("VMEM-SKIPPED", "info",
                   f"mode '{mode}' allocates no Pallas VMEM tiles")
        return

    for budget in budget_rungs(ctx):
        mb = budget / 2 ** 20
        limit = vmem_limit_bytes(budget)
        try:
            plan = plan_pallas(ctx, program, budget)
        except YaskException as e:
            vm = getattr(e, "vmem", {})
            # tiles inside the budget whose modelled scoped need is over
            # the class's room: the build refused what Mosaic would
            rule = ("VMEM-SPILL" if vm.get("over") == "room"
                    else _classify_plan_error(str(e)))
            report.add(rule, "error",
                       f"rung {mb:.0f} MiB: {e}",
                       detail={"vmem_budget": budget, "message": str(e),
                               "vmem_limit": limit, **vm,
                               "live_model_bytes":
                                   vm.get("scoped_need_bytes")})
            continue
        tile = plan["tile_bytes"]
        factor = plan["live_factor"]
        live = plan["scoped_need_bytes"]
        det = {"vmem_budget": budget, "vmem_limit": limit,
               "tile_bytes": tile, "live_factor": factor,
               "result_bytes": plan["result_bytes"],
               "live_model_bytes": live,
               "block": plan["block"], "fuse_steps": plan["fuse_steps"],
               "in_tile_bytes": plan["in_tile_bytes"],
               "work_bytes": plan["work_bytes"],
               "carry_bytes": plan["carry_bytes"],
               "ostage_bytes": plan["ostage_bytes"],
               "push": plan.get("push", False),
               "push_vars": plan.get("push_vars", []),
               "push_tile_bytes": plan.get("push_tile_bytes", 0)}
        if live > limit:
            report.add(
                "VMEM-SPILL", "error",
                f"rung {mb:.0f} MiB: live-value model "
                f"{live / 2**20:.1f} MiB ({factor:g} × "
                f"{tile / 2**20:.1f} MiB tiles at K="
                f"{plan['fuse_steps']}) exceeds the scoped Mosaic limit "
                f"{limit / 2**20:.0f} MiB — the round-3 register-spill "
                "OOM class (spill slots > vmem_limit); shrink block, "
                "fuse_steps, or the budget", detail=det)
        elif (budget > checker_default_budget(ctx, plan["fuse_steps"])
              and live > _NEAR_LIMIT * limit):
            # only above the class's default budget: at or below it the
            # default is DESIGNED to leave the model's headroom
            report.add(
                "VMEM-SPILL-MARGIN", "warn",
                f"rung {mb:.0f} MiB: live-value model "
                f"{live / 2**20:.1f} MiB is within "
                f"{100 * (1 - _NEAR_LIMIT):.0f}% of the "
                f"{limit / 2**20:.0f} MiB scoped limit; the "
                f"{factor:g}× model has error bars — expect possible "
                "Mosaic OOM", detail=det)
        else:
            report.add(
                "VMEM-OK", "info",
                f"rung {mb:.0f} MiB: tiles {tile / 2**20:.1f} MiB, "
                f"live model {live / 2**20:.1f} MiB of "
                f"{limit / 2**20:.0f} MiB limit "
                f"(block {plan['block']}, K={plan['fuse_steps']})",
                detail=det)
