"""Online auto-tuner.

Counterpart of the reference's ``AutoTuner``
(``src/kernel/lib/auto_tuner.hpp:31-132``, ``auto_tuner.cpp:206``): a
greedy neighborhood walk over the tunable execution parameters, with a
perf cache keyed by the candidate tuple and early abandonment of slower
candidates mid-trial.

On TPU the search space is the **steps fused per compiled chunk**
(``wf_steps`` — the temporal-tiling analog: longer chunks amortize
dispatch and let XLA overlap across steps, at the cost of compile time)
and, when the Pallas backend is active, its **leading-dim block shapes**
(the vector-fold/block analog) — searched jointly: from the planner's
starting point, each move doubles or halves one knob (the reference's
power-of-two radius walk), moving while any neighbor improves. Each
candidate implies one XLA/Mosaic compilation, cached by tuple exactly as
the reference caches per-size results (``auto_tuner.hpp:65``).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from yask_tpu.backend import get_capability
from yask_tpu.resilience import (Breaker, CompilerOOM, classify,
                                 fault_point)


class AutoTuner:
    #: chunk-length candidates for the K-only sweep (jit/sharded modes).
    CHUNK_CANDIDATES = (1, 2, 4, 8, 16, 32)

    #: VMEM-budget rungs (MiB) the joint walks sweep as an OUTER tuning
    #: axis when ``-vmem_mb`` is 0 (auto) and ``-tune_vmem_ladder`` is
    #: on. 64 is the conservative planning default (Mosaic live SSA
    #: values roughly double tile usage); v5e's scoped limit probed
    #: ≥120 MiB, so the upper rungs admit wider blocks (at 512³ r=8 K=2
    #: the 64→96 step is the difference between 8×32 and 16×32 x-blocks)
    #: while Mosaic VMEM OOMs on over-eager rungs are caught as
    #: infeasible candidates, never fatal.  The rungs live in the
    #: backend capability table (single source with the checker's
    #: budget sweep).
    VMEM_LADDER_MIB = get_capability().vmem_ladder_mib

    def __init__(self, ctx):
        self.ctx = ctx
        self.results: Dict[Tuple, float] = {}   # candidate → secs/step
        # Outage breaker shared across every candidate of a walk: a dead
        # backend makes EVERY compile fail, and three consecutive failures
        # must stay loud (round-3 postmortem; hoisted to the shared
        # yask_tpu.resilience.Breaker).
        self._breaker = Breaker(threshold=3)
        # vmem-ladder plan-signature dedupe: rungs whose planner output
        # AND scoped Mosaic limit agree compile identical kernels, so
        # the later rung aliases the earlier rung's measurement instead
        # of re-compiling + re-timing it (all three default rungs share
        # vmem_limit 128 MiB, so a plan the budget doesn't pinch repeats
        # three times without this).
        self._sig_keys: Dict[str, Tuple] = {}
        self.ladder_dedup_hits = 0

    @property
    def _consec_fails(self) -> int:
        return self._breaker.consecutive

    def is_done(self) -> bool:
        return getattr(self.ctx, "_tuned", False)

    def tune_if_needed(self) -> None:
        if not self.is_done():
            self.run_auto_tuner_now()

    def run_auto_tuner_now(self, candidates: Optional[List[int]] = None,
                           min_trial_secs: Optional[float] = None) -> int:
        """Search the candidate space, pick the best, and record it in
        the settings (the API twin of ``yk_solution::run_auto_tuner_now``,
        ``yk_solution_api.hpp:881``). jit/sharded modes sweep chunk
        lengths; the pallas mode walks (K, block-shape) jointly.

        Trials run on a *copy* of the solution state and are discarded:
        unlike the reference (which folds trial steps into the production
        run), replayed trial step indices would corrupt t-dependent
        stencils, so the production run re-executes its full range with
        the tuned settings and the stats/timers only ever see real steps.
        The compiled chunks are cached, so trial compilation is reused."""
        import jax.numpy as jnp
        ctx = self.ctx
        self.trial_secs = (min_trial_secs if min_trial_secs is not None
                           else ctx._opts.auto_tune_trial_secs)
        self.best_rate: Optional[float] = None

        if ctx._mode == "shard_pallas":
            # Trials run on fresh copies of the sharded interiors; the
            # production state (ctx._state / ctx._resident) is untouched.
            # An explicit candidate list becomes a K-only sweep through
            # the SAME distributed executor (never the single-device jit
            # chunk — tuning the multi-chip config on the wrong executor
            # would write a meaningless K into settings).
            saved_cur, saved_done = ctx._cur_step, ctx._steps_done
            try:
                return self._walk_joint_shard(candidates=candidates)
            finally:
                ctx._cur_step, ctx._steps_done = saved_cur, saved_done

        ctx._materialize_state()   # shard-mode runs leave state resident
        ctx._state_to_device()
        ctx._refresh_derived()     # the trials read the derived arrays
        saved_state = ctx._state
        saved_cur, saved_done = ctx._cur_step, ctx._steps_done
        # Deep-copy: compiled chunks donate their input buffers, so trials
        # must not be handed the saved arrays.
        ctx._state = {k: [jnp.copy(a) for a in ring]
                      for k, ring in saved_state.items()}
        try:
            if ctx._mode == "pallas" and candidates is None:
                best = self._walk_joint()
            else:
                best = self._sweep_k(candidates)
        finally:
            ctx._state = saved_state
            ctx._cur_step, ctx._steps_done = saved_cur, saved_done
        # After restoring the production state, shrink pads from the
        # tune_max pre-plan to the tuned K (memory; see _replan docstring).
        ctx._replan_pallas_pads(ctx._opts.wf_steps)
        return best

    # ------------------------------------------------------------------

    def _measure(self, key: Tuple, make_compiled, call=None,
                 k: Optional[int] = None) -> float:
        """Timed trial of one candidate (cached): secs/step, or inf when
        the candidate cannot compile (e.g. tile over the VMEM budget).
        A candidate clearly slower than the best is abandoned mid-trial
        (the reference's eval cutoff, ``auto_tuner.cpp:206`` region).

        ``call(compiled)`` performs one k-step trial call (state
        threading included); the default drives ``ctx._state`` — the
        shard walk supplies its own, keeping the warmup/abandonment
        policy in exactly one place."""
        import jax
        if key in self.results:
            return self.results[key]
        ctx = self.ctx
        if k is None:
            k = key[0]
        if call is None:
            dirn = ctx._ana.step_dir

            def call(compiled):
                st = compiled(ctx._state, ctx._cur_step)
                jax.block_until_ready(st)
                ctx._state = st
                ctx._cur_step += k * dirn
        from yask_tpu.utils.exceptions import YaskException
        try:
            fault_point("tuner.measure")
            compiled = make_compiled()
        except YaskException:
            # infeasible candidate (tile over the VMEM budget, fusion
            # beyond planned pads) — skip it
            self.results[key] = float("inf")
            return float("inf")
        except Exception as e:  # noqa: BLE001
            # Backend compile failures are also infeasibility signals:
            # the in-build tile model cannot see Mosaic's register-
            # allocator spill slots, so a candidate can pass the budget
            # check yet exhaust VMEM at compile time (observed on v5e:
            # "Ran out of memory in memory space vmem ... register
            # allocator spill slots", surfaced as an INTERNAL remote-
            # compile error).  Walking on is the reference tuner's
            # stance too: a failed apply just scores worst
            # (auto_tuner.cpp eval loop).  Classification lives in
            # yask_tpu.resilience: a CompilerOOM is a *genuinely
            # infeasible candidate* and never counts toward the outage
            # breaker (so the vmem ladder's ambitious rungs can strike
            # out on dense kernels without ending the walk); every
            # other classified fault (backend drop / hang / compile
            # failure — a dead backend makes EVERY compile fail) feeds
            # the breaker, and three consecutive failures re-raise so
            # an outage stays loud instead of ending the walk
            # "successfully" with all-inf results.
            fault = classify(e, site="tuner.measure")
            if fault is None:
                raise
            msg = f"{type(e).__name__}: {e}"
            if isinstance(fault, CompilerOOM):
                self.ctx._env.trace_msg(
                    f"auto-tuner: candidate {key} exceeded VMEM "
                    f"({msg[:160]}); marking infeasible")
                self.results[key] = float("inf")
                return float("inf")
            if self._breaker.record(fault):
                raise
            self.ctx._env.trace_msg(
                f"auto-tuner: candidate {key} failed "
                f"[{fault.kind}] ({msg[:160]}); marking infeasible")
            self.results[key] = float("inf")
            return float("inf")
        self._breaker.reset()
        from yask_tpu.obs.tracer import span
        with span("tuner.trial", phase="tune", keep=True,
                  candidate=repr(key), k=k) as sp:
            # warmup call (not timed — excludes dispatch jitter)
            call(compiled)
            calls = 0
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < self.trial_secs:
                call(compiled)
                calls += 1
                if self.best_rate is not None and \
                        (time.perf_counter() - t0) / (calls * k) \
                        > 2.0 * self.best_rate:
                    break
            per_step = (time.perf_counter() - t0) / max(calls * k, 1)
            sp.set(per_step=per_step, calls=calls)
        self.results[key] = per_step
        if self.best_rate is None or per_step < self.best_rate:
            self.best_rate = per_step
        return per_step

    def _sweep_k(self, candidates: Optional[List[int]]) -> int:
        """Chunk-length sweep (jit/sharded, or an explicit K list)."""
        ctx = self.ctx
        use_pallas = ctx._mode == "pallas"
        best_key, best = None, None
        for k in list(candidates or self.CHUNK_CANDIDATES):
            if use_pallas:
                mk = (lambda k=k: ctx._get_pallas_chunk(k))
            else:
                mk = (lambda k=k: ctx._get_compiled_chunk(k))
            r = self._measure((k,), mk)
            if r != float("inf") and (best is None or r < best):
                best_key, best = (k,), r
        ctx._tuned = True
        if best_key is None:
            ctx._env.trace_msg("auto-tuner: no feasible candidates; "
                               "keeping current settings")
            return ctx._opts.wf_steps
        ctx._opts.wf_steps = best_key[0]
        ctx._env.trace_msg(
            f"auto-tuner: wf_steps={best_key[0]} ({best * 1e3:.3f} ms/step)")
        return best_key[0]

    def _walk(self, measure, k0, blk0, sizes, lead, kmax) -> Tuple:
        """The greedy (K, block-shape) neighborhood walk itself: a
        coarse ×2/÷2 phase from the starting point, then a refinement
        phase stepping to *adjacent divisors* of each dim (the
        reference's shrinking-radius refinement, ``auto_tuner.cpp:206``
        region — without it, e.g. block 24 on a 48-sized dim is
        unreachable from 8 by doublings alone). Returns the best
        ``(k, blk)`` and its rate via ``self.results``."""

        def fit(d, b):
            b = max(1, min(b, sizes[d]))
            while sizes[d] % b != 0:
                b -= 1
            return b

        def divisor_steps(d, b):
            """Nearest divisors of the dim size strictly above/below b."""
            up = b + 1
            while up <= sizes[d] and sizes[d] % up != 0:
                up += 1
            down = b - 1
            while down >= 1 and sizes[d] % down != 0:
                down -= 1
            out = []
            if up <= sizes[d]:
                out.append(up)
            if down >= 1:
                out.append(down)
            return out

        def walk_from(cur, cur_rate, neigh_fn):
            moved = True
            while moved:
                moved = False
                for cand in neigh_fn(*cur):
                    r = measure(cand)
                    if r < cur_rate:
                        cur, cur_rate = cand, r
                        moved = True
            return cur, cur_rate

        def coarse(k, blk):
            out = []
            for nk in (k * 2, k // 2):
                if 1 <= nk <= kmax:
                    out.append((nk, blk))
            for i, d in enumerate(lead):
                for nb in (fit(d, blk[i] * 2), fit(d, blk[i] // 2)):
                    if nb != blk[i]:
                        out.append((k, blk[:i] + (nb,) + blk[i + 1:]))
            return out

        def refine(k, blk):
            out = []
            for nk in (k + 1, k - 1):
                if 1 <= nk <= kmax:
                    out.append((nk, blk))
            for i, d in enumerate(lead):
                for nb in divisor_steps(d, blk[i]):
                    out.append((k, blk[:i] + (nb,) + blk[i + 1:]))
            return out

        cur = (k0, tuple(fit(d, b) for d, b in zip(lead, blk0)))
        cur_rate = measure(cur)
        cur, cur_rate = walk_from(cur, cur_rate, coarse)
        cur, cur_rate = walk_from(cur, cur_rate, refine)
        return cur, cur_rate

    def _ladder_rungs(self) -> List[int]:
        """VMEM-budget rungs for the joint walks: the full ladder when
        the budget is auto (``-vmem_mb 0``) and ``-tune_vmem_ladder`` is
        on, else just the configured budget (a single rung — the walk
        runs exactly as before)."""
        opts = self.ctx._opts
        if opts.vmem_budget_mb == 0 and getattr(
                opts, "tune_vmem_ladder", False):
            return list(self.VMEM_LADDER_MIB)
        return [opts.vmem_budget_mb]

    def _walk_ladder(self, walk_one, lead) -> int:
        """Outer vmem-budget loop shared by both joint walks.

        ``walk_one(mb, ladder)`` runs one full (K, block) walk with
        ``ctx._opts.vmem_budget_mb`` temporarily set to ``mb`` and
        returns ``(cur, cur_rate)``; measure keys gain the budget
        element only when laddering so single-rung behavior (and every
        existing test's key shapes) is unchanged. The winning rung is
        applied into ``vmem_budget_mb`` alongside ``_finish_joint`` so
        production compiles — and ``apply_best`` replays — use it."""
        ctx = self.ctx
        rungs = self._ladder_rungs()
        ladder = len(rungs) > 1
        saved_mb = ctx._opts.vmem_budget_mb
        outcomes = []
        try:
            for mb in rungs:
                ctx._opts.vmem_budget_mb = mb
                cur, cur_rate = walk_one(mb, ladder)
                outcomes.append((cur_rate, mb, cur))
                if ladder:
                    ctx._env.trace_msg(
                        f"auto-tuner: vmem rung {mb} MiB -> "
                        f"{cur} ({cur_rate * 1e3:.3f} ms/step)")
        finally:
            ctx._opts.vmem_budget_mb = saved_mb
        cur_rate, mb, cur = min(outcomes, key=lambda t: t[0])
        if ladder and cur_rate != float("inf"):
            ctx._opts.vmem_budget_mb = mb
            ctx._env.trace_msg(f"auto-tuner: vmem budget {mb} MiB wins")
        return self._finish_joint(cur, cur_rate, lead)

    def _plan_signature(self, k: int, blk: Tuple, mb: int):
        """Canonical JSON of the planner's full decision record for
        ``(K, block, budget)`` plus the scoped Mosaic limit that budget
        implies.  Two ladder rungs with equal signatures would compile
        byte-identical kernels — ``plan_only`` is the planner itself, so
        every block shrink, skew engagement, and pipeline
        decision is in the dict and the signature cannot drift from the
        build.  ``reasons`` strings (and the raw budget) are stripped
        recursively: they mention the rung by name without changing the
        artifact.  Returns None when planning fails (``_measure``
        classifies the failure on the real build instead)."""
        import json
        ctx = self.ctx
        from yask_tpu.checker.vmem import plan_pallas
        from yask_tpu.ops.pallas_stencil import vmem_limit_bytes
        bs = ctx._opts.block_sizes
        lead = ctx._ana.domain_dims[:-1]
        old_b = {d: bs[d] for d in lead}
        old_k = ctx._opts.wf_steps
        for d, b in zip(lead, blk):
            bs[d] = b
        ctx._opts.wf_steps = k
        try:
            plan = plan_pallas(ctx, ctx._program, mb * 2 ** 20)
        except Exception:  # noqa: BLE001 — infeasible rung, no dedupe
            return None
        finally:
            for d in lead:
                bs[d] = old_b[d]
            ctx._opts.wf_steps = old_k

        def strip(o):
            if isinstance(o, dict):
                return {kk: strip(v) for kk, v in o.items()
                        if kk not in ("reasons", "vmem_budget")}
            if isinstance(o, (list, tuple)):
                return [strip(x) for x in o]
            return o

        sig = strip(plan)
        sig["vmem_limit"] = vmem_limit_bytes(mb * 2 ** 20)
        return json.dumps(sig, sort_keys=True, default=str)

    def _dedup_ladder_key(self, k: int, blk: Tuple, mb: int,
                          key: Tuple) -> bool:
        """Alias ``key``'s result to an earlier rung's measurement when
        the plan signatures agree.  Returns True on a dedupe hit."""
        if key in self.results:
            return False
        sig = self._plan_signature(k, blk, mb)
        if sig is None:
            return False
        first = self._sig_keys.setdefault(sig, key)
        if first != key and first in self.results:
            self.results[key] = self.results[first]
            self.ladder_dedup_hits += 1
            self.ctx._env.trace_msg(
                f"auto-tuner: rung candidate {key} plans identically to "
                f"{first}; reusing its measurement")
            return True
        return False

    def _start_point(self, k0):
        """Planner-informed starting (K, blocks) for the joint walk."""
        from yask_tpu.ops.tile_planner import plan_blocks
        ctx = self.ctx
        lead = ctx._ana.domain_dims[:-1]
        bs = ctx._opts.block_sizes
        if any(bs[d] > 0 for d in lead):
            blk0 = tuple(bs[d] if bs[d] > 0 else 8 for d in lead)
        else:
            # seed with the same carry-floor + skewed-margin hints the
            # build's default plan uses, or the walk wastes trials
            # re-discovering the build's own block shape.  shard_pallas
            # engages skew only where the stream dim is unsharded
            # (the carry cannot cross shards), so the seed must model
            # uniform margins in a sharded dim — same guard as the HBM
            # model.
            from yask_tpu.ops.pallas_stencil import (
                block_sizer, skew_engaged_dims, skew_plan_hints)
            smin, smarg, engaged, unsh = None, None, [], None
            if ctx._opts.skew_wavefront:
                if ctx._opts.mode == "shard_pallas":
                    unsh = [d for d in lead
                            if ctx._opts.num_ranks[d] <= 1]
                engaged = skew_engaged_dims(ctx._program, k0,
                                            unsharded=unsh)
                if engaged:
                    smin, smarg = skew_plan_hints(ctx._program, k0,
                                                  engaged=engaged)
            # priced by the build's own accounting at that tiling
            planned = plan_blocks(
                ctx._program, fuse_steps=k0,
                vmem_budget=ctx.vmem_budget(k0),
                vinstr_cap=ctx._opts.max_tile_vinstr,
                min_block=smin, margin_override=smarg,
                sizer=block_sizer(ctx._program, k0, skew=list(engaged),
                                  unsharded_dims=unsh))
            blk0 = tuple(planned[d] for d in lead)
        return blk0

    def _finish_joint(self, cur, cur_rate, lead) -> int:
        ctx = self.ctx
        ctx._tuned = True
        if cur_rate == float("inf"):
            ctx._env.trace_msg("auto-tuner: no feasible candidates; "
                               "keeping current settings")
            return ctx._opts.wf_steps
        k, blk = cur
        ctx._opts.wf_steps = k
        for d, b in zip(lead, blk):
            ctx._opts.block_sizes[d] = b
        ctx._env.trace_msg(
            f"auto-tuner: wf_steps={k}, blocks={dict(zip(lead, blk))} "
            f"({cur_rate * 1e3:.3f} ms/step, {len(self.results)} "
            "candidates tried)")
        return k

    def _walk_joint(self) -> int:
        """Joint (K, block-shape) walk for the single-device pallas path.
        K can grow up to ``tune_max_wf_steps`` (pads are pre-planned for
        it when auto-tune was enabled at prepare time; otherwise larger
        Ks fail pad validation and are skipped as infeasible)."""
        ctx = self.ctx
        lead = ctx._ana.domain_dims[:-1]
        sizes = {d: ctx._program.sizes[d] for d in lead}
        bs = ctx._opts.block_sizes
        k0 = max(ctx._opts.wf_steps, 1)
        kmax = max(ctx._opts.tune_max_wf_steps, k0)

        def walk_one(mb, ladder):
            def measure(cand):
                k, blk = cand

                def mk():
                    old = {d: bs[d] for d in lead}
                    for d, b in zip(lead, blk):
                        bs[d] = b
                    try:
                        return ctx._get_pallas_chunk(k)
                    finally:
                        for d in lead:
                            bs[d] = old[d]
                key = (k, blk, mb) if ladder else (k, blk)
                if ladder:
                    self._dedup_ladder_key(k, blk, mb, key)
                return self._measure(key, mk, k=k)

            return self._walk(measure, k0, self._start_point(k0),
                              sizes, lead, kmax)

        best_k = self._walk_ladder(walk_one, lead)
        self._push_ab(best_k)
        self._pipeline_ab(best_k)
        return best_k

    def _push_ab(self, kw: int) -> None:
        """Push-memory fusion on/off at the winning (K, blocks, vmem)
        point, the final axis of the single-device joint walk.  Only
        when the configured ``push_memory`` knob resolves to a live
        push argument AND the planner actually engages a push at the
        winning point (otherwise both arms compile the same kernel);
        the losing arm pins ``push_memory`` so production compiles
        follow the measurement."""
        ctx = self.ctx
        if ctx._push_arg() is False:
            return
        kw = max(kw, 1)
        lead = ctx._ana.domain_dims[:-1]
        blkw = tuple(ctx._opts.block_sizes[d] for d in lead)
        # 0 = unset: plan at the effective default budget, not 0 MiB
        mbw = ctx._opts.vmem_budget_mb or (ctx.vmem_budget(kw) >> 20)
        try:
            plan = self._plan_signature(kw, blkw, mbw)
            import json
            engaged = (plan is not None
                       and json.loads(plan).get("push", False))
        except Exception:  # noqa: BLE001
            engaged = False
        if not engaged:
            return
        rates = {}
        saved = ctx._opts.push_memory
        arms = {False: "off", True: saved}
        try:
            for on in (False, True):
                ctx._opts.push_memory = arms[on]

                def mk():
                    return ctx._get_pallas_chunk(kw)

                rates[on] = self._measure(("push", kw, blkw, mbw, on),
                                          mk, k=kw)
        finally:
            ctx._opts.push_memory = saved
        r_on = rates.get(True, float("inf"))
        r_off = rates.get(False, float("inf"))
        if r_on == float("inf") and r_off == float("inf"):
            return
        win = r_on < r_off
        ctx._opts.push_memory = saved if win else "off"
        ctx._env.trace_msg(
            f"auto-tuner: push={'on' if win else 'off'} "
            f"(on {r_on * 1e3:.3f} vs off {r_off * 1e3:.3f} ms/step)")

    def _pipeline_ab(self, kw: int) -> None:
        """Fused vs host-chained pipeline arm, A/B'd at the winning
        (K, blocks, vmem) point of the joint walk — only when this
        context is the fused program of a
        :class:`~yask_tpu.ops.pipeline.SolutionPipeline` that engaged.
        The chained arm replays the per-step per-stage schedule
        (binding pushes included — its real cost) on trial copies of
        the stage states; the losing arm is pinned into the pipeline
        and the verdict recorded as a structured reason, so a fusion
        the HBM model likes but the measurement overrules never runs
        in production."""
        import jax.numpy as jnp
        ctx = self.ctx
        pipe = getattr(ctx, "_pipeline", None)
        if pipe is None or not getattr(pipe, "_fused", False):
            return
        kw = max(kw, 1)

        def mk():
            return ctx._get_pallas_chunk(kw)

        r_fused = self._measure(("pipe", "fused", kw), mk, k=kw)

        from yask_tpu.utils.exceptions import YaskException
        try:
            ctxs = pipe._ensure_stage_ctxs()
        except YaskException as e:
            ctx._env.trace_msg(
                f"auto-tuner: pipeline chained arm unpreparable ({e}); "
                "keeping fused")
            return
        saved = {}
        for s, c in ctxs.items():
            c._materialize_state()
            c._state_to_device()
            saved[s] = (c._state, c._cur_step, c._steps_done)
            c._state = {k: [jnp.copy(a) for a in ring]
                        for k, ring in c._state.items()}
        c0 = ctxs[pipe.stage_names[0]]
        dirn = c0._ana.step_dir
        t0 = c0._cur_step

        def call(_):
            pipe._run_chained(t0, t0 + (kw - 1) * dirn)

        try:
            r_chain = self._measure(("pipe", "chained", kw),
                                    lambda: None, call=call, k=kw)
        finally:
            for s, c in ctxs.items():
                c._state, c._cur_step, c._steps_done = saved[s]
        if r_fused == float("inf") and r_chain == float("inf"):
            return
        win_fused = r_fused <= r_chain
        verdict = {
            "code": "pipeline-ab", "ok": True,
            "msg": (f"tuner A/B at K={kw}: fused "
                    f"{r_fused * 1e3:.3f} vs chained "
                    f"{r_chain * 1e3:.3f} ms/step -> "
                    f"{'fused' if win_fused else 'host-chained'}"),
            "fused_secs_per_step": r_fused,
            "chained_secs_per_step": r_chain,
        }
        plan = getattr(pipe, "_plan", None)
        if plan is not None:
            plan["reasons"].append(verdict)
        if not win_fused:
            pipe._fused = False
            if plan is not None:
                plan["fused"] = False
        ctx._env.trace_msg("auto-tuner: " + verdict["msg"])

    def _walk_joint_shard(self, candidates=None) -> int:
        """Joint (K, block-shape) walk for the distributed shard_pallas
        path (VERDICT r2: the multi-chip config was tuned on one knob).
        Trials time the real compiled shard_map program — one K-step
        group per call — on copies of the sharded interiors; block
        feasibility is against the *rank* domain (blocks tile shards,
        not the global domain)."""
        import jax
        import jax.numpy as jnp
        from yask_tpu.parallel.shard_step import (
            get_shard_pallas_fn, pad_shards, shard_pallas_key,
            strip_shards, _strip_global_interiors)
        ctx = self.ctx
        lead = ctx._ana.domain_dims[:-1]
        lsizes = ctx._opts.rank_domain_sizes
        sizes = {d: lsizes[d] for d in lead}
        k0 = max(ctx._opts.wf_steps, 1)
        kmax = max(ctx._opts.tune_max_wf_steps, k0)
        dirn = ctx._ana.step_dir

        src = _strip_global_interiors(ctx)
        # Trials donate their inputs: hand them copies, keep src intact.
        # A program takes and hands back the padded shards of its own
        # geometry (it follows K, not the block): the copies are padded
        # to the first candidate's, and stripped and padded again only
        # where a candidate's differs from the last one's.
        trial = {k: [jnp.copy(a) for a in ring] for k, ring in src.items()}
        trial_geom = None       # None: ``trial`` holds interiors
        t_trial = ctx._cur_step

        def advance(fn, n, k, blk):
            """One timed call of the ``(n, k, blk)`` program on the
            trial state, which the call's output replaces."""
            nonlocal trial, trial_geom, t_trial
            geom = ctx._shard_rest[shard_pallas_key(ctx, n, k, blk)]
            if trial_geom is None or trial_geom.key != geom.key:
                if trial_geom is not None:
                    trial = strip_shards(ctx, trial_geom, trial)
                trial, trial_geom = pad_shards(ctx, geom, trial), geom
            # The donated input is exactly the previous call's output,
            # so no per-call copy is needed.
            trial = jax.block_until_ready(
                fn(trial, jnp.asarray(t_trial, dtype=jnp.int32)))
            t_trial += n * dirn
        # Trial executables are keyed (shard_pallas, k, k, blk); evict
        # them when the walk ends — production keys on the full run span,
        # so keeping tens of dead Mosaic executables (and their device
        # buffers) alive for the context's lifetime buys nothing.
        keys_before = set(ctx._jit_cache)

        def make_measure(mb=None, ladder=False):
            def measure(cand):
                k, blk = cand

                def mk():
                    return get_shard_pallas_fn(ctx, t_trial,
                                               n=k, K=k, blk=blk)

                def call(fn):
                    advance(fn, k, k, blk)
                key = (("sp", k, blk, mb) if ladder else ("sp", k, blk))
                return self._measure(key, mk, call=call, k=k)
            return measure

        measure = make_measure()

        try:
            if candidates is not None:
                # explicit K list: sweep at the current block settings
                def fitd(d, b):
                    b = max(1, min(b, sizes[d]))
                    while sizes[d] % b != 0:
                        b -= 1
                    return b
                blk0 = tuple(fitd(d, b) for d, b in
                             zip(lead, self._start_point(k0)))
                best_key, best = None, None
                for k in candidates:
                    r = measure((k, blk0))
                    if r != float("inf") and (best is None or r < best):
                        best_key, best = (k, blk0), r
                ctx._tuned = True
                if best_key is None:
                    ctx._env.trace_msg("auto-tuner: no feasible "
                                       "candidates; keeping current "
                                       "settings")
                    return ctx._opts.wf_steps
                best_k = self._finish_joint(best_key, best, lead)
            else:
                def walk_one(mb, ladder):
                    return self._walk(make_measure(mb, ladder), k0,
                                      self._start_point(k0), sizes,
                                      lead, kmax)

                best_k = self._walk_ladder(walk_one, lead)

            # Overlapped halo exchange on/off as the final axis of the
            # joint walk, A/B'd at the winning (K, blocks, vmem) point.
            # The walk's own trials run one K-group per call (n=K),
            # where there is no second group to overlap — both
            # schedules compile to the same program — so the arms are
            # timed on TWO-group calls (n=2K, one mid-call exchange
            # round) where the core/shell split can actually hide the
            # collectives.  Only when the setting is "auto" (an
            # explicit on/off is the user's call, not the tuner's) and
            # the geometry admits an aligned core.
            if getattr(ctx._opts, "overlap_exchange", None) == "auto":
                from yask_tpu.parallel.shard_step import overlap_decision
                kw = max(ctx._opts.wf_steps, 1)
                ov_ok, _, _, _ = overlap_decision(ctx, kw)
                if ov_ok:
                    blkw = tuple(ctx._opts.block_sizes[d] for d in lead)
                    mbw = ctx._opts.vmem_budget_mb
                    rates = {}
                    try:
                        for ov in (False, True):
                            ctx._opts.overlap_exchange = ("on" if ov
                                                          else "off")

                            def mk():
                                return get_shard_pallas_fn(
                                    ctx, t_trial, n=2 * kw,
                                    K=kw, blk=blkw)

                            def call(fn):
                                advance(fn, 2 * kw, kw, blkw)
                            rates[ov] = self._measure(
                                ("sp", kw, blkw, mbw, ov), mk,
                                call=call, k=2 * kw)
                    finally:
                        ctx._opts.overlap_exchange = "auto"
                    r_on = rates.get(True, float("inf"))
                    r_off = rates.get(False, float("inf"))
                    if r_on != float("inf") or r_off != float("inf"):
                        win = r_on < r_off
                        ctx._opts.overlap_exchange = ("on" if win
                                                      else "off")
                        ctx._env.trace_msg(
                            f"auto-tuner: overlap_x="
                            f"{'on' if win else 'off'} "
                            f"(on {r_on * 1e3:.3f} vs off "
                            f"{r_off * 1e3:.3f} ms/step, "
                            f"2-group trials)")

            # Message coalescing on/off as a final A/B at the winning
            # point (auto only — explicit on/off is the user's call).
            # Only when the CommPlan models a saving (some axis carries
            # more than one slab; a one-buffer exchange already sits at
            # the 2-collectives-per-axis floor).  Timed on two-group
            # calls like the overlap arm: the walk's one-group trials
            # never reach a mid-call exchange, so both schedules would
            # compile to the same program.
            if getattr(ctx._opts, "coalesce", None) == "auto":
                kw = max(ctx._opts.wf_steps, 1)
                plan0 = ctx.comm_plan(kw)
                if plan0.order and not plan0.errors and \
                        plan0.rounds_serial > 2 * len(plan0.order):
                    blkw = tuple(ctx._opts.block_sizes[d] for d in lead)
                    mbw = ctx._opts.vmem_budget_mb
                    rates = {}
                    try:
                        for co in (False, True):
                            ctx._opts.coalesce = "on" if co else "off"

                            def mk():
                                return get_shard_pallas_fn(
                                    ctx, t_trial, n=2 * kw,
                                    K=kw, blk=blkw)

                            def call(fn):
                                advance(fn, 2 * kw, kw, blkw)
                            rates[co] = self._measure(
                                ("spc", kw, blkw, mbw, co), mk,
                                call=call, k=2 * kw)
                    finally:
                        ctx._opts.coalesce = "auto"
                    r_on = rates.get(True, float("inf"))
                    r_off = rates.get(False, float("inf"))
                    if r_on != float("inf") or r_off != float("inf"):
                        win = r_on < r_off
                        ctx._opts.coalesce = "on" if win else "off"
                        ctx._env.trace_msg(
                            f"auto-tuner: coalesce="
                            f"{'on' if win else 'off'} "
                            f"(on {r_on * 1e3:.3f} vs off "
                            f"{r_off * 1e3:.3f} ms/step, "
                            f"2-group trials)")
            return best_k
        finally:
            for key in set(ctx._jit_cache) - keys_before:
                if key[0] == "shard_pallas":
                    del ctx._jit_cache[key]
                    ctx._shard_rest.pop(key, None)

    def apply_best(self) -> None:
        feasible = {k: v for k, v in self.results.items()
                    if v != float("inf")}
        if not feasible:    # nothing measurable — keep current settings
            return
        best = min(feasible, key=feasible.get)
        coal_flag = None
        if best[0] == "sp":     # shard_pallas joint result
            best = best[1:]
        elif best[0] == "spc":  # coalesce A/B arm won outright
            coal_flag = bool(best[4])
            best = best[1:4]
        self.ctx._opts.wf_steps = best[0]
        if len(best) > 1:   # joint (k, block-shape) result
            lead = self.ctx._ana.domain_dims[:-1]
            for d, b in zip(lead, best[1]):
                self.ctx._opts.block_sizes[d] = b
        if len(best) > 2 and best[2] is not None:
            # vmem-ladder result: pin the winning budget so replays
            # compile with the rung the measurement actually used
            self.ctx._opts.vmem_budget_mb = best[2]
        if hasattr(self.ctx._opts, "coalesce"):
            if coal_flag is not None:
                self.ctx._opts.coalesce = "on" if coal_flag else "off"
            else:
                # mirror of the overlap pinning below: the A/B
                # answered the question even when a walk key won on raw
                # rate — pin the faster coalesce arm at the chosen K
                carms = {kk[4]: v for kk, v in feasible.items()
                         if len(kk) == 5 and kk[0] == "spc"
                         and kk[1] == best[0]}
                if carms:
                    self.ctx._opts.coalesce = (
                        "on" if min(carms, key=carms.get) else "off")
        if not hasattr(self.ctx._opts, "overlap_exchange"):
            return
        if len(best) > 3 and best[3] is not None:
            # overlap A/B result (shard_pallas): pin the winning arm —
            # best[3] is the boolean overlap flag of the timed trial
            self.ctx._opts.overlap_exchange = "on" if best[3] else "off"
        else:
            # The walk's one-group trials (no exchange to overlap) can
            # out-rate the two-group A/B arms on raw ms/step, leaving
            # the global best without an overlap element; the A/B still
            # answered the question — pin the faster arm at the chosen
            # K so replays get the schedule the walk decided on.
            arms = {k[4]: v for k, v in feasible.items()
                    if len(k) == 5 and k[0] == "sp" and k[1] == best[0]}
            if arms:
                self.ctx._opts.overlap_exchange = (
                    "on" if min(arms, key=arms.get) else "off")
