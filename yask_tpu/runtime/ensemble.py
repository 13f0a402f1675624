"""Ensemble batching: N independent instances of one prepared
solution run as a single vmapped program.

Small domains (≤128³ — the parameter-sweep / ensemble-seismic-shot
regime) leave most of a chip idle, and N separate runs pay N
trace+lower+compiles.  Here the state rings gain a leading batch dim
(``jnp.stack`` over the members' rings), the step chunk is
``jax.vmap``ed over it, and the batched executable is built once
through :func:`yask_tpu.cache.aot_compile` — so N members cost one
compile and one fused device program per chunk.  The reference's
analog is one ``yk_solution`` per simulation instance sharing a
linked kernel library; the :class:`RunState` hoist
(``yask_tpu/runtime/run_state.py``) is what lets one prepared context
serve all members.

Feasibility is a *mode* property with a single definition
(:func:`ensemble_feasible`): the single-device modes (jit / pallas)
batch; the sharded modes decline with a structured reason (their
state is mesh-decomposed — batching over an unsharded mesh axis is
future work), and ``ref`` is the sequential oracle by contract.  The
checker's ENSEMBLE-INFEASIBLE rule reads the same
function, so a decline is a diagnosable verdict, not a crash.

Per-member initial conditions and result extraction ride the existing
interior-coordinate var APIs unchanged: :meth:`EnsembleRun.member`
swaps the context's active :class:`RunState`, so inside the ``with``
block every ``yk_var`` call targets that member.

Bit-identity contract: a batched run must produce, per member, the
same bits as that member run alone (tests/test_ensemble.py) — vmap
adds a leading axis but the per-lane arithmetic is unchanged.

Masked sub-domain members (``sub_domains=``, serve-side shape
bucketing): a member may occupy only the low-corner ``{dim: size}``
sub-box of the shared geometry.  The masked jit chunk zeroes
everything outside each member's sub-domain after every step (and on
entry), which reproduces the solo run's ghost-zero boundary exactly —
bit-identity extends to members at DIFFERENT logical domain sizes
riding one executable.  jit-only: pallas fuses wf_steps in-kernel and
has no inter-step hook (`yask_tpu.serve.buckets` is the feasibility
gate).  When
the vmapped build fails (e.g. a Pallas primitive without a batching
rule under interpret), the run degrades to sequential members that
still share the context's compiled chunk, and
:attr:`EnsembleRun.batched_reason` records why.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from yask_tpu.utils.exceptions import YaskException

#: modes whose whole state lives on one device — the ones a leading
#: batch dim can simply vmap over.
BATCHED_MODES = ("jit", "pallas")


def sub_domain_masks(ctx, sub_sizes: Dict[str, int]) -> Dict:
    """Per-var boolean masks selecting a tenant's sub-domain inside a
    larger (bucket) geometry: True on ``[origin, origin+sub)`` along
    every domain dim (LOW-corner anchoring), True across misc axes.

    The masked ensemble chunk zeroes everything outside the mask after
    EVERY step — the physical-boundary ghost-zero contract extended
    inward, so an interior point's neighborhood reads exactly what a
    solo run at ``sub_sizes`` would read from its ghost pads.  The
    same masks also zero the INITIAL stacked state: read-only
    coefficient vars are never stepped, so a fill that strayed past
    the sub-domain (e.g. ``set_all_elements_same`` over the whole
    bucket) must be zeroed before the first step reads it."""
    import numpy as np
    ctx._check_prepared()
    masks = {}
    for name, g in ctx._program.geoms.items():
        # (a derived array's ghost cells hold f(source's ghost), not
        # zeros: masking its sources is what masks it)
        if g.is_scratch or g.is_derived:
            continue
        m = np.zeros(tuple(g.shape), dtype=bool)
        idx = []
        for dn, kind in g.axes:
            if kind == "domain":
                size = int(sub_sizes.get(
                    dn, ctx._opts.global_domain_sizes[dn]))
                idx.append(slice(g.origin[dn], g.origin[dn] + size))
            else:
                idx.append(slice(None))
        m[tuple(idx)] = True
        masks[name] = m
    return masks


def ensemble_feasible(ctx) -> Tuple[bool, str]:
    """Can this configured context batch an ensemble?  Returns
    ``(ok, reason)`` — the ONE definition the run path and the checker's
    ENSEMBLE-INFEASIBLE rule consult (a mode's
    verdict must never differ between preflight and runtime)."""
    mode = ctx._mode or ctx._opts.mode
    if mode == "auto":
        mode = "jit" if ctx._opts.num_ranks.product() <= 1 else "sharded"
    if mode in BATCHED_MODES:
        return True, ""
    if mode == "ref":
        return False, ("mode 'ref' is the sequential numpy oracle; "
                       "ensemble batching only applies to the "
                       "compiled paths (jit/pallas)")
    return False, (
        f"mode '{mode}' decomposes state over the device mesh; "
        "batching would need an unsharded mesh axis (future work) — "
        "run members sequentially or use -mode jit/pallas")


class EnsembleRun:
    """N members of one prepared solution, run as a batch.

    Member 0 *is* the context's current :class:`RunState` (whatever
    initial conditions were already set stay member 0's); members
    1..N-1 get fresh zero-filled states from ``ctx.new_run_state()``.
    Use :meth:`member` to set per-member initial conditions / read
    per-member results through the normal var APIs, and :meth:`run`
    to advance all members together.
    """

    def __init__(self, ctx, n: Optional[int] = None,
                 members: Optional[List] = None,
                 sub_domains: Optional[List[Optional[Dict[str, int]]]]
                 = None):
        ctx._check_prepared()
        if members is not None:
            # Batch EXISTING RunStates (the serving scheduler's shape:
            # each tenant session owns its state; a micro-batch groups
            # them under the one prepared context without adopting the
            # context's own current state as a member).
            if n is not None and n != len(members):
                raise YaskException(
                    f"ensemble n={n} disagrees with {len(members)} "
                    "explicit members")
            n = len(members)
        if n is None or n < 1:
            raise YaskException(f"ensemble size must be >= 1, got {n}")
        ok, why = ensemble_feasible(ctx)
        if not ok:
            raise YaskException(f"ensemble={n} infeasible: {why}")
        self._ctx = ctx
        if members is not None:
            self._members = list(members)
        else:
            self._members = [ctx.get_run_state()]
            self._members += [ctx.new_run_state() for _ in range(n - 1)]
        # Sub-domain masking (serve-side shape bucketing): member i
        # runs as a masked sub-domain of the shared geometry when
        # sub_domains[i] is a {dim: size} dict (None = full domain).
        # Masking interposes after every step INSIDE the scanned jit
        # chunk — pallas fuses wf_steps in-kernel, so masked members
        # are a jit-only contract (buckets.bucket_cobatch_feasible is
        # the single feasibility definition the serve layer consults
        # before ever building one of these).
        self._sub_domains = list(sub_domains) if sub_domains else None
        if self._sub_domains is not None:
            if len(self._sub_domains) != len(self._members):
                raise YaskException(
                    f"sub_domains has {len(self._sub_domains)} entries "
                    f"for {len(self._members)} members")
            if not any(self._sub_domains):
                self._sub_domains = None
        if self._sub_domains is not None \
                and (ctx._mode or ctx._opts.mode) != "jit":
            raise YaskException(
                "masked sub-domain members need the per-step mask "
                "hook of the scanned jit chunk; mode "
                f"'{ctx._mode or ctx._opts.mode}' fuses steps")
        #: "" after a vmapped run; otherwise why the last run degraded
        #: to sequential members (still sharing compiled chunks).
        self.batched_reason = ""

    @property
    def masked(self) -> bool:
        return self._sub_domains is not None

    @property
    def n(self) -> int:
        return len(self._members)

    @contextmanager
    def member(self, i: int):
        """Make member ``i`` the context's active run state for the
        block: every var API call inside targets that member."""
        prev = self._ctx.set_run_state(self._members[i])
        try:
            yield self._ctx
        finally:
            self._ctx.set_run_state(prev)

    # ------------------------------------------------------------------

    def _stack_states(self):
        """Leading-batch-dim state: var → ring of (N, *shape) arrays.
        Stacking copies, so the members' own rings stay valid — the
        sequential fallback restarts from them untouched."""
        import jax.numpy as jnp
        ctx = self._ctx
        for i in range(self.n):
            with self.member(i):
                ctx._check_prepared()
                ctx._state_to_device()
        # the batched program evaluates every scratch var in-tile
        # (``ctx._in_tile_program``): the derived arrays stay behind
        names = [n for n in self._members[0].state
                 if not ctx._program.geoms[n].is_derived]
        return {
            name: [jnp.stack([m.state[name][s] for m in self._members])
                   for s in range(len(self._members[0].state[name]))]
            for name in names}

    def _unstack_states(self, batched) -> None:
        for i, m in enumerate(self._members):
            m.state = {**m.state,
                       **{name: [b[i] for b in ring]
                          for name, ring in batched.items()}}
            m.state_on_device = True
            m.resident = None

    def _stacked_masks(self):
        """(N, *shape) boolean mask per state var — True where the
        member's sub-domain lives (full-domain members are all-True,
        so ``where(mask, x, 0)`` is bitwise identity for them and one
        compiled masked chunk serves any sub-domain mix)."""
        import numpy as np
        ctx = self._ctx
        per_member = []
        for sd in self._sub_domains:
            per_member.append(sub_domain_masks(ctx, sd or {}))
        names = list(per_member[0])
        return {name: np.stack([pm[name] for pm in per_member])
                for name in names}

    def _batched_chunk_fn(self, k: int):
        """vmapped+AOT-compiled chunk advancing every member ``k``
        steps.  Cached in the context's jit cache under an
        ensemble-tagged key; persisted via yask_tpu.cache like any
        other executable (key carries the ensemble width — a batched
        program must never alias the unbatched one).  The masked
        variant takes the per-member masks as a RUNTIME argument
        (vmapped alongside the state, never donated), so the same
        executable serves every sub-domain mix at this width."""
        ctx = self._ctx
        key = ("ens_compiled", self.n, k, ctx._mode, self.masked)
        if key in ctx._jit_cache:
            return ctx._jit_cache[key]
        import jax
        from jax import lax
        from yask_tpu.cache import aot_compile
        prog = ctx._in_tile_program()
        dirn = ctx._ana.step_dir

        if ctx._mode == "pallas":
            from yask_tpu.ops.pallas_stencil import build_pallas_chunk
            _, blk, skw = ctx._pallas_build_key(k)
            chunk, _tb = build_pallas_chunk(
                prog, fuse_steps=k, block=blk,
                interpret=ctx._env.get_platform() != "tpu",
                vmem_budget=ctx.vmem_budget(k), skew=skw,
                vinstr_cap=ctx._opts.max_tile_vinstr)
        elif self.masked:
            import jax.numpy as jnp

            # zero-mask after EVERY step: the ghost-zero contract
            # extended inward, so a sub-domain point's neighborhood
            # reads exactly what the solo run's ghost pads would
            # hold.  The selects must live in their OWN programs:
            # even fenced behind lax.optimization_barrier on both
            # sides, a select inside the scan body shifts how XLA
            # compiles the stencil arithmetic itself (fusion /
            # vectorization choices) and the masked run drifts from
            # its solo twin by ulps.  So the masked "chunk" is a
            # chained pair of executables — a vmapped ONE-step
            # program whose graph is exactly the solo chunk's, and a
            # vmapped select program between steps — called k times.
            # Chained == fused is bit-exact for the jit step program
            # (the sequential fallback rests on the same fact);
            # keeping the step graph select-free is what buys
            # bit-identity, the bucketing contract.
            def step1(state, t0):
                def body(carry, _):
                    st, t = carry
                    return (prog.step(st, t), t + dirn), None
                (st, _), _ = lax.scan(body, (state, t0), None,
                                      length=1)
                return st

            def mask_sel(state, masks):
                return {name: [jnp.where(masks[name], s, 0)
                               if name in masks else s for s in ring]
                        for name, ring in state.items()}

            # the step program is graph-identical to an unmasked
            # width-n k=1 ensemble chunk — share its persistent key
            # so warm caches hit across masked/unmasked servers
            res_s = aot_compile(
                jax.vmap(step1, in_axes=(0, None)),
                (self._stacked_example, 0),
                key=ctx._persistent_key("ens_chunk", n=1,
                                        ensemble=self.n,
                                        mode=ctx._mode,
                                        variant=ctx._pallas_variant_key()),
                platform=ctx._env.get_platform(), donate_argnums=0)
            res_m = aot_compile(
                jax.vmap(mask_sel, in_axes=(0, 0)),
                (self._stacked_example, self._mask_example),
                key=ctx._persistent_key("ens_mask", ensemble=self.n,
                                        mode=ctx._mode),
                platform=ctx._env.get_platform(), donate_argnums=0)
            ctx._compile_secs += res_s.compile_secs + res_m.compile_secs
            ctx._last_cache_hit = res_s.cache_hit and res_m.cache_hit
            sfn, mfn = res_s.fn, res_m.fn

            def masked_chunk(state, t0, masks):
                st, t = state, t0
                for _ in range(k):
                    st = mfn(sfn(st, t), masks)
                    t += dirn
                return st

            ctx._jit_cache[key] = masked_chunk
            return masked_chunk
        else:
            def chunk(state, t0):
                def body(carry, _):
                    st, t = carry
                    return (prog.step(st, t), t + dirn), None
                (st, _), _ = lax.scan(body, (state, t0), None, length=k)
                return st

        bchunk = jax.vmap(chunk, in_axes=(0, None))
        example = (self._stacked_example, 0)
        res = aot_compile(
            bchunk, example,
            key=ctx._persistent_key("ens_chunk", n=k, ensemble=self.n,
                                    mode=ctx._mode,
                                    variant=ctx._pallas_variant_key()),
            platform=ctx._env.get_platform(), donate_argnums=0)
        ctx._compile_secs += res.compile_secs
        ctx._last_cache_hit = res.cache_hit
        ctx._jit_cache[key] = res.fn
        return res.fn

    def run(self, first_step_index: int,
            last_step_index: Optional[int] = None) -> None:
        """Advance every member over the step range (inclusive) — the
        ensemble analog of ``run_solution``.  Wall-clock lands in
        member 0's run timer (it is the *aggregate* batched time, not
        a per-member cost); every member's ``cur_step``/``steps_done``
        advance as if run alone."""
        from yask_tpu.obs.tracer import span
        ctx = self._ctx
        ctx._check_prepared()
        if last_step_index is None:
            last_step_index = first_step_index
        start, n = ctx._step_seq(first_step_index, last_step_index)

        try:
            with span("ensemble.run", phase="compute",
                      members=self.n, steps=n, masked=self.masked):
                self._run_batched(start, n)
            self.batched_reason = ""
        except YaskException:
            raise
        except Exception as e:  # noqa: BLE001 - degrade, don't die:
            # a missing vmap batching rule (Pallas primitives under
            # interpret) must cost the batching win, not the run.
            # Member states are untouched (stacking copies), so the
            # sequential path restarts cleanly and still shares the
            # context's compiled per-member chunk.
            self.batched_reason = f"{type(e).__name__}: {e}"
            with span("ensemble.sequential", phase="compute",
                      members=self.n, steps=n,
                      reason=self.batched_reason[:120]):
                self._run_sequential(first_step_index,
                                     last_step_index)
            return

        dirn = ctx._ana.step_dir
        for m in self._members:
            m.cur_step = start + n * dirn
            m.steps_done += n

    def _run_batched(self, start: int, n: int) -> None:
        import jax
        ctx = self._ctx
        batched = self._stack_states()
        masks = None
        if self.masked:
            import jax.numpy as jnp
            masks = {name: jnp.asarray(m)
                     for name, m in self._stacked_masks().items()}
            # mask the INITIAL state too: read-only vars are never
            # stepped, so out-of-sub-domain fill values would leak
            # into the first step's neighborhood reads otherwise
            batched = {name: [jnp.where(masks[name], s, 0)
                              if name in masks else s for s in ring]
                       for name, ring in batched.items()}
        # Example avals for lowering (shapes only — jit caches by
        # shape; keeping the live dict separate lets donation consume
        # it while the key stays valid for every group).
        self._stacked_example = batched
        self._mask_example = masks
        if ctx._mode == "pallas":
            # mirror _run_pallas_steps: fuse depth is bounded by the
            # K the pads were planned for (wf_steps; 0 → 1), never n
            wf = min(max(ctx._opts.wf_steps, 1), n)
        else:
            wf = ctx._opts.wf_steps if ctx._opts.wf_steps > 0 else n
        sizes = []
        rem = n
        while rem > 0:
            k = min(wf, rem)
            sizes.append(k)
            rem -= k
        fns = {k: self._batched_chunk_fn(k) for k in set(sizes)}
        del self._stacked_example
        self._mask_example = None
        dirn = ctx._ana.step_dir
        t = start
        with self._members[0].run_timer:
            st = batched
            for k in sizes:
                st = fns[k](st, t) if masks is None \
                    else fns[k](st, t, masks)
                t += k * dirn
            jax.block_until_ready(st)
        self._unstack_states(st)

    def _mask_member_state(self, i: int) -> None:
        """Zero member ``i``'s state outside its sub-domain — the
        sequential fallback's analog of the in-chunk mask (applied
        before the run and after every step, so fallback bits equal
        the vmapped masked chunk's: jit fused==chained is exact)."""
        import jax.numpy as jnp
        sd = self._sub_domains[i]
        if not sd:
            return
        masks = sub_domain_masks(self._ctx, sd)
        m = self._members[i]
        m.state = {name: [jnp.where(masks[name], s, 0)
                          if name in masks else s for s in ring]
                   for name, ring in m.state.items()}
        m.state_on_device = True
        m.resident = None

    def _run_sequential(self, first_step_index: int,
                        last_step_index: int) -> None:
        if not self.masked:
            for i in range(self.n):
                with self.member(i):
                    self._ctx.run_solution(first_step_index,
                                           last_step_index)
            return
        ctx = self._ctx
        start, n = ctx._step_seq(first_step_index, last_step_index)
        dirn = ctx._ana.step_dir
        for i in range(self.n):
            with self.member(i):
                ctx._state_to_device()
                self._mask_member_state(i)
                t = start
                for _ in range(n):
                    ctx.run_solution(t, t)
                    self._mask_member_state(i)
                    t += dirn
