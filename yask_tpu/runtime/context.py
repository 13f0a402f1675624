"""The stencil context: ``yk_solution`` driving compiled step programs.

Counterpart of the reference's ``StencilContext``
(``src/kernel/lib/context.hpp:231-786``, ``context.cpp``, ``soln_apis.cpp``):
owns settings, vars, and state storage; ``prepare_solution`` performs the
setup pipeline (decomposition → geometry → allocation, mirroring
``soln_apis.cpp:137-250``); ``run_solution`` advances steps on the selected
execution path; ``run_ref``/``compare_data`` implement the validation oracle
(``context.cpp:46``, ``yask_main.cpp:564-616``).

Execution modes (see ``KernelSettings.mode``):

* ``jit`` — one device: the whole step traced and XLA-fused, steps advanced
  under ``lax.scan`` with donated (ring-rotated) state.
* ``sharded`` — global arrays with ``NamedSharding`` over the device mesh;
  the same traced step; XLA inserts halo collectives for the shifted reads
  (the idiomatic-TPU replacement for MPI halo exchange).
* ``shard_map`` — explicit per-shard program with ``lax.ppermute`` ghost
  exchange (the structural twin of the reference's ``exchange_halos``,
  ``halo.cpp``), used for overlap control and as the scaling path.
* ``ref`` — eager numpy oracle (the reference's scalar ``run_ref``).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from yask_tpu.obs.tracer import span
from yask_tpu.utils.exceptions import YaskException
from yask_tpu.utils.cli import CommandLineParser
from yask_tpu.runtime.env import yk_env
from yask_tpu.runtime.run_state import RunState
from yask_tpu.runtime.settings import KernelSettings
from yask_tpu.runtime.stats import yk_stats
from yask_tpu.runtime.var import yk_var

#: ``jax.named_scope`` of one step of the XLA path (the jit modes; no
#: step of a ``pallas`` call takes it) in a device trace
SCOPE_XLA_STEP = "yt_xla_step"


#: the argument of a one-chip launch's executable that is donated:
#: ``base``, the given-up slots its outputs are written onto
_LAUNCH_DONATES = (2,)


def _launch_exe(written):
    """``exe(state, t, base)`` of a one-chip launch: the chunk's
    ``written``, built ``onto``, under its own name (the compiled
    module's in a device trace, ``jit_yt_<solution>_r<radius>_k<K>``),
    to be compiled with ``base`` donated (``_LAUNCH_DONATES``).  The
    state the kernel reads is not donated: a tile's margins are read
    from the very slots a launch in place would write."""
    def exe(state, t, base):
        return written(state, t, None, base)
    exe.__name__ = exe.__qualname__ = written.__name__
    return exe


def _zeroed_like(array, count: int) -> List:
    """``count`` zeroed arrays like ``array``, for a launch whose pool
    of given-up slots is short; counted in ``run.spare_made``."""
    import jax.numpy as jnp
    from yask_tpu.obs.metrics import get_registry
    get_registry().counter("run.spare_made").inc(count)
    return [jnp.zeros_like(array) for _ in range(count)]


class _PallasLaunch:
    """A one-chip Pallas chunk as ``fn(state, t) -> state``.

    ``exe(state, t, base)`` is the executable of the chunk's
    ``written`` built ``onto``: it returns the ring slots the kernel
    wrote, ``writes[var]`` of them a written var, written ONTO the
    arrays of ``base``, which it is DONATED; ``merge`` puts the other
    arrays of the input beside them by reference.  ``base`` is taken
    from the run's pool of given-up slots (``RunState.spare``) and the
    slots this launch evicts are left there for the next one, so a loop
    of launches allocates nothing and no pad band is zeroed after the
    kernel: the bands of a given-up slot are zero already.  Where the
    pool is short (a run state's first launch; a var whose last group
    was a shorter one) the launch makes zeroed arrays of the padded
    shape, once (the counter ``run.spare_made``).

    Only the pool's arrays are ever donated: the state a launch reads
    is handed over as it is and outlives a launch that raises (what was
    taken from the pool is then lost with it).  After the call the
    slots it evicted, ``state[var][:writes[var]]``, are the pool's:
    whoever still reads one (a ``fuse_vars`` peer) finds it deleted
    once the next launch has run.

    Whatever else is asked of it (``as_text``, ``memory_analysis``) is
    the executable's answer, where it has one."""

    __slots__ = ("exe", "merge", "writes", "written", "operands", "ctx")

    def __init__(self, exe, merge, writes: Dict[str, int], operands,
                 ctx: "StencilContext"):
        self.exe, self.merge, self.writes = exe, merge, writes
        self.written = sum(writes.values())
        self.operands, self.ctx = operands, ctx

    def takes(self, state):
        """The rings ``exe`` is handed: the kernel's operands and no
        others (an array only a hoisted scratch var's fill reads is no
        argument of the launch)."""
        return {n: state[n] for n in self.operands}

    def evicts(self, state):
        """The slots of ``state`` the launch evicts, as many of a var
        and of the shapes of what ``base`` holds of it."""
        return {name: state[name][:n] for name, n in self.writes.items()}

    def onto(self) -> int:
        """How many outputs of a launch made now would be written onto
        a given-up slot: what the run's pool holds of what it needs."""
        spare = self.ctx._run.spare
        return sum(min(n, len(spare.get(name, ())))
                   for name, n in self.writes.items())

    def __call__(self, state, t):
        spare = self.ctx._run.spare
        base = {}
        for name, n in self.writes.items():
            pool = spare.setdefault(name, [])
            base[name], pool[:] = pool[:n], pool[n:]
            if len(base[name]) < n:
                base[name] += _zeroed_like(state[name][0],
                                           n - len(base[name]))
        news = self.exe(self.takes(state), t, base)
        for name, evicted in self.evicts(state).items():
            spare[name] += evicted
        return self.merge(state, news)

    def __getattr__(self, name):
        return getattr(self.exe, name)


class StencilContext:
    """One runnable instance of a compiled stencil solution."""

    def __init__(self, env: yk_env, source, dtype=None):
        self._env = env
        # Accept a yc_solution_base (defines on demand), a yc_solution, or a
        # pre-lowered CompiledSolution — the flexibility the reference gets
        # from linking any generated solution into yk_factory.
        from yask_tpu.compiler.solution import yc_solution
        from yask_tpu.compiler.solution_base import yc_solution_base
        from yask_tpu.compiler.lowering import CompiledSolution
        if isinstance(source, yc_solution_base):
            source.run_define()
            soln = source.get_soln()
            self._csol = soln.compile(dtype=dtype)
        elif isinstance(source, yc_solution):
            self._csol = source.compile(dtype=dtype)
        elif isinstance(source, CompiledSolution):
            self._csol = source
        else:
            raise YaskException(
                f"cannot build a kernel solution from {type(source).__name__}")
        self._soln = self._csol.soln
        self._ana = self._csol.ana
        # the lowered solution by whether it hoists (see _lowered):
        # _csol / _ana are the prepared mode's, of these two
        self._lowered_by = {True: self._csol}

        self._opts = KernelSettings(self._ana.domain_dims)
        self._program = None          # StepProgram (compute geometry)
        # ALL per-run mutable state (var rings, resident shard
        # interiors, step position, run timer) lives in the
        # active RunState; the historical attribute names below
        # (_state, _resident, _cur_step, …) are delegating properties,
        # so one prepared solution can serve many swapped runs
        # (ensemble members, repeated sweeps) without re-preparing.
        self._run = RunState()
        self._vars: Dict[str, yk_var] = {}
        self._mode = None
        self._mesh = None
        self._shardings = None
        self._rank_offset: Dict[str, int] = {
            d: 0 for d in self._ana.domain_dims}
        self._jit_cache: Dict = {}
        self._launch_attrs: Dict = {}   # shard build key → span attrs
        self._shard_rest: Dict = {}     # shard build key → RestGeom
        self._pallas_tiling: Dict = {}  # build key → tiling actually chosen
        self._comm_plans: Dict = {}     # (mode, K, knobs) → CommPlan

        self._compile_secs = 0.0
        self._last_cache_hit = None     # cache verdict of latest build
        # cross-solution pipeline fusion (yask_tpu.ops.pipeline): the
        # merged-chain signature is one more variant-key dimension —
        # a fused chain must never alias an unfused solution's cached
        # executable — and the owning SolutionPipeline registers
        # itself for the tuner's fused-vs-chained arm.
        self._pipeline_sig = None
        self._pipeline = None
        self._pipeline_plan = None

        self._hooks: Dict[str, List[Callable]] = {
            "before_prepare": [], "after_prepare": [],
            "before_run": [], "after_run": []}
        self._trace_dir: Optional[str] = None

        # yc_solution::call_after_new_solution hooks run now — right
        # after kernel-solution construction, as the reference injects
        # its code block at the end of yk_factory::new_solution
        for code in getattr(self._soln, "_after_new_solution", ()):
            if callable(code):
                code(self)
            else:
                exec(compile(str(code), "<call_after_new_solution>",
                             "exec"), {"kernel_soln": self})

    # ------------------------------------------------------------------
    # per-run state delegation (RunState hoist)
    # ------------------------------------------------------------------
    # The historical attribute names stay valid for every consumer
    # (var.py, shard_step.py, the tools) but resolve through the
    # active RunState so whole runs can be swapped under one prepared
    # solution (ensemble batching, repeated sweeps).

    @property
    def _state(self):
        return self._run.state

    @_state.setter
    def _state(self, v):
        # a state put in place whole (a host copy, a restore, another
        # geometry after a re-plan, none) comes without the slots the
        # launches on the one before it gave up; the launch loop, whose
        # pool they are, moves ``RunState.state`` itself
        self._run.state = v
        self._run.spare.clear()

    @property
    def _resident(self):
        """The state as sharded interiors, the form every reader
        outside a launch reads.  Where a shard program left its padded
        shards (``RunState.padded``) they are stripped here, once
        (``shard_step.strip_rest``: the ``run.repad`` span,
        ``run.state_strips``), and the interiors rest in their place:
        a host access between two calls costs this strip and the next
        launch's pad, a loop of calls with none between them neither."""
        if self._run.padded is not None:
            from yask_tpu.parallel.shard_step import strip_rest
            strip_rest(self)
        return self._run.resident

    @_resident.setter
    def _resident(self, v):
        self._run.resident = v

    @property
    def _state_on_device(self):
        return self._run.state_on_device

    @_state_on_device.setter
    def _state_on_device(self, v):
        self._run.state_on_device = v

    @property
    def _cur_step(self):
        return self._run.cur_step

    @_cur_step.setter
    def _cur_step(self, v):
        self._run.cur_step = v

    @property
    def _steps_done(self):
        return self._run.steps_done

    @_steps_done.setter
    def _steps_done(self, v):
        self._run.steps_done = v

    @property
    def _run_timer(self):
        return self._run.run_timer

    def get_run_state(self) -> RunState:
        """The active per-run state bundle."""
        return self._run

    def set_run_state(self, rs: RunState) -> RunState:
        """Swap in another run's state bundle; returns the previous
        one.  The solution side (program, jit cache, tiling) is
        untouched — that is the point: one compile, many runs."""
        prev, self._run = self._run, rs
        return prev

    def new_run_state(self) -> RunState:
        """A fresh zero-state run over the prepared geometry (the
        ensemble-member allocator).  Mirrors ``prepare_solution``'s
        allocation: zero-filled rings, pads identically zero,
        shardings applied when the mode shards resting state."""
        self._check_prepared()
        rs = RunState()
        self._alloc_resting(rs)
        return rs

    def _alloc_resting(self, rs: RunState) -> None:
        """Allocate ``rs``'s zero resting state where it will live —
        the ONE allocator behind ``prepare_solution`` and
        :meth:`new_run_state`.  Mesh modes are sharded AT ALLOCATION
        (a global array on the default device first would put the whole
        problem on chip 0): ``sharded`` gets padded global arrays under
        its NamedShardings; ``shard_map``/``shard_pallas`` start as
        sharded INTERIORS (``resident``), which is what the public
        fills write.  The first launch pads them per shard
        (``shard_step.rest_padded``) and from then on the state rests
        as the padded shards its program hands back
        (``RunState.padded``) until something reads it
        (:attr:`_resident` strips lazily; host access that needs the
        global pads materializes through :meth:`_materialize_state`)."""
        rs.state, rs.resident = None, None
        rs.padded = rs.padded_geom = None
        rs.derived_from = None      # no derived array before a fill
        rs.pulled.clear()           # nor a pull of the new arrays
        rs.spare.clear()            # nor a slot a launch gave up
        if self._mode in ("shard_map", "shard_pallas"):
            from yask_tpu.parallel.shard_step import alloc_resident
            rs.resident = alloc_resident(self)
        elif self._shardings is not None:
            import jax.numpy as jnp
            rs.state = {
                name: [jnp.zeros(tuple(g.shape), self._program.dtype,
                                 device=self._shardings[name])
                       for _ in range(g.num_slots)]
                for name, g in self._program.geoms.items()
                if not g.is_scratch and not g.is_derived}
        else:
            rs.state = self._program.alloc_state()
        rs.state_on_device = True

    def new_ensemble(self, n: Optional[int] = None) -> "EnsembleRun":
        """N members of this prepared solution batched as one vmapped
        program (``yask_tpu.runtime.ensemble``).  ``n`` defaults to
        the ``-ensemble`` setting; member 0 adopts the context's
        current run state (initial conditions already set stay
        member 0's)."""
        from yask_tpu.runtime.ensemble import EnsembleRun
        return EnsembleRun(self, n if n is not None
                           else max(self._opts.ensemble, 1))

    # ------------------------------------------------------------------
    # identity / settings / vars
    # ------------------------------------------------------------------

    def get_name(self) -> str:
        return self._soln.get_name()

    def get_description(self) -> str:
        return self._soln.get_description()

    def get_env(self) -> yk_env:
        return self._env

    def get_settings(self) -> KernelSettings:
        return self._opts

    def get_step_dim_name(self) -> str:
        return self._ana.step_dim or ""

    def get_domain_dim_names(self) -> List[str]:
        return list(self._ana.domain_dims)

    def set_overall_domain_size(self, dim: str, size: int) -> None:
        self._opts.global_domain_sizes[dim] = size

    def set_overall_domain_size_vec(self, sizes) -> None:
        for d, v in (sizes.items() if hasattr(sizes, "items") else sizes):
            self._opts.global_domain_sizes[d] = v

    def get_overall_domain_size(self, dim: str) -> int:
        return self._opts.global_domain_sizes[dim]

    def set_rank_domain_size(self, dim: str, size: int) -> None:
        self._opts.rank_domain_sizes[dim] = size

    def get_rank_domain_size(self, dim: str) -> int:
        return self._opts.rank_domain_sizes[dim]

    def set_block_size(self, dim: str, size: int) -> None:
        self._opts.block_sizes[dim] = size

    def get_block_size(self, dim: str) -> int:
        return self._opts.block_sizes[dim]

    def get_element_bytes(self) -> int:
        """Bytes per FP element (reference ``yk_solution::get_element_bytes``,
        driven by ``swe_main.cpp:398``)."""
        return int(np.dtype(self._csol.dtype).itemsize)

    def set_num_ranks(self, dim: str, n: int) -> None:
        self._opts.num_ranks[dim] = n

    def get_num_ranks(self, dim: str) -> int:
        return self._opts.num_ranks[dim]

    def get_num_vars(self) -> int:
        return len([v for v in self._soln.get_vars() if not v.is_scratch()])

    def get_var_names(self) -> List[str]:
        return [v.get_name() for v in self._soln.get_vars()
                if not v.is_scratch()]

    def get_var(self, name: str) -> yk_var:
        if name not in self._vars:
            raise YaskException(
                f"no var '{name}' (or prepare_solution not called)")
        return self._vars[name]

    def get_vars(self) -> List[yk_var]:
        return list(self._vars.values())

    def new_fixed_size_var(self, name: str, dim_names, dim_sizes):
        """Create standalone N-D storage with the var data API
        (``yk_solution::new_fixed_size_var``); not part of stepping."""
        from yask_tpu.runtime.var import FixedSizeVar
        v = FixedSizeVar(name, list(dim_names), list(dim_sizes))
        self._fixed_vars = getattr(self, "_fixed_vars", {})
        self._fixed_vars[name] = v
        return v

    def copy_vars_to_device(self) -> None:
        """Force state onto device (``yk_solution::copy_vars_to_device``;
        mostly a no-op here since runs keep state resident)."""
        self._check_prepared()
        self._state_to_device()

    def copy_vars_from_device(self) -> None:
        self._check_prepared()
        self._state_to_host()

    def fuse_vars(self, other: "StencilContext") -> None:
        """Share storage with another prepared context where var geometry
        matches (``yk_solution::fuse_vars``, used by the reference's
        validation flow to alias vars between solutions). Arrays are
        immutable under JAX, so sharing is simply adopting references.

        Caveat: the jit path's compiled chunks donate their input
        buffers, and a ``pallas`` launch donates the ring slots the
        launch before it evicted (``RunState.spare``), so after either
        context RUNS, buffers of a written var previously shared
        through fuse_vars may be consumed — re-fuse after runs rather
        than relying on stale aliases.  (An array no step writes is
        never donated and stays one object in both.)"""
        self._check_prepared()
        other._check_prepared()
        self._materialize_state()
        other._materialize_state()
        for name, ring in other._state.items():
            if name not in self._state:
                continue
            mine = self._state[name]
            if len(mine) != len(ring):
                continue
            # (shapes alone: one of mine may be consumed by now)
            ok = all(tuple(a.shape) == tuple(b.shape)
                     for a, b in zip(mine, ring))
            if ok:
                self._state[name] = list(ring)

    def first_domain_index(self, dim: str) -> int:
        return 0

    def last_domain_index(self, dim: str) -> int:
        return self._opts.global_domain_sizes[dim] - 1

    # ------------------------------------------------------------------
    # hooks (yk_solution hook registration, soln_apis.cpp)
    # ------------------------------------------------------------------

    def call_before_prepare_solution(self, fn: Callable) -> None:
        self._hooks["before_prepare"].append(fn)

    def call_after_prepare_solution(self, fn: Callable) -> None:
        self._hooks["after_prepare"].append(fn)

    def call_before_run_solution(self, fn: Callable) -> None:
        self._hooks["before_run"].append(fn)

    def call_after_run_solution(self, fn: Callable) -> None:
        self._hooks["after_run"].append(fn)

    # ------------------------------------------------------------------
    # prepare
    # ------------------------------------------------------------------

    def _plan_geometry(self):
        """Settings adjustment → mode resolution → var-geometry planning,
        WITHOUT allocating any state or marking the context prepared.

        Returns the planned :class:`StepProgram`.  ``prepare_solution``
        assigns it to ``self._program`` and allocates; the static
        checker (``yask_tpu.checker``) calls this directly so a 512³
        feasibility question never materializes gigabytes of state —
        ``plan()`` is pure geometry (``alloc_state`` is a separate
        step).  Sets ``self._mode`` / ``self._plan_kwargs`` but NOT
        ``self._program`` (``is_prepared()`` keys off the latter)."""
        ndev = self._env.get_num_ranks()
        self._opts.adjust_settings(ndev)

        mode = self._opts.mode
        nranks = self._opts.num_ranks.product()
        if mode == "auto":
            mode = "jit" if nranks == 1 else "sharded"
        if self._opts.force_scalar:
            mode = "ref"
        self._mode = mode
        self._csol = self._lowered(mode not in self.IN_TILE_MODES)
        self._ana = self._csol.ana

        extra = self._merged_pads({})
        gsizes = self._opts.global_domain_sizes

        if mode in ("shard_map", "shard_pallas"):
            from yask_tpu.parallel.decomp import validate_shard_geometry
            validate_shard_geometry(self._csol, self._opts)
        if mode == "shard_pallas":
            from yask_tpu.ops.pallas_stencil import pallas_applicable
            ok, why = pallas_applicable(self._csol)
            if not ok:
                raise YaskException(
                    f"solution '{self.get_name()}' cannot use the "
                    f"shard_pallas path: {why}; use -mode shard_map")

        # Compute geometry is always the *global* problem; the shard_map
        # path re-plans per-shard geometry inside the mapped region.
        # Sharded mode needs padded extents divisible by the mesh extent
        # (jax requires whole-dim divisibility for NamedSharding).
        pad_mult = None
        if mode == "sharded":
            pad_mult = {d: self._opts.num_ranks[d]
                        for d in self._ana.domain_dims
                        if self._opts.num_ranks[d] > 1}
        if mode == "pallas":
            # The fused Pallas path needs pad ≥ radius × fuse_steps in the
            # leading (tiled) dims so halo tiles can be DMA'd whole.
            from yask_tpu.ops.pallas_stencil import pallas_applicable
            ok, why = pallas_applicable(self._csol)
            if not ok:
                raise YaskException(
                    f"solution '{self.get_name()}' cannot use the pallas "
                    f"path: {why}; use -mode jit")
            K = max(self._opts.wf_steps, 1)
            if self._opts.do_auto_tune:
                # Plan pads for the largest K the joint walk may try so
                # the tuner can grow K, not only shrink it (the pads are
                # zero-filled and cheap; without this every K-doubling
                # candidate fails pad validation and caches as inf).
                K = max(K, self._opts.tune_max_wf_steps)
            extra = self._merged_pads(self._pallas_pad_needs(K))
        # Mosaic lane/sublane alignment only serves the manual-DMA Pallas
        # paths; the XLA/ref paths keep minimal pads (the r3 headline
        # regression was the lane round-up taxing the jit path).
        self._plan_kwargs = dict(extra_pad=extra, pad_multiple=pad_mult,
                                 mosaic_align=mode in ("pallas",
                                                       "shard_pallas"))
        return self._csol.plan(gsizes, **self._plan_kwargs)

    def prepare_solution(self) -> None:
        """Setup pipeline (reference ``prepare_solution``,
        ``soln_apis.cpp:137-250``): settings adjustment → decomposition →
        var geometry → state allocation."""
        for h in self._hooks["before_prepare"]:
            h(self)
        self._ended = False
        # set-up's spans (kept: read where no profiler runs): analysis,
        # lowering and planning; the mesh; the resting state
        with span("setup.prepare", phase="setup", keep=True) as sp:
            with span("setup.plan", phase="setup", keep=True):
                self._program = self._plan_geometry()
            mode = self._mode
            self._mesh = self._shardings = None
            if mode in ("sharded", "shard_map", "shard_pallas"):
                from yask_tpu.parallel.mesh import (build_mesh,
                                                    state_shardings)
                with span("setup.mesh", phase="setup", keep=True,
                          mode=mode):
                    self._mesh = build_mesh(self._env, self._opts)
                    if mode == "sharded":
                        self._shardings = state_shardings(
                            self._mesh, self._program, self._opts)
            with span("setup.alloc", phase="setup", keep=True) as alloc:
                self._alloc_resting(self._run)
                held = self._run.state if self._run.resident is None \
                    else self._run.resident
                nbytes = sum(int(a.nbytes) for ring in held.values()
                             for a in ring)
                alloc.set(vars=len(held), bytes=nbytes)
            sp.set(mode=mode, vars=len(held), bytes=nbytes)

        self._vars = {v.get_name(): yk_var(self, v.get_name())
                      for v in self._soln.get_vars() if not v.is_scratch()}
        self._cur_step = 0
        self._jit_cache.clear()
        self._launch_attrs.clear()
        self._shard_rest.clear()
        self._pallas_tiling.clear()
        self._comm_plans.clear()
        for h in self._hooks["after_prepare"]:
            h(self)

    def is_prepared(self) -> bool:
        return self._program is not None

    # ------------------------------------------------------------------
    # state plumbing
    # ------------------------------------------------------------------

    def _check_prepared(self):
        if self._program is None:
            if getattr(self, "_ended", False):
                raise YaskException(
                    "end_solution was called; call prepare_solution "
                    "again to run")
            raise YaskException("prepare_solution has not been called")

    def _materialize_state(self) -> None:
        """Re-attach the (zero) global pads if state currently lives as
        device-resident sharded interiors, or as the padded shards a
        shard program left (:attr:`_resident` strips those first) — the
        lazy sync point for any host-visible var access between
        shard-mode runs that needs the global arrays."""
        if self._resident is None and self._state is None:
            if getattr(self, "_ended", False):
                raise YaskException(
                    "end_solution was called; call prepare_solution "
                    "again to access var data")
            raise YaskException(
                "solution state was lost (a shard-mode run failed after "
                "its buffers were donated); call prepare_solution again")
        if self._resident is not None:
            from yask_tpu.parallel.shard_step import _repad_global
            res, self._resident = self._resident, None
            self._state = _repad_global(self._program, list(res), res)
            self._state_on_device = True

    def _update_state_array(self, name: str, slot: int, fn) -> None:
        self._check_prepared()
        self._materialize_state()
        arr = self._state[name][slot]
        new = fn(np.asarray(arr))
        # Physical-boundary ghost cells are identically zero in every
        # execution mode (the value unexchanged halos hold in the reference
        # unless explicitly managed); masking here keeps jit / sharded /
        # shard_map / ref bit-consistent at domain edges.
        new = self._zero_pads(name, np.array(new))
        if self._state_on_device:
            import jax
            if self._shardings is not None:
                new = jax.device_put(new.astype(np.asarray(arr).dtype),
                                     self._shardings[name])
            else:
                new = jax.device_put(new.astype(np.asarray(arr).dtype))
        self._state[name][slot] = new

    def _zero_pads(self, name: str, arr: np.ndarray) -> np.ndarray:
        g = self._program.geoms[name]
        idxs = []
        for dn, kind in g.axes:
            if kind == "domain":
                idxs.append(slice(g.origin[dn],
                                  g.origin[dn]
                                  + self._opts.global_domain_sizes[dn]))
            else:
                idxs.append(slice(None))
        out = np.zeros_like(arr)
        out[tuple(idxs)] = arr[tuple(idxs)]
        return out

    def _state_to_host(self) -> None:
        self._materialize_state()
        if self._state_on_device:
            self._state = {k: [np.asarray(a) for a in ring]
                           for k, ring in self._state.items()}
            self._state_on_device = False

    def _state_to_device(self) -> None:
        rs = self._run
        if rs.padded is not None or rs.resident is not None:
            if self._mode in ("shard_map", "shard_pallas"):
                return  # shards already device-resident, in either form
            self._materialize_state()  # non-shard path needs padded state
        if not self._state_on_device:
            import jax
            # the host→device staging window is the DMA phase a trace
            # can actually observe (in-kernel DMA never re-enters
            # Python)
            with span("state.to_device", phase="dma", keep=True,
                      nvars=len(self._state)):
                out = {}
                for k, ring in self._state.items():
                    if self._shardings is not None:
                        out[k] = [jax.device_put(a, self._shardings[k])
                                  for a in ring]
                    else:
                        out[k] = [jax.device_put(a) for a in ring]
                self._state = out
            self._state_on_device = True

    # ------------------------------------------------------------------
    # run
    # ------------------------------------------------------------------

    def _step_seq(self, first_t: int, last_t: int):
        """Evaluation order for the step range (ascending for forward
        stencils, descending for reverse-time, reference ``run_solution``
        stride handling)."""
        if first_t > last_t:
            first_t, last_t = last_t, first_t
        n = last_t - first_t + 1
        start = first_t if self._ana.step_dir > 0 else last_t
        return start, n

    def run_solution(self, first_step_index: int,
                     last_step_index: Optional[int] = None) -> None:
        """Apply the stencil for the given step indices (inclusive), the
        reference's ``run_solution(first_t, last_t)`` hot path."""
        self._check_prepared()
        if last_step_index is None:
            last_step_index = first_step_index
        for h in self._hooks["before_run"]:
            h(self)
        start, n = self._step_seq(first_step_index, last_step_index)

        # Supervised mode: checkpoint cadence / watchdog / deadline knobs
        # re-enter run_solution per chunk with hooks swapped out, exactly
        # like trace mode below.  All-zero knobs (the default) make this
        # three int compares — a true no-op on the hot path.
        o = self._opts
        if (o.ckpt_every > 0 or o.watchdog_every > 0
                or o.run_deadline_secs > 0) \
                and not getattr(self, "_in_supervised", False):
            hooks, self._hooks = self._hooks, {k: [] for k in self._hooks}
            try:
                self._run_supervised(start, n)
            finally:
                self._hooks = hooks
            for h in self._hooks["after_run"]:
                h(self)
            return

        # Trace mode: advance one step at a time, dumping written state
        # after each (trace_mem analog). Hooks fire once for the whole
        # span, exactly as untraced.
        if self._trace_dir and n > 1:
            t = start
            hooks, self._hooks = self._hooks, {k: [] for k in self._hooks}
            try:
                for _ in range(n):
                    self.run_solution(t, t)
                    t += self._ana.step_dir
            finally:
                self._hooks = hooks
            for h in self._hooks["after_run"]:
                h(self)
            return

        if self._opts.do_auto_tune and self._mode in (
                "jit", "sharded", "pallas", "shard_pallas"):
            from yask_tpu.runtime.auto_tuner import AutoTuner
            AutoTuner(self).tune_if_needed()
        # after the tuner, which fills them for its trials and may then
        # rebuild the state on other pads
        self._refresh_derived()

        # the root of the runtime's span tree: one per leaf call (the
        # supervised and trace modes above re-enter per chunk), and
        # one row of the run's call record
        rec = self._run.begin_call(self._mode, start, n)
        try:
            with span("run.call", phase="compute", mode=self._mode,
                      first=start, n=n):
                self._run_steps(start, n)
        except BaseException:
            self._run.call = None   # a call that failed leaves no row
            raise
        self._run.end_call(rec, self._env.get_devices()[0])

        self._cur_step = start + n * self._ana.step_dir
        self._steps_done += n
        if self._trace_dir:
            self._trace_dump(self._cur_step)
        for h in self._hooks["after_run"]:
            h(self)

    def _run_steps(self, start: int, n: int) -> None:
        """Mode dispatch of one leaf ``run_solution`` call."""
        if self._mode == "ref":
            self._run_ref_steps(start, n)
        elif self._mode == "pallas":
            self._run_pallas_steps(start, n)
        elif self._mode in ("shard_map", "shard_pallas"):
            from yask_tpu.parallel.shard_step import (run_shard_map,
                                                      run_shard_pallas)
            runner = run_shard_map if self._mode == "shard_map" \
                else run_shard_pallas
            self._state_to_device()
            # wf_steps chunks the span so ONE compiled program length
            # serves any run length (programs are cached per length);
            # interiors stay device-resident across chunks. The runner
            # does its own timer accounting (compiles must stay out of
            # elapsed) and opens the launch/wait spans around its
            # program call.
            wf = self._opts.wf_steps if self._opts.wf_steps > 0 else n
            if self._mode == "shard_pallas":
                wf = n   # its fusion/grouping happens inside the program
            t, rem = start, n
            while rem > 0:
                k = min(wf, rem)
                runner(self, t, k)
                t += k * self._ana.step_dir
                rem -= k
        else:
            self._run_jit_steps(start, n)

    #: the modes that decline the hoist: each evaluates every scratch
    #: var in-tile (``hoist_kept``: ``declined``).  ``ref`` is the
    #: independent oracle; the shard modes have no derived array: since
    #: PR 57 their state rests as the padded shards the program computes
    #: on, so one could rest beside them, but no sharded deployment
    #: declares a scratch var to measure that against.
    IN_TILE_MODES = ("ref", "shard_map", "shard_pallas")

    def _lowered(self, hoist: bool):
        """The solution lowered with its step-invariant scratch vars
        hoisted (``SolutionAnalysis.hoisted``) or with every one left
        in-tile; the same object where the rule hoists none.
        ``_plan_geometry`` picks the context's by its mode."""
        hoisting = self._lowered_by[True]
        if hoist or not hoisting.ana.hoisted:
            return hoisting
        if False not in self._lowered_by:
            from yask_tpu.compiler.analysis import SolutionAnalysis
            from yask_tpu.compiler.lowering import CompiledSolution
            self._lowered_by[False] = CompiledSolution(
                self._soln, SolutionAnalysis(self._soln, hoist=False),
                dtype=hoisting.dtype)
        return self._lowered_by[False]

    def _in_tile_program(self, ops=None):
        """The step program that evaluates EVERY scratch var in-tile, on
        this context's geometry: it runs on the context's state, where
        a derived array rides along unread.  The numpy oracle's
        (``run_ref``: independent of the fill, of its ghost values and
        of its staleness) and the ensemble's (a masked member's ghost
        cells are zeroed inside the batched program, after any fill a
        member could bring)."""
        return self._lowered(False).plan(
            self._opts.global_domain_sizes, ops=ops, **self._plan_kwargs)

    def _derive_from(self) -> Tuple:
        """The source arrays (the objects) the state holds now."""
        return tuple(self._state[name][0]
                     for name in self._ana.derive_sources)

    def _refresh_derived(self) -> None:
        """Fill the hoisted scratch vars' arrays
        (``SolutionAnalysis.hoisted``) where they are stale or not there
        yet, on the device, under the span ``yt.state.derive``
        (``vars``, ``bytes``, ``secs``) and the counter
        ``state.derived_fills``.

        Lazy, at the head of a run: the arrays remember the source
        ARRAY OBJECTS they were computed from (``RunState.derived_from``)
        and are stale once the state holds another object for a source
        -- which every write does, a public fill (``_update_state_array``
        puts a new array), a restore, and a caller that installs device
        arrays into ``ctx._state`` itself after ``prepare_solution``
        (the benchmark's seeding).  An untouched state costs one ``is``
        a source.  Nothing allocates them before the first fill, and
        whatever rebuilds the state at another shape
        (``_replan_pallas_pads``) drops them."""
        names = self._ana.hoisted
        if not names:
            return
        self._state_to_device()
        srcs = self._derive_from()
        was = self._run.derived_from
        if was is not None and all(a is b for a, b in zip(was, srcs)):
            return
        import jax
        from yask_tpu.obs.metrics import get_registry
        state = {n: self._state[n] for n in self._ana.derive_sources}
        for n in names:
            self._state.pop(n, None)    # free before the new ones
        with span("state.derive", phase="compute", keep=True,
                  vars=len(names)) as sp:
            t0 = time.perf_counter()
            fn = self._jit_cache.get(("derive",))
            if fn is None:
                sh = self._shardings
                fn = self._jit_cache[("derive",)] = jax.jit(
                    self._program.derive,
                    out_shardings=None if sh is None else
                    {n: [sh[n]] for n in names})
            out = jax.block_until_ready(fn(state))
            self._state.update(out)
            sp.set(bytes=sum(int(a.nbytes) for ring in out.values()
                             for a in ring),
                   secs=round(time.perf_counter() - t0, 6))
        self._run.derived_from = srcs
        get_registry().counter("state.derived_fills").inc()

    def _run_ref_steps(self, start: int, n: int) -> None:
        from yask_tpu.compiler.lowering import NumpyOps
        self._state_to_host()
        prog = self._in_tile_program(ops=NumpyOps())
        with self._run_timer:
            t = start
            for _ in range(n):
                self._state = prog.step(self._state, t)
                t += self._ana.step_dir

    # ------------------------------------------------------------------
    # supervised runs: checkpoint cadence, watchdog, degradation ladder
    # ------------------------------------------------------------------

    def _run_supervised(self, start: int, n: int) -> None:
        """Chunked run with checkpoint cadence, per-chunk deadline, a
        cheap device-state watchdog, and on a classified fault a rollback
        to the last good snapshot + retry down the mode-degradation
        ladder (``shard_pallas → shard_map → jit``, ``pallas → jit``).

        Snapshots are interior-coordinate (:mod:`..resilience.checkpoint`)
        so a rollback taken in one mode restores bit-identically into the
        next rung.  A LOCAL breaker (recorded manually — chunk successes
        must not reset it) bounds total degrade attempts; anomalies from
        the watchdog classify as :class:`ResultAnomaly` and take the same
        path.  Progress is tracked as ``(last_good, last_done)`` pairs —
        never inferred from ``_cur_step``."""
        import os
        from yask_tpu.resilience import checkpoint as ckpt
        from yask_tpu.resilience.faults import Breaker, Fault
        from yask_tpu.resilience.guard import guarded_call
        from yask_tpu.resilience.journal import SessionJournal

        o = self._opts
        cad = max(0, int(o.ckpt_every))
        wd = max(0, int(o.watchdog_every))
        ddl = float(o.run_deadline_secs) if o.run_deadline_secs > 0 \
            else None
        dirn = self._ana.step_dir
        ckpt_file = None
        if cad:
            ckpt_dir = o.ckpt_dir or ckpt.default_ckpt_dir()
            if ckpt_dir:
                ckpt_file = os.path.join(
                    ckpt_dir, f"{self.get_name()}.ckpt.npz")

        def _journal(outcome, attempt, **detail):
            # best-effort: supervision journaling is evidence, never a
            # dependency (journal.record raises on I/O failure by
            # contract — a run must survive a read-only journal dir)
            try:
                SessionJournal().record(
                    "run", case=self.get_name(), outcome=outcome,
                    attempt=attempt, **detail)
            except Exception:  # noqa: BLE001
                pass

        # manual enter/exit: the supervised root span brackets the
        # whole chunk loop without re-indenting it (span ignores
        # exception info by design — faults are journaled, not traced)
        _sp = span("run.supervised", phase="compute",
                    solution=self.get_name(), steps=n,
                    ckpt_every=cad, watchdog_every=wd)
        _sp.__enter__()
        self._in_supervised = True
        try:
            last_good = ckpt.extract_snapshot(self)
            last_done = 0
            if ckpt_file:
                guarded_call(ckpt.save_checkpoint, self, ckpt_file,
                             site="ckpt.save")
            ladder = ckpt.degradation_ladder(self._mode)
            from_mode = self._mode
            breaker = Breaker()
            ladder_path = []
            attempt = 1
            stride = n
            if cad:
                stride = min(stride, cad)
            if wd:
                stride = min(stride, wd)
            done = last_done
            while done < n:
                k = min(stride, n - done)
                t0 = start + done * dirn
                try:
                    guarded_call(self.run_solution, t0,
                                 t0 + (k - 1) * dirn,
                                 site="run.chunk", deadline_secs=ddl)
                    done += k
                    # scan BEFORE the cadence snapshot: corrupt state
                    # must never become the rollback target
                    if wd and (done >= n or done % wd == 0):
                        self._watchdog_scan()
                except Fault as f:
                    breaker.record(f)
                    _journal("fault", attempt, kind=f.kind,
                             site=getattr(f, "site", "run.chunk"),
                             rollback_step=start + last_done * dirn,
                             from_mode=self._mode,
                             ladder=list(ladder))
                    if breaker.tripped or not ladder:
                        raise
                    to_mode = ladder.pop(0)
                    self._opts.mode = to_mode
                    self.prepare_solution()
                    if not ckpt.apply_snapshot(self, last_good):
                        raise
                    ladder_path.append(to_mode)
                    attempt += 1
                    done = last_done
                    continue
                if cad and done < n and done % cad == 0:
                    last_good = ckpt.extract_snapshot(self)
                    last_done = done
                    if ckpt_file:
                        guarded_call(ckpt.save_checkpoint, self,
                                     ckpt_file, site="ckpt.save")
            if ckpt_file:
                guarded_call(ckpt.save_checkpoint, self, ckpt_file,
                             site="ckpt.save")
            if ladder_path:
                _journal("ok", attempt, from_mode=from_mode,
                         final_mode=self._mode,
                         ladder_path=ladder_path, attempts=attempt)
        finally:
            self._in_supervised = False
            _sp.__exit__(None, None, None)

    def _watchdog_scan(self) -> None:
        """Cheap per-cadence state scan: nonfinite / all-zero written
        interiors raise :class:`ResultAnomaly` (same thresholds as
        :mod:`..resilience.sanity`), feeding the supervision ladder."""
        from yask_tpu.resilience.faults import ResultAnomaly, maybe_corrupt
        from yask_tpu.resilience.sanity import check_output
        self._materialize_state()
        gsz = self._opts.global_domain_sizes
        arrs = {}
        for name, g in self._program.geoms.items():
            if not g.is_written or g.is_scratch:
                continue
            idx = tuple(
                slice(g.origin[dn], g.origin[dn] + gsz[dn])
                if kind == "domain" else slice(None)
                for dn, kind in g.axes)
            arrs[name] = [np.asarray(self._state[name][-1][idx])]
        arrs = maybe_corrupt("run.scan", arrs)
        verdict = check_output(arrs)
        if not verdict["ok"]:
            raise ResultAnomaly(
                "watchdog scan flagged written state: "
                + ", ".join(verdict["anomalies"]),
                site="run.scan")

    def _persistent_key(self, kind: str, **build) -> Tuple:
        """Cross-process cache key for :func:`yask_tpu.cache.aot_compile`.

        The key must fully determine the traced program: the equation
        structure (``skey`` covers radii, coefficients, conditions —
        the solution *name* alone under-keys, e.g. radius is a
        constructor arg), the padded state geometry the trace bakes in
        (shapes, origins, ring depths), dtype, step direction, and the
        caller's build parameters (step count / fuse depth / variant
        tuple via ``**build``).  The jax/platform/git fingerprint is
        NOT here — ``aot_compile`` hashes it into the content address
        itself."""
        import hashlib
        eqs = hashlib.sha256(
            repr([e.skey() for e in self._soln.get_equations()])
            .encode()).hexdigest()[:16]
        geoms = tuple(
            (name, tuple(g.shape), g.alloc, g.is_scratch,
             tuple(sorted(g.origin.items())), tuple(g.axes))
            for name, g in sorted(self._program.geoms.items()))
        return (kind, self.get_name(), eqs, str(self._program.dtype),
                self._ana.step_dir, geoms, tuple(sorted(build.items())))

    def _compile_span(self, kind: str, **attrs):
        """The span ``yt.compile.chunk`` of one build; inside a leaf
        call it also counts in the call's record (0 after warm-up)."""
        if self._run.call is not None:
            self._run.call.compiles += 1
        return span("compile.chunk", phase="compile", keep=True,
                    kind=kind, **attrs)

    def _get_compiled_chunk(self, n: int):
        """Compiled function advancing exactly ``n`` steps (cached per n;
        the reference caches per-size auto-tuner results the same way)."""
        key = ("compiled", n)
        if key in self._jit_cache:
            return self._jit_cache[key]
        import jax
        from jax import lax
        from yask_tpu.cache import aot_compile
        prog = self._program
        dirn = self._ana.step_dir

        # the function's name is the compiled module's in a device
        # trace (``jit_yt_xla_chunk``): the XLA path is found by it
        def yt_xla_chunk(state, t0):
            def body(carry, _):
                st, t = carry
                # the XLA path's step, named for the device trace
                with jax.named_scope(SCOPE_XLA_STEP):
                    st2 = prog.step(st, t)
                return (st2, t + dirn), None
            (st, _), _ = lax.scan(body, (state, t0), None, length=n)
            return st

        self._state_to_device()
        with self._compile_span("jit", n=n):
            res = aot_compile(yt_xla_chunk, (self._state, 0),
                              key=self._persistent_key("jit_chunk", n=n),
                              platform=self._env.get_platform(),
                              donate_argnums=0)
        self._compile_secs += res.compile_secs
        self._last_cache_hit = res.cache_hit
        self._jit_cache[key] = res.fn
        return res.fn

    def _run_jit_steps(self, start: int, n: int) -> None:
        """Advance ``n`` steps in chunks of ``wf_steps`` (the temporal-
        tiling analog: one compiled chunk per wf_steps steps, reference
        wave-front stride over the step loop, ``context.cpp:352``)."""
        wf = self._opts.wf_steps if self._opts.wf_steps > 0 else n
        self._run_groups(start, n, wf, self._get_compiled_chunk)
        if self._run.derived_from is not None:
            # the XLA chunk is donated, and returns, the whole state:
            # the sources a fill read come back as other objects, their
            # values and the derived arrays' as they were
            self._run.derived_from = self._derive_from()

    def _run_groups(self, start: int, n: int, wf: int,
                    get_chunk: Callable) -> None:
        """Advance ``n`` steps as ``n // wf`` launches of the ``wf``-step
        chunk and one of the ``n mod wf``-step chunk, each from
        ``get_chunk(k)``; one wait at the end.

        *What a call holds.*  The context's state follows the launches:
        once a launch is enqueued its outputs are the state.  A Pallas
        launch writes them onto the ring slots the launch before it
        evicted (``_PallasLaunch``; ``RunState.spare``, donated) and
        leaves its own evicted slots for the next, so the device holds
        the ring and one given-up generation of the written slots
        through the whole loop: every launch is enqueued before the
        first has run and none asks the allocator for anything (iso3dfd
        768^3 on a v5e: two generations of the pressure ring fit, three
        do not).  An XLA chunk is donated, and returns, the whole
        state.

        *A call that raises* leaves state and step position agreeing:
        at the K-group boundary before the launch that raised.  A fault
        the device reports only in the wait leaves them at the call's
        end, the state bound to the failed launches' outputs: reading
        it raises again, and a supervised run rolls back to its last
        good snapshot."""
        import jax
        self._state_to_device()
        sizes = [wf] * (n // wf) + ([n % wf] if n % wf else [])
        # Pre-compile outside the timed section (the reference excludes
        # warmup from trials similarly, yask_main.cpp:131).
        fns = {k: get_chunk(k) for k in dict.fromkeys(sizes)}
        # arrays a launch returns, of those it is handed: a Pallas
        # launch its kernel's, of its operands' (the rest it keeps by
        # reference), ``onto`` given-up slots but those it had to make;
        # an XLA chunk is donated, and returns, the whole state
        arrays = {k: sum(len(self._state[name]) for name in
                         getattr(fn, "operands", self._state))
                  for k, fn in fns.items()}
        written = {k: getattr(fn, "written", arrays[k])
                   for k, fn in fns.items()}
        dirn = self._ana.step_dir
        t = start
        run = self._run
        rec = run.call
        try:
            with self._run_timer:
                for k in sizes:
                    fn = fns[k]
                    onto = fn.onto() if hasattr(fn, "onto") else written[k]
                    with span("run.launch", phase="compute", k=k,
                              written=written[k], onto=onto,
                              kept=arrays[k] - written[k]):
                        t0 = rec.clock()
                        # (not ``self._state =``: the given-up slots
                        # are this loop's, and stay)
                        run.state = fn(run.state, t)
                        rec.launch(k, rec.clock() - t0, onto)
                    t += k * dirn
                with span("run.wait", phase="compute"):
                    t0 = rec.clock()
                    jax.block_until_ready(self._state)
                    rec.wait_secs += rec.clock() - t0
        except BaseException:
            # run_solution moves the step position only past a call
            # that returned: this one's goes with the state it leaves
            self._cur_step = t
            self._steps_done += abs(t - start)
            raise

    def vmem_budget(self, fuse_steps: Optional[int] = None) -> int:
        """Pallas VMEM tile budget in bytes for a kernel fusing
        ``fuse_steps`` steps (default: the configured ``wf_steps``):
        the ``-vmem_mb`` knob, or the device's default for that fuse
        depth and this solution's stage count — the capability table's
        live-value model (a loose 100 MiB under CPU interpret, where
        VMEM is emulated and the budget only shapes planning)."""
        mb = self._opts.vmem_budget_mb
        if mb > 0:
            return mb * 2 ** 20
        from yask_tpu.ops.pallas_stencil import default_vmem_budget
        if fuse_steps is None:
            fuse_steps = max(self._opts.wf_steps, 1)
        return default_vmem_budget(self._env.get_platform(),
                                   self._env.get_device_kind(),
                                   fuse_steps, len(self._ana.stages),
                                   len(self._ana.tile_scratch))

    def _pallas_pad_needs(self, k: int) -> Dict[str, Tuple[int, int]]:
        """Per-lead-dim ``(left, right)`` pallas pad requirement for fuse
        depth ``k`` — the ONE definition prepare-time planning and
        :meth:`_replan_pallas_pads` both use (a replan that plans leaner
        pads than prepare would silently knock engaged skew dims back to
        uniform shrink after tuning).

        Beyond the radius×k halo, the dim the skewed wavefront MAY
        engage (the stream dim) gets extra RIGHT pad: ceil coverage runs
        (k−1)·r further right than the uniform grid (final-level writes
        sit shifted left), and its slabs round out to the sublane tile.

        And a block need not divide its extent: the right pad of a lead
        dim grows by the rows the last tile of the block the build will
        choose walks past the edge, where the pads above do not hold
        them already (:meth:`_block_overshoot_pad`; nothing where the
        block divides)."""
        step_rad = self._ana.fused_step_radius()
        lead = self._ana.domain_dims[:-1]
        sk_dims = lead[-1:] if self._opts.skew_wavefront else ()
        needs = {}
        for d in lead:
            rd = step_rad.get(d, 0)
            need = rd * max(k, 1)
            need_r = need
            if d in sk_dims:
                from yask_tpu.compiler.lowering import tpu_tile_dims
                need_r = need + 2 * tpu_tile_dims(self._csol.dtype)[0]
                # Misaligned (non-sublane-multiple) stream radii: the
                # skewed tiling computes E_sk extra right width and its
                # widened slabs need the same again in rounding room
                # (single E_sk definition:
                # pallas_stencil.skew_extra_width).
                from yask_tpu.ops.pallas_stencil import skew_extra_width
                need_r += 2 * skew_extra_width(self._csol.dtype, rd)
            needs[d] = (need, need_r)
        for d, need_r in self._block_overshoot_pad(k, needs).items():
            needs[d] = (needs[d][0], need_r)
        return needs

    def _block_overshoot_pad(self, k: int,
                             needs: Dict[str, Tuple[int, int]]
                             ) -> Dict[str, int]:
        """The right pad of each lead dim whose last DMA window would
        end outside an allocation planned on ``needs``: a block need not
        divide its extent (801 = 3² × 89), the kernel covers a span by
        ceil and masks what lies past the edge, and the rows the last
        tile walks past it must exist.  Nothing for a dim whose block
        divides (every array keeps the shape it had).

        Tile sizes do not depend on right pads, so the block is planned
        on a geometry with room to spare — the build's own plan for the
        configured depth ``k``, the planner's block or the explicit
        ``-b_*`` one, by the same arguments :meth:`_get_pallas_chunk`
        builds with — and how far the last windows of the blocks it
        passed through reached is held against the allocations
        ``needs`` alone would give.  A shorter last group of
        a call plans a block of its own on these pads, and one the build
        cannot plan (a tuner's deepest K) gets none: ``_fit_block``
        still keeps every window inside an allocation, and its
        ``block_fitted`` reason says what that cost."""
        from yask_tpu.ops.pallas_stencil import build_pallas_chunk
        gsizes = self._opts.global_domain_sizes
        pads = self._merged_pads(needs)
        roomy = dict(pads, **{d: (pads[d][0], pads[d][1] + gsizes[d])
                              for d in needs})
        try:
            plan = build_pallas_chunk(
                self._csol.plan(gsizes, extra_pad=roomy),
                vmem_budget=self.vmem_budget(k), plan_only=True,
                **self._pallas_build_args(k))
        except YaskException:
            return {}
        geoms = self._csol.plan(gsizes, extra_pad=pads).geoms
        short = {d: 0 for d in needs}
        for key, end in plan["window_reach"].items():
            n, d = key.split("/")
            short[d] = max(short[d],
                           end - geoms[n].shape[geoms[n].axis_of(d)])
        return {d: pads[d][1] + rows for d, rows in short.items() if rows}

    def _merged_pads(self, needs: Dict[str, Tuple[int, int]]
                     ) -> Dict[str, Tuple[int, int]]:
        """``extra_pad`` of every domain dim: the ``-mp`` minimum,
        raised to ``needs`` where a dim has one."""
        extra = {d: (self._opts.min_pad_sizes[d],) * 2
                 for d in self._ana.domain_dims}
        for d, (need, need_r) in needs.items():
            extra[d] = (max(extra[d][0], need), max(extra[d][1], need_r))
        return extra

    def _replan_pallas_pads(self, k: int) -> None:
        """Shrink pallas pads back to radius×k after the tuner settles.

        Pads were pre-planned for ``tune_max_wf_steps`` so the joint
        walk could *grow* K; keeping them would tax every ring slot's
        HBM footprint forever (e.g. radius 8 × Kmax 16 = 128 cells per
        side). Interiors are migrated into right-sized arrays (pads stay
        identically zero — the framework invariant) and the jit cache is
        cleared: compiled chunks are shape-keyed, so the tuned point
        recompiles once at production shape. Note a later
        ``reset_auto_tuner`` re-tune can then only shrink K again."""
        if self._mode != "pallas":
            return
        extra = self._merged_pads(self._pallas_pad_needs(k))
        if extra == self._plan_kwargs.get("extra_pad"):
            return
        import jax.numpy as jnp
        gsz = self._opts.global_domain_sizes
        new_kwargs = dict(self._plan_kwargs, extra_pad=extra)
        new_prog = self._csol.plan(gsz, **new_kwargs)
        old_prog = self._program

        def interior(g):
            return tuple(
                slice(g.origin[dn], g.origin[dn] + gsz[dn])
                if kind == "domain" else slice(None)
                for dn, kind in g.axes)

        # (a derived array is not migrated: its pads hold f(source's
        # ghost), not zeros, so the next run fills it anew at the new
        # shape from the migrated sources)
        self._run.derived_from = None
        new_state = {}
        for name, ring in self._state.items():
            og, ng = old_prog.geoms[name], new_prog.geoms[name]
            if ng.is_derived:
                continue
            oidx, nidx = interior(og), interior(ng)
            new_state[name] = [
                jnp.zeros(tuple(ng.shape), dtype=new_prog.dtype)
                .at[nidx].set(jnp.asarray(a)[oidx]) for a in ring]
        self._program = new_prog
        self._plan_kwargs = new_kwargs
        self._state = new_state
        self._state_on_device = True
        self._jit_cache.clear()
        self._launch_attrs.clear()
        self._pallas_tiling.clear()
        self._comm_plans.clear()

    def _pallas_variant_key(self) -> Tuple:
        """(skew, vmem_mb, ...) cache-key suffix shared by
        EVERY pallas build variant (single-device and shard): these are
        the settings beyond (K, block) that change the compiled kernel,
        so both the jit cache and the tiling record must key on them —
        the vmem ladder in particular walks the same (K, block) at
        several budgets and the rungs must never alias each other's
        executables."""
        o = self._opts
        skw = None if o.skew_wavefront else False
        ovx = getattr(o, "overlap_exchange", "auto")
        # comm-schedule knobs: the shard exchange bodies bake the
        # CommPlan's order/coalescing into the traced program, so
        # toggling them must never alias another schedule's executable
        cmo = getattr(o, "comm_order", "")
        col = getattr(o, "coalesce", "auto")
        # push-memory fusion changes which vars ride the DMA paths, so
        # push variants must never alias each other's executables
        psh = self._push_arg()
        # pipeline-fusion signature: a merged producer→consumer chain
        # compiles a different kernel than any standalone solution
        psig = self._pipeline_sig or ""
        return (skw, o.vmem_budget_mb, ovx, cmo, col, psh, psig)

    def _push_arg(self):
        """The ``build_pallas_chunk(push=)`` argument the configured
        ``push_memory`` setting resolves to — single definition shared
        with the checker's ``plan_pallas`` so the static plan and the
        executed build can never disagree.  ``auto`` engages only for
        pipeline-fused contexts: a plain solution's user expects every
        written var observable after ``run()``, a pipeline hides its
        pushed intermediates behind :meth:`SolutionPipeline.get_var`."""
        pm = getattr(self._opts, "push_memory", "auto")
        if pm == "off":
            return False
        if pm == "on":
            return None
        if pm == "force":
            return True
        if pm != "auto":
            from yask_tpu.utils.exceptions import YaskException
            raise YaskException(
                f"bad -push value '{pm}': expected auto|on|force|off")
        return None if getattr(self, "_pipeline", None) is not None \
            else False

    def comm_plan(self, K: Optional[int] = None):
        """The communication schedule (CommPlan) for the configured
        shard mode — derived once per (mode, K, knobs) and cached; the
        shard_map/shard_pallas exchange paths and the checker's COMM
        rules consume this single instance (the TilePlan discipline
        applied to collectives)."""
        from yask_tpu.parallel.comm_plan import build_comm_plan
        mode = self._mode or self._opts.mode
        if K is None:
            K = max(self._opts.wf_steps, 1) if mode == "shard_pallas" \
                else 1
        key = (mode, int(K), getattr(self._opts, "comm_order", ""),
               getattr(self._opts, "coalesce", "auto"))
        if key not in self._comm_plans:
            self._comm_plans[key] = build_comm_plan(self, K=K)
        return self._comm_plans[key]

    def _pallas_build_key(self, K: int):
        """(cache key, block tuple, skew arg) for the configured pallas
        build — single definition so stats can look up the tiling the
        built kernel actually chose (ADVICE r3)."""
        bs = self._opts.block_sizes
        blk = None
        if any(bs[d] > 0 for d in self._ana.domain_dims[:-1]):
            blk = tuple(bs[d] if bs[d] > 0 else 8
                        for d in self._ana.domain_dims[:-1])
        var = self._pallas_variant_key()
        return ("pallas", K, blk) + var, blk, var[0]

    def _pallas_build_args(self, K: int) -> Dict:
        """What ``build_pallas_chunk`` is told of the configured
        one-device build of depth ``K``, but the program and the budget:
        one definition for the build that runs, the plan prepare sizes
        the pads by and the checker's."""
        _key, blk, skw = self._pallas_build_key(K)
        return dict(fuse_steps=K, block=blk, skew=skw,
                    vinstr_cap=self._opts.max_tile_vinstr,
                    push=self._push_arg())

    def _get_pallas_chunk(self, K: int):
        """Compiled fused-Pallas chunk for K steps with the current block
        settings (cached per (K, block) — the auto-tuner varies both)."""
        key, blk, _skw = self._pallas_build_key(K)
        if key not in self._jit_cache:
            from yask_tpu.ops.pallas_stencil import (build_pallas_chunk,
                                                     plan_attrs)
            interp = self._env.get_platform() != "tpu"
            # planned before the span opens, so the plan rides the
            # profiler's annotation as well as the JSONL row
            chunk, tile_bytes = build_pallas_chunk(
                self._program, interpret=interp,
                vmem_budget=self.vmem_budget(K), onto=True,
                **self._pallas_build_args(K))
            with self._compile_span("pallas", k=K,
                                    **plan_attrs(chunk.tiling)):
                self._state_to_device()
                t0c = time.perf_counter()
                fn = _PallasLaunch(_launch_exe(chunk.written),
                                   chunk.merge,
                                   writes=chunk.written.writes,
                                   operands=chunk.written.operands,
                                   ctx=self)
                if interp:
                    # the interpreter's, compiled at its first call: a
                    # given-up slot is consumed on a CPU as on the chip
                    import jax
                    fn.exe = jax.jit(fn.exe,
                                     donate_argnums=_LAUNCH_DONATES)
                else:
                    # AOT-compile so the first timed call doesn't
                    # include XLA/Mosaic compilation (mirrors
                    # _get_compiled_chunk).
                    from yask_tpu.cache import aot_compile
                    res = aot_compile(
                        fn.exe, (fn.takes(self._state), 0,
                                 fn.evicts(self._state)),
                        key=self._persistent_key(
                            "pallas_onto", K=K, blk=blk,
                            variant=self._pallas_variant_key()),
                        platform=self._env.get_platform(),
                        donate_argnums=_LAUNCH_DONATES)
                    fn.exe = res.fn
                    self._last_cache_hit = res.cache_hit
            self._jit_cache[key] = fn
            # only after a successful compile: a Mosaic failure must not
            # leave stats modeling a tiling that never ran
            secs = time.perf_counter() - t0c
            self._pallas_tiling[key] = dict(
                chunk.tiling, compile_secs=secs,
                cache_hit=None if interp else res.cache_hit)
            self._compile_secs += secs
            self._env.trace_msg(
                f"pallas chunk: K={K}, blocks={blk or 'planner'}, "
                f"tile {tile_bytes / 2**20:.2f} MiB")
        return self._jit_cache[key]

    def _run_pallas_steps(self, start: int, n: int) -> None:
        """Advance using the fused Pallas sweep alone: ⌊n/K⌋ launches of
        the K-step chunk (K = wf_steps temporal fusion, on pads planned
        for wf_steps) and, for the last ``n mod K`` steps, one launch of
        the fused chunk of that length.  No step leaves the kernel for
        the XLA path."""
        K = min(max(self._opts.wf_steps, 1), n)
        self._run_groups(start, n, K, self._get_pallas_chunk)

    def run_ref(self, first_step_index: int,
                last_step_index: Optional[int] = None) -> None:
        """Run the independent eager-numpy oracle over the same state
        (reference ``run_ref``, ``context.cpp:46``)."""
        self._check_prepared()
        if last_step_index is None:
            last_step_index = first_step_index
        start, n = self._step_seq(first_step_index, last_step_index)
        self._run_ref_steps(start, n)
        self._cur_step = start + n * self._ana.step_dir
        self._steps_done += n

    # ------------------------------------------------------------------
    # auto-tuning (yk_solution_api.hpp:839-881)
    # ------------------------------------------------------------------

    def run_auto_tuner_now(self, candidates=None, min_trial_secs=None) -> int:
        """Offline auto-tune (advances real steps, like the reference)."""
        self._check_prepared()
        from yask_tpu.runtime.auto_tuner import AutoTuner
        return AutoTuner(self).run_auto_tuner_now(
            candidates=candidates, min_trial_secs=min_trial_secs)

    def reset_auto_tuner(self, enable: bool = True) -> None:
        self._tuned = False
        self._opts.do_auto_tune = enable

    def is_auto_tuner_enabled(self) -> bool:
        return self._opts.do_auto_tune and not getattr(self, "_tuned", False)

    # ------------------------------------------------------------------
    # validation (yask_main.cpp:564-616 -validate flow)
    # ------------------------------------------------------------------

    def compare_data(self, other: "StencilContext", epsilon: float = 1e-4,
                     abs_epsilon: float = 1e-7,
                     field_epsilon: float = 0.0) -> int:
        """Element-wise compare of all common vars against another context;
        returns #mismatches. Mixed absolute+relative tolerance like the
        reference's within-tolerance check (``compare_data``): a point
        mismatches only if |x−y| > abs_eps + eps·max(|x|,|y|), so fp32
        reassociation noise at near-cancellation points doesn't count.

        ``field_epsilon`` adds a FIELD-scale term to the tolerance:
        ``field_eps · max(‖x‖∞, ‖y‖∞)`` per compared array.  Stencil
        updates sum neighbor values, so rounding error at a point is
        ulps of the largest summed INPUT, not of the local result — a
        point whose true value nearly cancels to zero can carry an
        absolute error of ~ulp(field max) that no pointwise relative
        tolerance models.  Use it when comparing execution paths with
        different FP association (fused in-tile vs XLA-fused order);
        the default 0.0 keeps the strict pointwise behavior.  A real
        geometry bug (dropped halo band, stale margin) produces
        O(field) errors and still fails any small field_epsilon."""
        self._check_prepared()
        other._check_prepared()
        self._materialize_state()
        other._materialize_state()

        def interior(ctx, name, arr):
            g = ctx._program.geoms[name]
            idxs = []
            for dn, kind in g.axes:
                if kind == "domain":
                    idxs.append(slice(
                        g.origin[dn],
                        g.origin[dn] + ctx._opts.global_domain_sizes[dn]))
                else:
                    idxs.append(slice(None))
            return np.asarray(arr, dtype=np.float64)[tuple(idxs)]

        bad = 0
        for name, ring in self._state.items():
            # (a derived array is its sources', which are compared)
            if name not in other._state \
                    or self._program.geoms[name].is_derived:
                continue
            oring = other._state[name]
            for a, b in zip(ring[::-1], oring[::-1]):
                x = interior(self, name, a)
                y = interior(other, name, b)
                if x.shape != y.shape:
                    bad += x.size
                    continue
                tol = abs_epsilon + epsilon * np.maximum(np.abs(x), np.abs(y))
                if field_epsilon > 0.0 and x.size:
                    scale = max(np.abs(x).max(), np.abs(y).max())
                    tol = tol + field_epsilon * scale
                bad += int((np.abs(x - y) > tol).sum())
        return bad

    # ------------------------------------------------------------------
    # tracing (SURVEY §5: trace_mem analog — per-step write dumps,
    # diffable by tools/analyze_trace to find the first divergent write)
    # ------------------------------------------------------------------

    def set_trace_dir(self, path: Optional[str]) -> None:
        """Enable per-step state dumps into ``path`` (one .npz per step,
        interiors of all written vars). The runtime then advances steps
        one at a time so each step's writes are observable — the analog of
        the reference's ``trace_mem=1`` builds (``common_utils.hpp:201``)."""
        self._trace_dir = path
        if path:
            import os
            os.makedirs(path, exist_ok=True)

    def _trace_dump(self, t_written: int) -> None:
        import os
        self._materialize_state()
        arrs = {}
        for name, ring in self._state.items():
            g = self._program.geoms[name]
            if not g.is_written:
                continue
            idxs = []
            for dn, kind in g.axes:
                if kind == "domain":
                    idxs.append(slice(
                        g.origin[dn],
                        g.origin[dn] + self._opts.global_domain_sizes[dn]))
                else:
                    idxs.append(slice(None))
            arrs[name] = np.asarray(ring[-1])[tuple(idxs)]
        np.savez(os.path.join(self._trace_dir, f"step_{t_written}.npz"),
                 **arrs)

    # ------------------------------------------------------------------
    # checkpoint / resume (SURVEY §5: the reference has none; the slice
    # get/set API defines the serialization surface — we provide whole-
    # solution snapshot/restore on top of the same state)
    # ------------------------------------------------------------------

    @staticmethod
    def _ckpt_path(path: str) -> str:
        # np.savez appends '.npz' to extensionless paths; normalize so a
        # save/load round trip works with any path string.
        return path if path.endswith(".npz") else path + ".npz"

    def save_checkpoint(self, path: str, backend: str = "npz") -> None:
        """Snapshot all var state + step position.

        ``backend="npz"`` (default) writes one ``.npz`` file;
        ``backend="orbax"`` writes an Orbax PyTree checkpoint directory
        (async-capable, multi-host-ready storage format — the scale
        path for big distributed states; exceeds the reference, which
        has no checkpointing at all)."""
        self._check_prepared()
        self._materialize_state()
        carried = self._carried_state()
        if backend == "orbax":
            import os
            import orbax.checkpoint as ocp
            tree = {
                "cur_step": np.asarray(self._cur_step),
                "steps_done": np.asarray(self._steps_done),
                "state": {name: {f"slot{i}": np.asarray(a)
                                 for i, a in enumerate(ring)}
                          for name, ring in carried.items()},
            }
            ocp.PyTreeCheckpointer().save(
                os.path.abspath(path), tree, force=True)
            return
        if backend != "npz":
            raise YaskException(
                f"unknown checkpoint backend '{backend}' "
                "(use 'npz' or 'orbax')")
        payload = {"__cur_step__": np.asarray(self._cur_step),
                   "__steps_done__": np.asarray(self._steps_done)}
        for name, ring in carried.items():
            for i, a in enumerate(ring):
                payload[f"{name}__slot{i}"] = np.asarray(a)
        np.savez(self._ckpt_path(path), **payload)

    def _carried_state(self) -> Dict[str, List]:
        """The rings a checkpoint carries: every array of the state but
        the hoisted scratch vars' (rebuilt from their sources, which a
        restore replaces; never saved, never pulled)."""
        return {name: ring for name, ring in self._state.items()
                if not self._program.geoms[name].is_derived}

    def load_checkpoint(self, path: str, backend: str = "npz") -> None:
        """Restore a snapshot (shapes must match the prepared geometry)."""
        self._check_prepared()
        # materialize (not discard) resident interiors: the restore
        # validates shapes against the current rings
        self._materialize_state()
        if backend == "orbax":
            import os
            import orbax.checkpoint as ocp
            tree = ocp.PyTreeCheckpointer().restore(os.path.abspath(path))
            data = {"__cur_step__": tree["cur_step"],
                    "__steps_done__": tree["steps_done"]}
            for name, slots_ in tree["state"].items():
                for k, a in slots_.items():
                    data[f"{name}__slot{k[4:]}"] = a
        elif backend == "npz":
            data = np.load(self._ckpt_path(path))
        else:
            raise YaskException(
                f"unknown checkpoint backend '{backend}' "
                "(use 'npz' or 'orbax')")
        # (the derived arrays stay as they are: stale once their
        # sources are the checkpoint's, and refilled by the next run)
        new_state: Dict[str, List] = dict(self._state)
        for name, ring in self._carried_state().items():
            arrs = []
            for i, old in enumerate(ring):
                key = f"{name}__slot{i}"
                if key not in data:
                    raise YaskException(f"checkpoint missing '{key}'")
                a = data[key]
                if tuple(a.shape) != tuple(np.asarray(old).shape):
                    raise YaskException(
                        f"checkpoint shape mismatch for '{name}': "
                        f"{a.shape} vs {np.asarray(old).shape}")
                arrs.append(a)
            new_state[name] = arrs
        self._state = new_state
        self._state_on_device = False
        self._state_to_device()
        self._cur_step = int(data["__cur_step__"])
        self._steps_done = int(data["__steps_done__"])

    # ------------------------------------------------------------------
    # stats (yk_stats)
    # ------------------------------------------------------------------

    def hbm_model_bytes_pp(self) -> Tuple[float, float]:
        """(read, write) HBM bytes per point per step of the CONFIGURED
        execution path (mode/wf_steps/blocks resolved from settings) —
        THE single resolution used by get_stats and the roofline."""
        if self._program is None:
            return (0.0, 0.0)
        if self._opts.mode in ("pallas", "shard_pallas"):
            blk = {d: self._opts.block_sizes[d]
                   for d in self._ana.domain_dims[:-1]
                   if self._opts.block_sizes[d] > 0} or None
            K = max(1, self._opts.wf_steps)
            built = self._built_pallas_tiling()
            if built is not None:
                return self._program.hbm_bytes_per_point(
                    fuse_steps=built["fuse_steps"],
                    block=built["block"],
                    skew=built.get("skew_dims", built["skew"]))
            from yask_tpu.ops.pallas_stencil import skew_engaged_dims
            skw = []
            if self._opts.skew_wavefront:
                # distributed skew engages only where the stream dim
                # is unsharded (the carry cannot cross shards)
                lead = self._ana.domain_dims[:-1]
                unsh = None
                if self._opts.mode == "shard_pallas":
                    unsh = [d for d in lead
                            if self._opts.num_ranks[d] <= 1]
                skw = skew_engaged_dims(self._program, K,
                                        unsharded=unsh)
            return self._program.hbm_bytes_per_point(
                fuse_steps=K, block=blk, skew=skw)
        return self._program.hbm_bytes_per_point()

    def _built_pallas_tiling(self):
        """The tiling the built kernel ACTUALLY chose for the current
        configuration (skew/pipelining can auto-fall-back during
        planning — ADVICE r3), or None before the first build / on
        non-pallas modes.  Keys on the exact build key the run path
        derives, or an auto-tune walk's other variants could shadow
        it."""
        if self._program is None or self._opts.mode not in (
                "pallas", "shard_pallas"):
            return None
        K = max(1, self._opts.wf_steps)
        # single blk/variant derivation: _pallas_build_key (the shard
        # run path uses the identical formula)
        _key, blk_, _skw = self._pallas_build_key(K)
        probe = (self._opts.mode,) + _key[1:]
        t = self._pallas_tiling.get(probe)
        if t is None:
            # run paths clamp K to the run span (K = min(wf_steps, n)):
            # a short run records under a smaller K — report the
            # nearest built variant rather than predicting
            cands = [k for k in self._pallas_tiling
                     if k[0] == probe[0] and k[2:] == probe[2:]
                     and k[1] <= K]
            if cands:
                t = self._pallas_tiling[max(cands, key=lambda k: k[1])]
        return t

    def compiled_plans(self) -> List[Dict]:
        """The plan of every Pallas chunk this context holds, one row a
        chunk in the order they were built: the scalars of its tiling
        record (``chunk.tiling``: what the planner ACTUALLY chose, after
        every fall-back) and what its compile cost.  ``hoisted`` names
        the scratch vars the kernel reads as arrays filled once
        (``analysis._find_hoisted``: step-invariant, and worth an
        array; ``[]`` where none), ``hoist_kept`` the step-invariant
        ones it still evaluates in-tile, with why (``cheap``, ``shape``,
        ``declined``).  ``reach`` is the
        margin one fused step consumes in each lead dim
        (``fused_step_radius``: what pads, halos and slabs are sized
        by), ``stage_consumed`` how much of it each stage has eaten
        once evaluated (``analysis.stage_consumed``: the regions the
        kernel computes).  ``margin_overhead`` is points computed
        beyond the useful ones per useful point,
        ``fetch_overhead`` input-tile points fetched beyond the block's
        own per block point, counted on what the input DMAs move:
        ``fetch_windows`` says by ``"var/slot"`` the rows each copies of
        its slab in each lead dim (``[lo, hi)`` in tile coordinates: the
        window the stage chain reads of that slot), ``fetch_skipped``
        the slots no DMA is started for (no stage reads them), and
        ``fetch_bytes_per_step`` the bytes the input DMAs of one launch
        move on one device, over the steps it fuses, and
        ``write_bytes_per_step`` the bytes its output DMAs move (a shard
        program's: its core and shells together);
        ``scratch_overhead`` points of scratch
        vars evaluated beyond the useful ones per useful point of those
        vars (a scratch var read with a halo is evaluated over its
        stage's region grown by that halo; 0.0 without scratch vars),
        ``edge_overhead`` points of the grid's blocks that lie past the
        domain's edge in the lead dims (evaluated, then masked to zero)
        per point of the domain, ``overshoot`` the rows of each lead
        dim among them (grid × block less the extent: a block need not
        divide it) and ``overshoot_pad`` the rows of right pad beyond
        the tile's margin they lie in (prepare pads the arrays for the
        rows the last tile walks past the edge), ``lane_fill`` the
        domain's minor extent
        over the minor extent of the widest DMA'd tile,
        ``scoped_need_bytes`` the capability table's model of what
        Mosaic holds for the kernel (``live_factor`` times
        ``tile_bytes``), ``vinstr_est`` the estimated vector
        instructions ``max_tile_vinstr`` was held against (the
        operations the evaluation memo emits for each equation times
        the registers of the region it is evaluated on),
        ``dag_ops_per_point`` the sum that estimate multiplies (a point
        and step, an operation that several trees hold counted once: a
        part's equations share a memo) and ``ops_per_point`` every
        equation's whole tree (held to nothing), ``growth_ended`` the
        reading that ended the default plan's growth (``cap``: the next
        doubling's estimate is over ``max_tile_vinstr``; ``budget``:
        its declared tiles are over the budget; ``room``: the build
        shrank the planner's block under the class's modelled scoped
        need; ``extent``: nothing larger covers the span in fewer
        tiles; None for an explicit block),
        ``eval`` the evaluator the chunk got
        (``"strip"``: tiles stay in VMEM refs and a stage is walked in
        strips; ``"tile"``: whole-tile values), ``strip`` the strip's
        lead rows and sublane rows, ``strips`` the strips walked a grid
        step over all stages and sub-steps, ``strip_vregs`` the
        registers of a strip's value.  A shard program's row is
        its per-shard chunk's, and its ``overlap`` says for each sharded
        mesh axis whether the core/shell split of the exchange was taken
        there (``{"taken": True, "core": [lo, hi)}``) or why not
        (``shard_step.overlap_axes``; ``None`` in any other row), its
        ``loop`` how the K-group loop of the variant compiled last runs
        (``loop_groups`` groups a scan iteration, so that the carry
        copies nothing (``shard_step.carry_period``), ``loop_iters``
        iterations, ``peeled_before`` / ``peeled_after`` groups outside
        the scan, ``reused`` outputs a group writes onto the ring slot
        it evicts: the launch span's attrs, ``shard_step._launch_attrs``;
        ``None`` in any other row);
        ``cache_hit`` is None where nothing was
        compiled ahead (Pallas interpret) or the compile was the shard
        program's.  No row for a mode that builds no Pallas chunk."""
        keys = ("kernel", "stages", "hoisted", "hoist_kept", "reach",
                "stage_consumed", "block",
                "grid", "tile_bytes",
                "result_bytes", "budget", "live_factor",
                "scoped_need_bytes", "vinstr_est", "growth_ended",
                "ops_per_point", "dag_ops_per_point", "eval", "strip",
                "strips", "strip_vregs", "margin_overhead",
                "fetch_overhead", "fetch_windows", "fetch_skipped",
                "fetch_bytes_per_step", "write_bytes_per_step",
                "scratch_overhead", "edge_overhead", "overshoot",
                "overshoot_pad", "lane_fill", "pipeline_dmas",
                "pipeline_out",
                "compile_secs", "cache_hit")
        return [{"k": til["fuse_steps"], **{k: til[k] for k in keys},
                 "overlap": til.get("overlap"), "loop": til.get("loop")}
                for til in self._pallas_tiling.values()]

    def call_log(self) -> List[Dict]:
        """The active run's call record, oldest first: one plain dict a
        leaf ``run_solution`` call, the newest
        ``run_state.CALL_LOG_LEN``.  A row: ``t0`` (``perf_counter`` at
        the call's start), ``secs``, ``mode``, ``first``, ``n``;
        ``launches``, a ``(k, seconds inside the enqueue)`` pair for
        each ``yt.run.launch``, and ``onto``, beside it, the outputs
        each wrote onto a slot an earlier launch gave up;
        ``wait_secs`` of ``yt.run.wait``;
        ``compiles``, the ``yt.compile.chunk`` spans opened inside the
        call; what the host did meanwhile (``gc_secs``, ``gc_runs``,
        ``cpu_secs``, ``nivcsw``, ``nvcsw``, ``majflt``); device 0's
        ``bytes_in_use`` at the call's end where the backend says; and
        the slow-call rule's verdict (``run_state.judge_call``):
        ``median``, ``slow`` and, slow, ``worst_launch``,
        ``worst_enqueue_secs``, ``held_by``.  Always on; a swapped
        ``RunState`` answers with its own calls."""
        return [dict(row) for row in self._run.calls]

    def compiled_texts(self) -> List[str]:
        """Optimised HLO text of every executable this context holds.
        Its instruction names are a device trace's event names, and an
        instruction's ``op_name`` metadata carries the
        ``jax.named_scope``s (``yt_exchange_pack`` ...) that the trace
        itself does not print: a reader joins the two to put device
        time down to a scope.  Functions that were not compiled ahead
        (Pallas interpret on a CPU) have no text and are left out."""
        return [fn.as_text() for fn in self._jit_cache.values()
                if hasattr(fn, "as_text")]

    def compiled_memory(self) -> List[Dict]:
        """What the compiler's own analysis says of every executable this
        context holds (``memory_analysis()``, per device): ``temp_bytes``
        (the program's temporaries, among them a shard program's padded
        per-shard copies; ``peak_bytes_in_use`` counts none of them),
        ``argument_bytes``, ``output_bytes``, ``alias_bytes`` (arguments
        donated to outputs) and ``generated_code_bytes``, under the
        ``kind`` its cache key starts with (``shard_pallas``,
        ``compiled`` ...).  ``temp_bytes`` is the compiler's count, not
        a measured residency: for the shard programs on a v5e it reads
        more than the chip has (``PERF.md`` §7).  One row an executable,
        in the order of :meth:`compiled_texts`; functions that were not
        compiled ahead, and backends that give no analysis, have no
        row."""
        rows = []
        for key, fn in self._jit_cache.items():
            if not hasattr(fn, "memory_analysis"):
                continue
            try:
                m = fn.memory_analysis()
            except (RuntimeError, NotImplementedError):
                continue
            if m is None:
                continue
            rows.append({
                "kind": str(key[0]),
                "temp_bytes": int(m.temp_size_in_bytes),
                "argument_bytes": int(m.argument_size_in_bytes),
                "output_bytes": int(m.output_size_in_bytes),
                "alias_bytes": int(m.alias_size_in_bytes),
                "generated_code_bytes":
                    int(m.generated_code_size_in_bytes)})
        return rows

    def get_stats(self) -> yk_stats:
        c = self._ana.counters
        npts = self._opts.global_domain_sizes.product()
        rb_pp, wb_pp = self.hbm_model_bytes_pp()
        st = yk_stats(
            npts=npts, nsteps=self._steps_done,
            nreads_pp=c.num_reads, nwrites_pp=c.num_writes,
            nfpops_pp=c.num_ops,
            elapsed=self._run_timer.get_elapsed_secs(),
            compile_secs=self._compile_secs,
            read_bytes_pp=rb_pp, write_bytes_pp=wb_pp,
            # aggregate peak: throughput is global (all chips), so the
            # roofline denominator must scale with the mesh size
            hbm_peak=(self._env.get_hbm_peak_bytes_per_sec()
                      * max(self._env.get_num_ranks(), 1)),
            tiling=self._built_pallas_tiling())
        return st

    def clear_stats(self) -> None:
        self._run_timer.clear()
        self._steps_done = 0

    # ------------------------------------------------------------------
    # CLI parity
    # ------------------------------------------------------------------

    # ------------------------------------------------------------------
    # full accessor parity (yk_solution_api.hpp) — "grid" names are the
    # reference's v2-era aliases for vars; vector forms return values in
    # domain-dim order; thread/NUMA/offload knobs answer truthfully for
    # a TPU (XLA manages cores; the chip IS the offload device).
    # ------------------------------------------------------------------

    get_grid = get_var
    get_grids = get_vars
    fuse_grids = fuse_vars
    new_fixed_size_grid = new_fixed_size_var

    def get_num_grids(self) -> int:
        return self.get_num_vars()

    def get_num_domain_dims(self) -> int:
        return len(self.get_domain_dim_names())

    def get_first_rank_domain_index(self, dim: str) -> int:
        return 0    # host APIs present the GLOBAL problem (SPMD inside)

    def get_last_rank_domain_index(self, dim: str) -> int:
        return self.get_overall_domain_size(dim) - 1

    def _dvec(self, fn):
        return [fn(d) for d in self.get_domain_dim_names()]

    def get_first_rank_domain_index_vec(self):
        return self._dvec(self.get_first_rank_domain_index)

    def get_last_rank_domain_index_vec(self):
        return self._dvec(self.get_last_rank_domain_index)

    def get_overall_domain_size_vec(self):
        return self._dvec(self.get_overall_domain_size)

    def get_rank_domain_size_vec(self):
        return self._dvec(self.get_rank_domain_size)

    def set_rank_domain_size_vec(self, sizes) -> None:
        for d, s in zip(self.get_domain_dim_names(), sizes):
            self.set_rank_domain_size(d, s)

    def get_block_size_vec(self):
        return self._dvec(self.get_block_size)

    def set_block_size_vec(self, sizes) -> None:
        for d, s in zip(self.get_domain_dim_names(), sizes):
            self.set_block_size(d, s)

    def get_num_ranks_vec(self):
        return self._dvec(self.get_num_ranks)

    def set_num_ranks_vec(self, ns) -> None:
        for d, n in zip(self.get_domain_dim_names(), ns):
            self.set_num_ranks(d, n)

    def get_rank_index(self, dim: str) -> int:
        return 0    # single-process SPMD: shards are traced, not ranked

    def get_rank_index_vec(self):
        return self._dvec(self.get_rank_index)

    def set_rank_index(self, dim: str, idx: int) -> None:
        if idx != 0:
            raise YaskException(
                "explicit rank placement is not applicable: shards are "
                "laid out by the mesh, not per-process (reference "
                "set_rank_index is for manual MPI layouts)")

    def set_rank_index_vec(self, idxs) -> None:
        for d, i in zip(self.get_domain_dim_names(), idxs):
            self.set_rank_index(d, i)

    def get_min_pad_size(self, dim: str) -> int:
        return self._opts.min_pad_sizes[dim]

    def set_min_pad_size(self, dim: str, size: int) -> None:
        self._opts.min_pad_sizes[dim] = max(
            self._opts.min_pad_sizes[dim], int(size))

    def get_step_wrap(self) -> bool:
        return getattr(self, "_step_wrap", False)

    def set_step_wrap(self, wrap: bool) -> None:
        """``yk_solution::set_step_wrap``: with wrapping on, var element
        APIs accept ANY step index and map it onto the ring modulo the
        allocation (consumed by ``yk_var._slot_for_step``)."""
        self._step_wrap = bool(wrap)

    def get_num_outer_threads(self) -> int:
        return 1    # XLA owns core-level parallelism

    def get_num_inner_threads(self) -> int:
        return 1

    def is_offloaded(self) -> bool:
        return self._env.get_platform() == "tpu"

    def get_default_numa_preferred(self) -> int:
        return self._opts.numa_pref

    def set_default_numa_preferred(self, node: int) -> bool:
        self._opts.numa_pref = int(node)
        return True

    def get_elapsed_run_secs(self) -> float:
        return self._run_timer.get_elapsed_secs()

    def get_command_line_values(self) -> str:
        """Echo the effective option values (reference
        ``get_command_line_values``)."""
        o = self._opts
        dd = self.get_domain_dim_names()
        parts = [f"-g_{d} {o.global_domain_sizes[d]}" for d in dd]
        parts += [f"-b_{d} {o.block_sizes[d]}" for d in dd]
        parts += [f"-nr_{d} {o.num_ranks[d]}" for d in dd]
        parts += [f"-wf_steps {o.wf_steps}", f"-mode {o.mode}",
                  f"-vmem_mb {o.vmem_budget_mb}"]
        return " ".join(parts)

    def exchange_halos(self) -> None:
        """Force-refresh ghost copies (reference ``exchange_halos``,
        ``soln_apis.cpp``).  Global-array modes have no persistent
        ghosts (every run re-derives them); shard-resident state is
        materialized so the next run re-places and re-exchanges from
        the authoritative interiors."""
        self._check_prepared()
        self._materialize_state()
        for v in self.get_vars():
            v._dirty = False

    def alloc_storage(self) -> None:
        """Allocate any released var rings (bulk alloc happens in
        prepare_solution; reference splits prepare/alloc)."""
        self._check_prepared()
        for v in self.get_vars():
            v.alloc_storage()

    def end_solution(self) -> None:
        """Release run resources (reference ``end_solution``): drops
        var storage and compiled-program caches; re-prepare to run
        again."""
        self._jit_cache.clear()
        self._launch_attrs.clear()
        self._shard_rest.clear()
        self._pallas_tiling.clear()
        self._comm_plans.clear()
        self._state = None
        self._run.padded = self._run.padded_geom = None
        self._resident = None
        self._program = None
        self._ended = True

    def apply_command_line_options(self, args) -> List[str]:
        if isinstance(args, str):
            args = args.split()
        p = CommandLineParser()
        self._opts.add_options(p)
        return p.parse_args(list(args))

    def get_command_line_help(self) -> str:
        p = CommandLineParser()
        self._opts.add_options(p)
        return p.print_help()

    def __repr__(self):
        return (f"<StencilContext '{self.get_name()}' mode={self._mode} "
                f"prepared={self.is_prepared()}>")
