"""Runtime settings: every knob the kernel accepts.

Counterpart of ``KernelSettings`` (reference
``src/kernel/lib/settings.hpp:200-327``, option wiring in ``settings.cpp``):
domain geometry, tiling sizes, decomposition grid, overlap/exchange toggles,
and auto-tune controls — re-expressed for TPU execution:

* block sizes become Pallas/XLA tile hints (the auto-tuner's search space);
* the rank grid becomes the device-mesh shape;
* ``overlap_comms``/``use_shm``/``use_device_mpi`` collapse into the
  execution-mode choice (XLA async collectives already overlap; there is no
  host/device copy distinction on TPU) — they are accepted and recorded so
  reference command lines keep working.
"""

from __future__ import annotations

from typing import List, Optional

from yask_tpu.utils.exceptions import YaskException
from yask_tpu.utils.idx_tuple import IdxTuple
from yask_tpu.utils.cli import CommandLineParser


#: Execution modes for run_solution.
MODES = ("auto",       # single device → "jit"; >1 rank requested → "sharded"
         "jit",        # single-device jitted jnp program
         "pallas",     # hand-tiled Pallas kernels w/ K-step temporal fusion
         "sharded",    # global arrays + NamedSharding (XLA inserts comms)
         "shard_map",  # explicit per-shard program + ppermute halo exchange
         "shard_pallas",  # shard_map outer + fused Pallas inner (the
         #                  multi-chip scaling path: exchange every K steps)
         "ref",        # eager numpy oracle (the reference's run_ref)
         )


class KernelSettings:
    """All runtime knobs for one solution instance."""

    def __init__(self, domain_dims: List[str]):
        self.domain_dims = list(domain_dims)
        z = {d: 0 for d in domain_dims}
        # Geometry (reference -g / -d / -b … options).
        self.global_domain_sizes = IdxTuple(z)   # -g* (0 = derive from rank)
        self.rank_domain_sizes = IdxTuple(z)     # -d* (0 = derive from global)
        self.block_sizes = IdxTuple(z)           # -b* tile hints (0 = auto)
        self.min_pad_sizes = IdxTuple(z)         # -mp* extra pad per dim
        self.num_ranks = IdxTuple(z)             # -nr* mesh grid (0 = auto)
        # Temporal tiling (reference wave-front options, context.hpp:331).
        self.wf_steps = 0          # steps fused per compiled chunk (0 = auto)
        # Behavior toggles.
        self.mode = "auto"
        self.overlap_comms = True
        self.use_shm = True            # accepted for parity; no-op on TPU
        self.use_device_mpi = True     # accepted for parity; no-op on TPU
        self.bundle_allocs = True
        self.force_scalar = False      # run the numpy oracle path
        # Auto-tuner (reference auto_tuner.hpp options).
        self.do_auto_tune = False
        self.auto_tune_each_stage = False
        self.auto_tune_trial_secs = 0.5
        # Largest wf_steps the joint walk may try. When auto-tune is on,
        # pallas-mode pads are planned up to radius × this at prepare
        # time so the walk can *grow* K, not only shrink it.
        self.tune_max_wf_steps = 16
        # Streaming skewed-wavefront tiling on the pallas path (zero
        # redundant compute in the stream dim, the innermost grid dim;
        # the TPU-native answer to the reference's temporal blocking,
        # setup.cpp:863).  True = auto (on when the geometry is
        # eligible and the margin model says it pays), False = the
        # uniform shrink in every dim.
        self.skew_wavefront = True
        # Push-memory tile-graph fusion on the pallas path (the
        # Halide-to-push-memory dataflow idea, arxiv 2105.12858): an
        # eligible intermediate var's VMEM output tile is consumed by
        # its reader stages inside the SAME grid step and the var
        # leaves both HBM paths (no input DMA, no write-back DMA) —
        # its HBM ring goes stale by design.  "auto" = engage for
        # pipeline-fused contexts only (plain solutions keep every var
        # observable), "on" = auto-engage eligible vars on any pallas
        # context, "force" = raise when nothing is eligible,
        # "off" = never.
        self.push_memory = "auto"
        # Overlapped halo exchange on the shard_pallas path: split each
        # fused K-group into a core chunk (interior shrunk by radius×K
        # per sharded dim, evaluated against PRE-exchange state so XLA
        # runs the previous group's collectives concurrently) + shell
        # slabs on the post-exchange state — the fused-chunk analog of
        # the reference's interior/exterior MPI overlap
        # (context.cpp:377-478).  "auto" = on when every sharded dim's
        # rank domain admits an aligned core (≥ 2·radius·K),
        # "on" = force (raises when infeasible), "off" = serial.
        self.overlap_exchange = "auto"
        # Communication-pattern scheduling for the explicit shard modes
        # (shard_map / shard_pallas), decided by the CommPlan
        # (yask_tpu/parallel/comm_plan.py) off its ICI/DCN link model.
        # comm_order: "" = auto (DCN axes exchange
        # first so their longer flight hides under more compute, then
        # ICI by descending modeled flight time); a comma list like
        # "y,x" forces the order (unknown axes are a CommPlan error —
        # run paths raise, the checker reports COMM-ORDER).
        self.comm_order = ""
        # Message coalescing: pack every buffer's ghost slab for one
        # (mesh axis, direction) into a single concatenated ppermute
        # payload instead of one collective per buffer per face.  Pure
        # data movement — bit-identical to the serial schedule — but
        # fewer collective rounds per exchange.  "auto" = on whenever
        # some axis carries more than one slab, "on" = force,
        # "off" = serial per-buffer collectives.  The joint auto-tuner
        # A/Bs on|off at its winning point when left on "auto".
        self.coalesce = "auto"
        # Let the joint auto-tuner sweep the Pallas VMEM budget
        # (64/96/120 MiB ladder) as an outer tuning axis when
        # vmem_budget_mb is 0 (auto).  Larger budgets admit wider
        # blocks; Mosaic VMEM OOMs are caught as infeasible candidates
        # (never fatal), so the ladder is safe to walk on hardware.
        self.tune_vmem_ladder = True
        # Pallas VMEM budget in MiB (0 = auto: ~16 MiB/core on real TPU
        # per the hardware guide, a loose 100 MiB under CPU interpret
        # where VMEM is emulated). The reference exposes every size knob
        # via CLI (settings.hpp:200-327); this is the TPU-side analog.
        self.vmem_budget_mb = 0
        # Cap on the estimated Mosaic vector-instruction count per fused
        # Pallas kernel (the build's ``vinstr_est``: the operations the
        # evaluation memo emits, times the registers of the regions):
        # the tile planner refuses to grow blocks past it.  Guards
        # against long Mosaic compiles (ssg-K2/swe2d took >15 min
        # mid-r3).  About a minute of Mosaic on the chip's host, read
        # off one-equation kernels, whose estimate is the same by the
        # trees and by the DAG (``plan_blocks`` has the readings); the
        # 300 000 it was until PR 42 dated from an estimate 2-5 times
        # as large.  Every plan the benchmark runs is under it (the
        # flagship's 97 600 the largest).  0 disables the cap.
        self.max_tile_vinstr = 100_000
        # Whether checker.preflight(ctx) checks or returns True at
        # once: the gate a driver calls before spending chip time on
        # a configuration the checker can prove infeasible (the
        # VMEM-OOM class).  Findings print; the launch proceeds (a
        # checker false-positive must not cost a chip run).
        self.preflight = True
        # Ensemble batching (yask_tpu/runtime/ensemble.py): run N
        # independent instances of the solution as ONE vmapped program
        # — state rings gain a leading batch dim, so N parameter-sweep
        # members share a single compile and saturate the chip on
        # small domains.  Only the single-device modes (jit/pallas)
        # batch; sharded modes decline with a structured reason
        # (ensemble_feasible — the checker's ENSEMBLE-INFEASIBLE rule
        # reads the same definition).  1 = off.
        self.ensemble = 1
        # Server-hosted solution (yask_tpu/serve/): set by
        # StencilServer on the contexts it prepares (also -serve for
        # explicit checker runs).  Gates the checker's serve pass
        # (SERVE-BATCH-INCOMPAT / SERVE-CACHE-COLD) the same way the
        # supervision knobs gate the ckpt pass — a non-serving
        # `make check -all_stencils` stays silent.
        self.serve = False
        # Supervised runs (yask_tpu/resilience/checkpoint.py): checkpoint
        # cadence in steps (0 = off — the hot path sees three int
        # compares and nothing else), snapshot directory (empty = the
        # YT_CKPT_DIR env; cadence without any dir keeps in-memory
        # rollback snapshots only), watchdog scan cadence (nonfinite /
        # all-zero written-interior check every M steps), and a per-chunk
        # deadline in seconds.  Any nonzero knob routes run_solution
        # through the supervision loop with its mode-degradation ladder
        # (shard_pallas → shard_map → jit, pallas → jit).
        self.ckpt_every = 0
        self.ckpt_dir = ""
        self.watchdog_every = 0
        self.run_deadline_secs = 0
        # Misc.
        self.max_threads = 0           # accepted for parity; XLA manages
        self.numa_pref = -1            # accepted for parity
        self.allow_addl_pad = True

    # ------------------------------------------------------------------

    def add_options(self, parser: CommandLineParser) -> None:
        """Register every option (reference ``KernelSettings::add_options``).
        Option names follow the reference CLI (``-g``, ``-d``, ``-b``,
        ``-nr``, ``-wf_steps``…), with per-dim forms like ``-d_x``."""
        dd = self.domain_dims
        parser.add_idx_option(
            "g", "Global (overall) domain size in each dim.", self,
            "global_domain_sizes", dd)
        parser.add_idx_option(
            "d", "Per-rank domain size in each dim.", self,
            "rank_domain_sizes", dd)
        parser.add_idx_option(
            "b", "Block (tile) size hint in each dim.", self,
            "block_sizes", dd)
        parser.add_idx_option(
            "mp", "Minimum extra pad in each dim.", self,
            "min_pad_sizes", dd)
        parser.add_idx_option(
            "nr", "Number of ranks (mesh extent) in each dim.", self,
            "num_ranks", dd)
        parser.add_int_option(
            "wf_steps", "Steps fused per compiled chunk (temporal "
            "wave-front analog).", self, "wf_steps")
        parser.add_string_option(
            "mode", f"Execution mode, one of {MODES}.", self, "mode")
        parser.add_bool_option(
            "overlap_comms", "Overlap ghost exchange with interior compute.",
            self, "overlap_comms")
        parser.add_bool_option(
            "use_shm", "Accepted for reference parity (no-op on TPU).",
            self, "use_shm")
        parser.add_bool_option(
            "use_device_mpi", "Accepted for reference parity (no-op on TPU).",
            self, "use_device_mpi")
        parser.add_bool_option(
            "force_scalar", "Use the eager numpy oracle instead of the "
            "compiled path.", self, "force_scalar")
        parser.add_bool_option(
            "auto_tune", "Auto-tune tile sizes during the run.", self,
            "do_auto_tune")
        parser.add_int_option(
            "tune_max_wf_steps", "Largest wf_steps the auto-tuner may "
            "try (pallas pads are pre-planned to cover it).", self,
            "tune_max_wf_steps")
        parser.add_bool_option(
            "skew", "Streaming skewed-wavefront tiling on the pallas "
            "path (auto-on when eligible; the temporal-blocking "
            "analog).", self, "skew_wavefront")
        parser.add_string_option(
            "push", "Push-memory tile-graph fusion on the pallas path: "
            "auto|on|force|off (eligible intermediate tiles are "
            "consumed in-VMEM and skip HBM entirely; their rings go "
            "stale — auto engages only for pipeline-fused contexts).",
            self, "push_memory")
        parser.add_string_option(
            "overlap_x", "shard_pallas overlapped halo exchange: "
            "auto|on|off (core/shell split of the fused K-group; the "
            "interior/exterior MPI-overlap analog).", self,
            "overlap_exchange")
        parser.add_string_option(
            "comm_order", "Mesh-axis ghost-exchange order for the shard "
            "modes, e.g. 'y,x' (empty = auto: DCN axes first, then ICI "
            "by modeled flight time — see the CommPlan).", self,
            "comm_order")
        parser.add_string_option(
            "coalesce", "Ghost-exchange message coalescing: auto|on|off "
            "(one concatenated ppermute per mesh axis and direction "
            "instead of one collective per buffer per face).", self,
            "coalesce")
        parser.add_int_option(
            "vmem_mb", "Pallas VMEM budget in MiB (0 = derive from the "
            "device).", self, "vmem_budget_mb")
        parser.add_bool_option(
            "tune_vmem_ladder", "Let the auto-tuner sweep the VMEM "
            "budget (64/96/120 MiB) as an outer axis when -vmem_mb is "
            "0.", self, "tune_vmem_ladder")
        parser.add_int_option(
            "max_vinstr", "Cap on estimated Mosaic vector instructions "
            "per fused kernel, shared operations counted once "
            "(tile-planner growth guard; 0 = off).",
            self, "max_tile_vinstr")
        parser.add_bool_option(
            "preflight", "Run the static checker (yask_tpu.checker) "
            "before launching in the driver tools; findings print, "
            "the launch proceeds (-no-preflight to skip).",
            self, "preflight")
        parser.add_int_option(
            "ensemble", "Batch N independent solution instances as one "
            "vmapped program (jit/pallas single-device modes; sharded "
            "modes decline).  1 = off.", self, "ensemble")
        parser.add_bool_option(
            "serve", "Mark this solution as server-hosted "
            "(yask_tpu/serve/): enables the checker's serve pass "
            "(batch-compatibility + compile-cache warmth).  "
            "StencilServer sets it on the contexts it prepares.",
            self, "serve")
        parser.add_int_option(
            "ckpt_every", "Checkpoint the run every N steps (portable "
            "interior-coordinate snapshots; 0 = off).", self,
            "ckpt_every")
        parser.add_string_option(
            "ckpt_dir", "Directory for on-disk checkpoints (empty = "
            "YT_CKPT_DIR env; cadence without a dir keeps in-memory "
            "rollback snapshots only).", self, "ckpt_dir")
        parser.add_int_option(
            "watchdog_every", "Scan written state for nonfinite / "
            "all-zero interiors every M steps (0 = off).", self,
            "watchdog_every")
        parser.add_int_option(
            "run_deadline", "Per-chunk deadline in seconds for "
            "supervised runs (0 = off).", self, "run_deadline_secs")
        parser.add_int_option(
            "max_threads", "Accepted for reference parity.", self,
            "max_threads")

    # ------------------------------------------------------------------

    def adjust_settings(self, num_devices: int = 1) -> None:
        """Derive unset values (reference ``adjust_settings``,
        ``settings.cpp``): rank grid from device count, global↔rank domain
        sizes, default block sizes."""
        if self.mode not in MODES:
            raise YaskException(f"unknown mode '{self.mode}'; one of {MODES}")

        # Rank grid: like the reference, one rank unless the user asks for
        # decomposition (mpirun -np there; -nr/-mode here). A total of -1 in
        # the first dim means "auto": factorize all devices over the grid
        # keeping the minor-most dim whole for TPU lanes.
        nr = self.num_ranks
        if any(v < 0 for v in nr.get_vals()):
            from yask_tpu.parallel.decomp import factorize_rank_grid
            auto = factorize_rank_grid(max(num_devices, 1), self.domain_dims)
            for d in self.domain_dims:
                nr[d] = auto[d]
        elif all(v == 0 for v in nr.get_vals()) and num_devices > 1 \
                and self.mode in ("sharded", "shard_map", "shard_pallas"):
            # Distribution requested by mode but no grid given: split the
            # outer-most dim so halo slabs stay lane-contiguous.
            for d in self.domain_dims:
                nr[d] = 1
            nr[self.domain_dims[0]] = num_devices
        else:
            for d in self.domain_dims:
                if nr[d] == 0:
                    nr[d] = 1
        if nr.product() > max(num_devices, 1):
            raise YaskException(
                f"rank grid {nr} needs {nr.product()} devices, "
                f"only {num_devices} available")

        # Domain sizes: global ⇄ rank.
        for d in self.domain_dims:
            g, r, n = self.global_domain_sizes[d], self.rank_domain_sizes[d], nr[d]
            if g == 0 and r == 0:
                raise YaskException(f"domain size for dim '{d}' not set")
            if g == 0:
                self.global_domain_sizes[d] = r * n
            elif r == 0:
                if g % n != 0:
                    raise YaskException(
                        f"global size {g} in dim '{d}' not divisible by "
                        f"{n} ranks")
                self.rank_domain_sizes[d] = g // n
            elif r * n != g:
                raise YaskException(
                    f"inconsistent sizes in dim '{d}': global {g} != "
                    f"rank {r} × {n} ranks")
