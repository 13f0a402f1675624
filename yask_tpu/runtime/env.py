"""Kernel environment: device discovery and the mesh bootstrap.

Counterpart of ``yk_env`` / ``KernelEnv`` (reference
``include/yask_kernel_api.hpp:167-293``, ``src/kernel/lib/settings.hpp:47-80``,
init in ``setup.cpp:51-90``): where the reference calls
``MPI_Init_thread`` and splits a shared-memory communicator, the TPU runtime
discovers JAX devices and exposes them as the "ranks" a solution's domain is
decomposed over. Collectives over ranks (barriers, reductions, equality
assertions) are trivial here because the controller is a single process
driving all devices (JAX SPMD); the API surface is kept for parity.
"""

from __future__ import annotations

import os
import sys
from typing import List, Optional

from yask_tpu.utils.exceptions import YaskException


#: per-chip peak HBM bandwidth, bytes/s, keyed by the ``device_kind``
#: JAX reports (Google Cloud TPU documentation, per-generation "System
#: architecture" pages: v5e 819 GB/s, v5p 2765 GB/s, v6e 1640 GB/s,
#: v4 1228 GB/s).  A kind that is not here is an error, not a default.
HBM_PEAK_BYTES_PER_SEC = {
    "TPU v5 lite": 819e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v4": 1228e9,
}

#: JAX's persistent compilation cache when nothing outside places it:
#: a FIXED path (the path is part of the cache key — a directory that
#: moves never hits), git-ignored.
DEFAULT_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its directory — the
    ONE place the program does so.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set the cache is placed from outside and nothing is set in code
    (returns None); otherwise it lives at :data:`DEFAULT_JAX_CACHE_DIR`
    (returned).  Never derived from tempfile, a pid or the time."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE_DIR)
    return DEFAULT_JAX_CACHE_DIR


class yk_env:
    """Execution environment: devices, debug output, trace gating."""

    def __init__(self, devices: Optional[List] = None):
        import jax
        place_compile_cache()
        self._devices = (list(devices) if devices is not None
                         else jax.devices())
        self._trace = False
        self._debug = sys.stdout
        self._msg_rank = 0

    # ---- device/"rank" info ---------------------------------------------

    def get_num_ranks(self) -> int:
        """Number of devices available for domain decomposition (the
        reference's MPI world size)."""
        return len(self._devices)

    def get_rank_index(self) -> int:
        """Always 0: one controller process drives all devices (JAX SPMD);
        per-device work is expressed via sharding, not per-process code."""
        return 0

    def get_devices(self) -> List:
        return list(self._devices)

    def get_platform(self) -> str:
        """The platform JAX reports for the first device ("tpu",
        "cpu", ...); "none" with an empty device list."""
        if not self._devices:
            return "none"
        return self._devices[0].platform

    def get_device_kind(self) -> str:
        """``device_kind`` of the first device as JAX reports it (the
        key of the peak and capability tables); "" with no devices."""
        if not self._devices:
            return ""
        return getattr(self._devices[0], "device_kind", "")

    def get_hbm_peak_bytes_per_sec(self) -> float:
        """Per-chip HBM peak bandwidth for the roofline readout in
        ``yk_stats`` (:data:`HBM_PEAK_BYTES_PER_SEC`).  0.0 off-TPU —
        on the CPU mesh a roofline fraction is meaningless; a TPU whose
        ``device_kind`` is not in the table raises."""
        if self.get_platform() != "tpu":
            return 0.0
        kind = self.get_device_kind()
        if kind not in HBM_PEAK_BYTES_PER_SEC:
            raise YaskException(
                f"no HBM peak for device kind '{kind}'; known: "
                f"{', '.join(sorted(HBM_PEAK_BYTES_PER_SEC))}")
        return HBM_PEAK_BYTES_PER_SEC[kind]

    # ---- collectives-over-ranks (single-controller no-ops, kept for API
    # parity with yk_env barriers/reductions) ------------------------------

    def global_barrier(self) -> None:
        import jax
        # Materialize any pending async work — the observable effect a
        # barrier has in the reference harness timing.
        jax.effects_barrier()

    def sum_over_ranks(self, val: int) -> int:
        return val

    def min_over_ranks(self, val: int) -> int:
        return val

    def max_over_ranks(self, val: int) -> int:
        return val

    def assert_equality_over_ranks(self, val: int, descr: str = "") -> None:
        return None  # single controller: trivially equal

    # ---- debug & trace ---------------------------------------------------

    def set_trace_enabled(self, enable: bool) -> None:
        self._trace = bool(enable)

    def is_trace_enabled(self) -> bool:
        return self._trace

    def set_debug_output(self, out) -> None:
        self._debug = out.get_ostream() if hasattr(out, "get_ostream") else out

    def get_debug_output(self):
        return self._debug

    def trace_msg(self, msg: str) -> None:
        if self._trace:
            self._debug.write(f"YASK-TPU: {msg}\n")

    # ---- multi-host bootstrap (the MPI_Init analog across hosts) ---------

    @staticmethod
    def init_distributed(coordinator_address: str, num_processes: int,
                         process_id: int) -> None:
        """Join a multi-host JAX cluster (``jax.distributed``): after this,
        ``jax.devices()`` spans every host and meshes ride ICI within a
        slice / DCN across — the reference's multi-node MPI launch
        (``setup.cpp:51-90``) without per-rank SPMD processes."""
        import jax
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)

    # ---- profiling (SURVEY §5: VTune/XProf analog) -----------------------

    def start_profiler_trace(self, log_dir: str) -> None:
        """Begin an XProf/TensorBoard trace (the reference's VTune
        resume/pause hooks around trials, ``yask_main.cpp:33-44``)."""
        import jax
        jax.profiler.start_trace(log_dir)

    def stop_profiler_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def finalize(self) -> None:
        """Counterpart of MPI_Finalize; nothing to tear down."""
