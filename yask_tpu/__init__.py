"""yask_tpu — a TPU-native stencil-computation framework.

A from-scratch re-design of the capabilities of intel/yask for TPU:

* a stencil DSL **compiler** (``yask_tpu.compiler``): equations are built as an
  AST via operator overloading (the ``yc_*`` API surface of the reference,
  ``include/yask_compiler_api.hpp``), analyzed for dependencies, partitioned
  into parts/stages, and **lowered to JAX/XLA and Pallas** instead of
  intrinsic-laden C++;
* a kernel **runtime** (``yask_tpu.runtime``): the ``yk_*`` API surface
  (``include/yask_kernel_api.hpp``) — solutions, vars with halo/pad geometry,
  stats, auto-tuning — executing as compiled JAX programs;
* **distribution** (``yask_tpu.parallel``): the reference's MPI rank grid +
  halo exchange (``src/kernel/lib/setup.cpp``, ``halo.cpp``) becomes an N-D
  ``jax.sharding.Mesh`` with ``shard_map`` + ``lax.ppermute`` ghost-cell
  exchange over ICI;
* a **stencil library** (``yask_tpu.stencils``) covering the reference's
  ``src/stencils`` solutions (iso3dfd, ssg, fsg, awp, tti, …).

Nothing in this package is a translation of the reference's C++; file:line
citations in docstrings point at the behavior being matched, not code reused.
"""

import time as _time

# the package's own import is the first of the set-up spans
# (``yt.setup.import``, recorded at the last line: the tracer is not
# imported yet at this one)
_IMPORT_T0, _IMPORT_WALL = _time.perf_counter(), _time.time()

__version__ = "0.1.0"

# Public API surface (mirrors the three reference headers:
# yask_common_api.hpp, yask_compiler_api.hpp, yask_kernel_api.hpp).
from yask_tpu.utils.exceptions import YaskException  # noqa: F401
from yask_tpu.utils.idx_tuple import IdxTuple  # noqa: F401
from yask_tpu.utils.fd_coeff import (  # noqa: F401
    get_center_fd_coefficients,
    get_forward_fd_coefficients,
    get_backward_fd_coefficients,
    get_arbitrary_fd_coefficients,
)
from yask_tpu.utils.output import yask_output_factory  # noqa: F401
from yask_tpu.utils.cli import CommandLineParser  # noqa: F401

from yask_tpu.compiler.node_api import yc_node_factory  # noqa: F401
from yask_tpu.compiler.solution import yc_factory, yc_solution  # noqa: F401
from yask_tpu.compiler.solution_base import (  # noqa: F401
    yc_solution_base,
    yc_solution_with_radius_base,
    register_solution,
    get_registered_solutions,
)

from yask_tpu.runtime.factory import yk_factory  # noqa: F401


def quick_run(stencil: str, g: int = 64, steps: int = 10, radius=None,
              mode: str = "auto", **settings):
    """One-liner demo/benchmark: build a registered stencil, seq-init its
    vars, run ``steps`` steps, and return the context (read results via
    ``ctx.get_var(...)`` / ``ctx.get_stats()``).

    >>> ctx = yask_tpu.quick_run("iso3dfd", g=128, steps=20, radius=4)
    >>> print(ctx.get_stats().format())
    """
    fac = yk_factory()
    env = fac.new_env()
    ctx = fac.new_solution(env, stencil=stencil, radius=radius)
    ctx.apply_command_line_options(f"-g {g}")
    ctx.get_settings().mode = mode
    for k, v in settings.items():
        if not hasattr(ctx.get_settings(), k):
            raise YaskException(f"unknown kernel setting '{k}'")
        setattr(ctx.get_settings(), k, v)
    ctx.prepare_solution()
    from yask_tpu.runtime.init_utils import init_solution_vars
    init_solution_vars(ctx)
    if steps > 0:
        ctx.run_solution(0, steps - 1)
    return ctx


def _record_import():
    from yask_tpu.obs.tracer import process_age, record_span
    secs = _time.perf_counter() - _IMPORT_T0
    age = process_age()
    # what ran before the program was imported: the interpreter's
    # start-up, and whatever the caller imported and did first
    record_span("setup.import", "setup", _IMPORT_WALL, secs, keep=True,
                t0=_IMPORT_T0, secs=round(secs, 6),
                since_start_s=None if age is None
                else round(max(age - secs, 0.0), 3))


_record_import()
