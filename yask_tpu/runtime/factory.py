"""Kernel factory: ``yk_factory``.

Counterpart of the reference's ``yk_factory`` (``src/kernel/lib/factory.cpp:
36-107``): ``new_env`` bootstraps the execution environment (MPI there,
device discovery here); ``new_solution`` instantiates a runnable context
from a compiled solution — where the reference links a generated
``YASK_STENCIL_SOLUTION`` class, we accept any DSL solution object or a
registered stencil name.
"""

from __future__ import annotations

from typing import Optional

from yask_tpu.obs.tracer import span
from yask_tpu.runtime.env import yk_env
from yask_tpu.runtime.context import StencilContext


class yk_factory:
    def get_version_string(self) -> str:
        from yask_tpu import __version__
        return __version__

    def new_env(self, devices=None) -> yk_env:
        # the first touch of the backend, where the caller has not
        # touched it already
        with span("setup.env", phase="setup", keep=True) as sp:
            env = yk_env(devices=devices)
            sp.set(devices=env.get_num_ranks(),
                   platform=env.get_platform())
        return env

    def new_solution(self, env: yk_env, source=None, *,
                     stencil: Optional[str] = None,
                     radius: Optional[int] = None,
                     dtype=None) -> StencilContext:
        """Build a runnable solution.

        ``source`` may be a ``yc_solution``, ``yc_solution_base``, or
        ``CompiledSolution``; alternatively pass ``stencil=`` (+ optional
        ``radius=``) to instantiate from the registered stencil library the
        way the reference's harness selects ``-stencil`` at build time.
        """
        with span("setup.solution", phase="setup", keep=True,
                  stencil=stencil or "", radius=radius) as sp:
            if source is None:
                if stencil is None:
                    raise YaskExceptionHelper()
                from yask_tpu.compiler.solution_base import \
                    create_solution
                source = create_solution(stencil, radius=radius)
            ctx = StencilContext(env, source, dtype=dtype)
            if not stencil:
                sp.set(stencil=ctx.get_name())
        return ctx


def YaskExceptionHelper():
    from yask_tpu.utils.exceptions import YaskException
    return YaskException("new_solution needs a solution object or stencil=")
