"""Stencil library: every solution family from the reference's
``src/stencils`` re-expressed in the Python DSL (same names, same equations,
same radius parameterization) so users of the reference find each solution
here (SURVEY §2.6 inventory).

Importing this package registers all solutions (the analog of the
``REGISTER_SOLUTION`` static objects linking into the compiler binary).
"""

from yask_tpu.stencils import simple  # noqa: F401
from yask_tpu.stencils import iso3dfd  # noqa: F401
from yask_tpu.stencils import elastic  # noqa: F401
from yask_tpu.stencils import awp  # noqa: F401
from yask_tpu.stencils import tti  # noqa: F401
from yask_tpu.stencils import physics2d  # noqa: F401
from yask_tpu.stencils import filters  # noqa: F401
from yask_tpu.stencils import rtm  # noqa: F401
from yask_tpu.stencils import himeno  # noqa: F401
from yask_tpu.stencils import lbm  # noqa: F401
from yask_tpu.stencils import test_stencils  # noqa: F401
