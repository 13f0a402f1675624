"""Distribution: device-mesh decomposition and ghost-cell exchange.

TPU-native counterpart of the reference's MPI layer
(``src/kernel/lib/setup.cpp`` rank topology, ``halo.cpp`` exchange): the
N-D rank grid becomes a ``jax.sharding.Mesh`` whose axes are domain dims;
halo exchange becomes ``lax.ppermute`` neighbor shifts over ICI inside
``shard_map`` (or XLA-inserted collectives in ``sharded`` mode).
"""

from yask_tpu.parallel.mesh import build_mesh, make_mesh, state_shardings
from yask_tpu.parallel.comm_plan import (
    CommPlan,
    build_comm_plan,
)
from yask_tpu.parallel.decomp import (
    factorize_rank_grid,
    validate_shard_geometry,
)

__all__ = ["build_mesh", "make_mesh", "state_shardings",
           "CommPlan", "build_comm_plan",
           "factorize_rank_grid", "validate_shard_geometry"]
