"""yask_tpu.backend — per-backend capability tables.

The single place where a target's legality and layout facts live
(tile shapes, DMA alignment, banned in-kernel ops, VMEM limits).
Everything that generates, plans, or checks device code reads these
facts through :func:`yask_tpu.backend.capability.get_capability` —
never from module-local constants — so the static checker and the
runtime can never drift apart.  See ``docs/checking.md`` ("Backend
capability table") for the schema and the backend-extension recipe.
"""

from yask_tpu.backend.capability import (  # noqa: F401
    SCHEMA,
    TPU_KIND_ENTRIES,
    BackendCapability,
    backend_names,
    capability_for_platform,
    get_capability,
    register_capability,
)
