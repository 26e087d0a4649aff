"""Fused Pallas cell-update kernel for the sweep engine's chunk body.

``ops.cell_update`` runs one chunk of arrivals through the per-cell DES
update — free-time grid, policy/model selects, Kahan mean fold — with
the cells on the kernel's lanes and the whole per-cell carry resident in
VMEM across the chunk, then folds the responses into the hist-sketch. ``ref`` holds the single source of truth for the
step physics (``step_cell``) and the ``lax.scan`` reference body the
kernel must match bit-for-bit; ``repro.core.queueing`` dispatches
between the two behind its ``use_kernel`` flag.
"""
from repro.kernels.cell_update.ops import (cell_lanes,  # noqa: F401
                                           cell_update,
                                           cell_update_costs,
                                           kernel_path_mode,
                                           resolve_kernel_mode)
from repro.kernels.cell_update.ref import (cell_update_ref,  # noqa: F401
                                           step_cell)
