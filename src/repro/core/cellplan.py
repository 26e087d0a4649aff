"""Cell-plan construction for the sweep engine.

The fused engine's summaries are stacked over three axes — seeds ``S``
(dist-stacked for ``sweep_dists``), loads ``B``, replication factors
``K`` — but every (s, b, k) grid cell is an independent simulation:
per-cell server free-times, Kahan mean state, and histogram rows never
interact. A ``CellPlan`` makes that independence explicit by flattening
the stacked axes into ONE cell axis of length ``S * B * K`` (C-order:
seed slowest, k fastest, matching ``reshape(S, B, K)``), padded up to a
multiple of ``pad_to`` so the cell axis divides a device mesh evenly.

Each cell carries its coordinates (``seed_idx`` / ``load_idx`` /
``k_idx``) plus a validity mask. Since the scenario API (PR 5), the
k-axis is really a *variant* axis: next to (seed, load, k) every cell
also carries its replication-policy and service-model CODES
(``policy_code`` / ``model_code``, see ``repro.core.scenario``), so a
mixed-policy grid is just a plan whose cells disagree on those two
columns — the chunk body branches on them per cell via selects inside
one compiled scan, and the fused Pallas cell-update kernel
(``repro.kernels.cell_update``) receives the same codes in its per-cell
parameter block, one lane per cell, selecting the policy arm inside the
kernel body with identical select ops. Pad cells alias cell 0's coordinates (including its
policy/model codes) so they simulate real, finite work (no NaN/inf
poisoning a shared buffer or a collective) but are marked invalid and
sliced away by ``unflatten`` before any summary is read — a pad cell
cannot contribute to a Kahan mean or a hist_sketch bin of a real cell
because no per-cell state is ever reduced across the cell axis.

Both execution layers consume the same plan: the single-device driver in
``repro.core.queueing`` builds an unpadded plan (``pad_to=1``) and the
sharded driver in ``repro.distributed.sweep_shard`` pads to the mesh
size. Cell RANDOMNESS is keyed by the seed coordinate alone (chunk seed
keys indexed with ``seed_idx``), never by position on the cell axis or
device placement — which is what makes sharded and unsharded execution
bit-identical for any device count.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CellPlan:
    """Flattened (seed, load, variant) sweep grid with mesh padding."""

    n_seeds: int
    n_loads: int
    n_ks: int
    n_cells: int       # S * B * K real cells
    n_padded: int      # n_cells rounded up to a multiple of pad_to
    seed_idx: Array    # (n_padded,) int32 — seed coordinate per cell
    load_idx: Array    # (n_padded,) int32 — load coordinate per cell
    k_idx: Array       # (n_padded,) int32 — variant coordinate per cell
    valid: Array       # (n_padded,) bool  — False for pad cells
    policy_code: Array  # (n_padded,) int32 — scenario.Policy per cell
    model_code: Array   # (n_padded,) int32 — scenario.ServiceModel per cell
    dist_id: Array      # (n_padded,) int32 — dist-union index per cell

    @property
    def stacked_shape(self) -> tuple[int, int, int]:
        return (self.n_seeds, self.n_loads, self.n_ks)

    def sharding_rule(self, mesh):
        """Declare this plan's placement on a ``"cells"`` mesh: returns
        the ``repro.launch.mesh.SweepShardingRules`` whose specs /
        constructors the sharded executor consumes (cell-axis trees
        shard ``P("cells")``, chunk scalars replicate). The ONE place
        plan placement is decided — callers never hand-build
        ``NamedSharding``s. Requires ``n_padded`` to be a multiple of
        the mesh size (``make_cell_plan(pad_to=mesh.devices.size)``)."""
        from repro.launch.mesh import SweepShardingRules

        rules = SweepShardingRules(mesh)
        if self.n_padded % rules.n_devices:
            raise ValueError(
                f"plan has {self.n_padded} padded cells, not a multiple "
                f"of the {rules.n_devices}-device mesh; build it with "
                f"pad_to=mesh.devices.size")
        return rules


def make_cell_plan(n_seeds: int, n_loads: int, n_ks: int, *,
                   pad_to: int = 1,
                   policies=None, models=None,
                   dist_ids=None) -> CellPlan:
    """Flatten an (S, B, K) grid into a padded cell axis.

    Cell ``c`` maps to coordinates ``(c // (B*K), (c // K) % B, c % K)``
    — C-order, so ``unflatten`` is a plain ``reshape(S, B, K)`` of the
    first ``n_cells`` entries. Pad cells (when ``S*B*K`` is not a
    multiple of ``pad_to``) copy cell 0's coordinates and are flagged
    ``valid=False``.

    ``policies`` / ``models`` / ``dist_ids`` are per-VARIANT code
    sequences of length ``n_ks`` (``repro.core.scenario`` ints); each
    cell inherits the codes of its variant slot, pad cells inherit cell
    0's. ``None`` means all cells run the paper default (code 0:
    replicate-all, i.i.d. service, dist-union slot 0).
    """
    if min(n_seeds, n_loads, n_ks, pad_to) < 1:
        raise ValueError(
            f"all plan axes must be >= 1, got {(n_seeds, n_loads, n_ks)} "
            f"pad_to={pad_to}")
    for name, codes in (("policies", policies), ("models", models),
                        ("dist_ids", dist_ids)):
        if codes is not None and len(codes) != n_ks:
            raise ValueError(f"{name} must have one code per variant "
                             f"({n_ks}), got {len(codes)}")
    n_cells = n_seeds * n_loads * n_ks
    n_padded = -(-n_cells // pad_to) * pad_to
    c = np.arange(n_padded)
    k_idx = c % n_ks
    load_idx = (c // n_ks) % n_loads
    seed_idx = c // (n_ks * n_loads)
    pad = slice(n_cells, n_padded)
    seed_idx[pad] = load_idx[pad] = k_idx[pad] = 0
    policy = np.zeros(n_ks, np.int32) if policies is None else np.asarray(
        [int(p) for p in policies], np.int32)
    model = np.zeros(n_ks, np.int32) if models is None else np.asarray(
        [int(m) for m in models], np.int32)
    did = np.zeros(n_ks, np.int32) if dist_ids is None else np.asarray(
        [int(d) for d in dist_ids], np.int32)
    return CellPlan(
        n_seeds=n_seeds, n_loads=n_loads, n_ks=n_ks,
        n_cells=n_cells, n_padded=n_padded,
        seed_idx=jnp.asarray(seed_idx, jnp.int32),
        load_idx=jnp.asarray(load_idx, jnp.int32),
        k_idx=jnp.asarray(k_idx, jnp.int32),
        valid=jnp.asarray(c < n_cells),
        policy_code=jnp.asarray(policy[k_idx], jnp.int32),
        model_code=jnp.asarray(model[k_idx], jnp.int32),
        dist_id=jnp.asarray(did[k_idx], jnp.int32))


def device_row_maps(idx, n_devices: int):
    """Per-device input-row sets + device-local remap for a global
    ``(n_padded,)`` input-row index array (the plan's ``seed_idx``, or
    the heterogeneous-grid svc-row index ``dist_id * n_seeds +
    seed_idx``).

    Returns ``(rows, local)``: ``rows[d]`` lists the global input rows
    device ``d``'s cells gather — unique, sorted, padded to the common
    width ``R = max_d |unique(d)|`` by repeating the last entry so every
    device's block has the same shape — and ``local[c]`` is the position
    of cell ``c``'s row inside its OWN device's list. For any global
    input block ``x`` (rows = seed rows), device ``d``'s local block
    ``x[rows[d]]`` then satisfies

        x[rows[d]][local[c]] == x[idx[c]]   for every cell c on d,

    i.e. remapping indices to device-local row positions gathers
    exactly the same sampled values — the chunk body reads inputs ONLY
    through per-cell row gathers, so the remap cannot change bits; it
    only changes WHICH rows each host must materialize (the per-host
    sampling reduction of the multi-host executor).
    """
    idx = np.asarray(idx)
    n_padded = idx.shape[0]
    if n_padded % n_devices:
        raise ValueError(f"{n_padded} cells do not tile {n_devices} "
                         f"devices")
    per = n_padded // n_devices
    uniq = [np.unique(idx[d * per:(d + 1) * per])
            for d in range(n_devices)]
    width = max(u.size for u in uniq)
    rows = np.stack([np.pad(u, (0, width - u.size), mode="edge")
                     for u in uniq]).astype(np.int32)
    local = np.empty((n_padded,), np.int32)
    for d, u in enumerate(uniq):
        seg = idx[d * per:(d + 1) * per]
        local[d * per:(d + 1) * per] = np.searchsorted(u, seg)
    return rows, local


def unflatten(plan: CellPlan, x: Array) -> Array:
    """Per-cell values ``(n_padded, ...)`` -> stacked ``(S, B, K, ...)``,
    dropping pad cells. The inverse of ``flatten`` on valid cells."""
    return x[:plan.n_cells].reshape(plan.stacked_shape + x.shape[1:])


def flatten(plan: CellPlan, x: Array) -> Array:
    """Stacked ``(S, B, K, ...)`` -> per-cell ``(n_padded, ...)``. Pad
    cells receive copies of cell 0's row (finite, mask-dropped later)."""
    flat = jnp.reshape(x, (plan.n_cells,) + x.shape[3:])
    n_pad = plan.n_padded - plan.n_cells
    if n_pad:
        flat = jnp.concatenate(
            [flat, jnp.broadcast_to(flat[:1], (n_pad,) + flat.shape[1:])])
    return flat
