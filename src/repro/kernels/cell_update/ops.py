"""Public cell-update ops: kernel-mode resolution, validated kernel
dispatch, and the FLOPs/bytes cost model the roofline benchmark reads.

Kernel MODES (the ``kernel=`` knob of ``repro.core.queueing.run`` and
the benchmarks' ``--kernel`` flag):

  ``"off"``        the ``lax.scan`` reference body (``ref``) — the
                   default everywhere off-TPU.
  ``"on"``         the compiled Pallas kernel. Needs a TPU: off-TPU it
                   raises rather than quietly interpreting.
  ``"interpret"``  the Pallas kernel through the interpreter — same
                   jnp ops, runs anywhere; bit-exact vs the scan body,
                   so CPU/CI can test the kernel path.
  ``"auto"``       resolves to ``"on"`` on TPU, ``"off"`` elsewhere —
                   and to ``"off"`` when the sketch's ``n_bins`` is not
                   a multiple of the 128 lane width. Only ``"auto"``
                   may pick the reference; an explicit kernel mode the
                   kernel cannot serve raises.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.cell_update.kernel import (cell_update_tc, lane_blocks,
                                              time_block)
from repro.kernels.hist_sketch import ops as hist_ops
from repro.kernels.hist_sketch.kernel import LANE
from repro.kernels.hist_sketch.ops import on_tpu

KERNEL_MODES = ("auto", "on", "off", "interpret")


def resolve_kernel_mode(kernel: str | bool | None = "auto", *,
                        n_bins: int | None = None) -> str:
    """Normalize a ``kernel=`` knob to a concrete mode: ``"on"``,
    ``"off"`` or ``"interpret"`` (never ``"auto"``). Accepts the string
    modes plus ``None``/``False`` (off) and ``True`` (on). ``n_bins`` is
    the sketch width of a run that keeps percentiles (None: no sketch).
    Raises for ``"on"`` without a TPU and for a kernel mode with a
    sketch width the kernel cannot lay out."""
    if kernel is None or kernel is False:
        return "off"
    if kernel is True:
        kernel = "on"
    if kernel not in KERNEL_MODES:
        raise ValueError(
            f"kernel must be one of {KERNEL_MODES}, got {kernel!r}")
    aligned = n_bins is None or n_bins % LANE == 0
    if kernel == "auto":
        return "on" if on_tpu() and aligned else "off"
    if kernel != "off" and not aligned:
        raise ValueError(
            f"kernel={kernel!r} needs n_bins % {LANE} == 0, got "
            f"n_bins={n_bins}; use kernel='auto' or 'off'")
    if kernel == "on" and not on_tpu():
        raise RuntimeError(
            "kernel='on' needs a TPU; use kernel='interpret' to run the "
            "Pallas kernel through the interpreter")
    return kernel


def kernel_path_mode() -> str:
    """The kernel-path mode a measurement can always take here: the
    compiled kernel on a TPU, the interpreter elsewhere. For benchmark
    rows that exist to exercise the kernel path and record which mode
    ran; the engine itself never picks the interpreter."""
    return "on" if on_tpu() else "interpret"


def cell_lanes(n_cells: int) -> int:
    """Cell lanes the kernel computes for ``n_cells`` cells, padding
    included (a multiple of 128)."""
    n_cb, cr = lane_blocks(n_cells)
    return n_cb * cr * LANE


def _to_lanes(y, n_cb: int, cr: int):
    """(..., rows, lanes) -> (..., n_cb, rows * cr, 128): cell block
    leading, each row one (cr, 128) tile of its block."""
    *lead, rows, _ = y.shape
    y = y.reshape(*lead, rows, n_cb, cr, LANE)
    y = jnp.moveaxis(y, -3, -4)
    return y.reshape(*lead, n_cb, rows * cr, LANE)


def _from_lanes(y, n_cells: int):
    """Inverse of ``_to_lanes``: (..., n_cb, rows * cr, 128) ->
    (..., rows, n_cells), pad lanes dropped."""
    *lead, n_cb, rc, _ = y.shape
    cr = lane_blocks(n_cells)[1]
    y = y.reshape(*lead, n_cb, rc // cr, cr, LANE)
    y = jnp.moveaxis(y, -4, -3)
    return y.reshape(*lead, rc // cr, n_cb * cr * LANE)[..., :n_cells]


def cell_update(free, ssum, comp, cnt, hist, cum, warm, valid, servers,
                services, seed_idx, rates, k_mask, ovh, policy_code,
                model_code, mix, p_slow, slow_factor, p_fail, delay,
                svc_idx=None, *,
                n_servers: int, n_bins: int, block: int,
                interpret: bool = False, has_shared: bool = False,
                has_timed: bool = False, has_dists: bool = False):
    """Kernel-path twin of ``ref.cell_update_ref`` (same signature, same
    bits): validates the layout, gathers each cell's inputs and
    parameters onto the kernel's lanes (``kernel`` module note), calls
    the Pallas kernel, and folds its per-step responses into the
    histogram with ``hist_sketch``, as the scan does per block.

    The gather is exact: ``cum[seed_idx] / rates`` is the scan's own
    per-step arrival time, and the server ids and service columns are
    copies. ``k_mask`` rows are prefix masks by plan construction
    (``queueing._plan_cell_params``), so they compress losslessly to a
    per-cell copy COUNT. Pad lanes alias cell 0 and are dropped on the
    way out. Raises for a sketch whose ``n_bins`` is not a multiple of
    the 128 lane width and for a chunk not padded to ``block``.
    """
    del n_servers
    t_total = cum.shape[1]
    need_hist = hist.size > 0
    if need_hist and n_bins % LANE != 0:
        raise ValueError(f"the cell_update kernel needs n_bins % {LANE} "
                         f"== 0, got n_bins={n_bins}")
    if t_total % block != 0 or block % LANE != 0:
        raise ValueError(
            f"kernel mode needs the chunk padded to a multiple of a "
            f"lane-aligned block (T={t_total}, block={block}); "
            f"_chunk_layout arranges both when the kernel is on")
    n_cells, k_max = k_mask.shape
    n_cb, cr = lane_blocks(n_cells)
    lane = jnp.arange(n_cb * cr * LANE)
    cell = jnp.where(lane < n_cells, lane, 0)        # pad lanes -> cell 0
    seed_l = seed_idx[cell]
    svc_l = (svc_idx if has_dists else seed_idx)[cell]
    rates_l = rates[cell]
    x = jnp.concatenate([
        (cum.T[:, seed_l] / rates_l)[:, None],                 # (T, 1, L)
        jnp.moveaxis(servers, 0, -1)[..., seed_l].astype(jnp.float32),
        jnp.moveaxis(services, 0, -1)[..., svc_l]], axis=1)
    prm = jnp.stack([
        ovh, mix, k_mask.astype(jnp.float32).sum(axis=1),
        policy_code.astype(jnp.float32), model_code.astype(jnp.float32),
        p_slow, slow_factor, p_fail, delay])[:, cell]
    code = ((valid > 0).astype(jnp.int32)
            + 2 * (warm > 0).astype(jnp.int32))
    out = cell_update_tc(
        code, _to_lanes(prm, n_cb, cr), _to_lanes(free.T[:, cell], n_cb, cr),
        _to_lanes(jnp.stack([ssum, comp, cnt])[:, cell], n_cb, cr),
        _to_lanes(x, n_cb, cr), k_max=k_max,
        block_t=time_block(block, x.shape[1], cr, need_hist),
        need_hist=need_hist, has_shared=has_shared, has_timed=has_timed,
        interpret=interpret)
    free = _from_lanes(out[0], n_cells).T
    ssum, comp, cnt = _from_lanes(out[1], n_cells)
    if need_hist:
        resp, w_live = jnp.moveaxis(_from_lanes(out[2], n_cells), 1, 0)
        idx = hist_ops.bin_indices(resp, w_live, n_bins=n_bins)
        hist = hist + hist_ops.hist_accum(idx, n_bins=n_bins, block_t=block)
    return free, ssum, comp, cnt, hist


def cell_update_costs(*, n_cells: int, n_servers: int, k_max: int,
                      n_arrivals: int, n_bins: int, n_seeds: int,
                      n_svc: int | None = None, chunk: int | None = None,
                      need_hist: bool = True) -> dict[str, float]:
    """Analytic FLOPs / HBM-byte model of the kernel path over a whole
    stream, for the roofline benchmark.

    The kernel computes every cell LANE, padding included
    (``cell_lanes``). Per arrival per lane the step costs ~``k_max *
    (3 * n_servers + 12) + 10`` flops: the server compares, the gather's
    pick and the scatter's select are ``O(k * N)``, the policy selects
    the rest. With the sketch, the histogram fold adds ``2 * n_bins``
    flops per arrival per cell (the hist_sketch contraction). HBM bytes
    per chunk: the seed-level inputs read once by the wrapper's gather,
    the per-lane inputs it writes and the kernel reads (``1 + k_max +
    n_svc`` rows), with the sketch each step's response and weight
    written by the kernel and read by the fold, and the carry read and
    written once — per CHUNK, not per arrival, which is the kernel's
    point.
    """
    n_svc = k_max if n_svc is None else n_svc
    chunk = n_arrivals if chunk is None else min(chunk, n_arrivals)
    n_chunks = -(-n_arrivals // chunk)
    lanes = cell_lanes(n_cells)
    rows = 1 + k_max + n_svc
    step_flops = k_max * (3 * n_servers + 12) + 10
    flops = float(n_arrivals) * (lanes * step_flops
                                 + (n_cells * 2 * n_bins if need_hist
                                    else 0))
    carry_bytes = 2 * 4 * (lanes * (n_servers + 3)
                           + (n_cells * n_bins if need_hist else 0))
    input_bytes = 4 * chunk * (n_seeds * rows + 2 * lanes * rows)
    if need_hist:
        input_bytes += 4 * chunk * 2 * (2 * lanes + n_cells)
    hbm_bytes = float(n_chunks) * (carry_bytes + input_bytes)
    return {"flops": flops, "hbm_bytes": hbm_bytes,
            "intensity": flops / hbm_bytes}
