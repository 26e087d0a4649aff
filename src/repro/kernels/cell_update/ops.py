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

import jax

from repro.kernels.cell_update.kernel import cell_update_tc
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


def cell_update(free, ssum, comp, cnt, hist, cum, warm, valid, servers,
                services, seed_idx, rates, k_mask, ovh, policy_code,
                model_code, mix, p_slow, slow_factor, p_fail, delay,
                svc_idx=None, *,
                n_servers: int, n_bins: int, block: int,
                interpret: bool = False, has_shared: bool = False,
                has_timed: bool = False, has_dists: bool = False):
    """Kernel-path twin of ``ref.cell_update_ref`` (same signature, same
    bits): validates the layout, derives the scalar-prefetch operands
    from the plan parameters, and calls the Pallas kernel.

    ``k_mask`` rows are prefix masks by plan construction
    (``queueing._plan_cell_params``), so they compress losslessly to a
    per-cell copy COUNT — an int the kernel prefetches and re-expands
    with an iota compare (boolean, no rounding). The degradation /
    timed-policy parameters (``p_slow``/``slow_factor``/``p_fail``/
    ``delay``) prefetch as-is; ``has_timed`` is accepted for signature
    parity with the scan body only (the kernel's timed ops are always
    compiled — scalar selects keep them inert and bit-invisible for
    non-timed cells).
    Raises for a sketch whose ``n_bins`` is not a multiple of the 128
    lane width and for a chunk not padded to a lane-aligned block.
    """
    t_total = cum.shape[1]
    if hist.size > 0 and n_bins % LANE != 0:
        raise ValueError(f"the cell_update kernel needs n_bins % {LANE} "
                         f"== 0, got n_bins={n_bins}")
    if t_total % block != 0 or block % LANE != 0:
        raise ValueError(
            f"kernel mode needs the chunk padded to a multiple of a "
            f"lane-aligned block (T={t_total}, block={block}); "
            f"_chunk_layout arranges both when the kernel is on")
    k_count = k_mask.astype(jax.numpy.int32).sum(axis=1)
    return cell_update_tc(
        free, ssum, comp, cnt, hist, cum, warm, valid, servers, services,
        seed_idx, k_count, policy_code, model_code, rates, ovh, mix,
        p_slow, slow_factor, p_fail, delay, svc_idx,
        n_servers=n_servers, n_bins=n_bins, block_t=block,
        interpret=interpret, has_shared=has_shared, has_dists=has_dists)


def cell_update_costs(*, n_cells: int, n_servers: int, k_max: int,
                      n_arrivals: int, n_bins: int, n_seeds: int,
                      n_svc: int | None = None, chunk: int | None = None,
                      need_hist: bool = True) -> dict[str, float]:
    """Analytic FLOPs / HBM-byte model of the fused kernel over a whole
    stream, for the roofline benchmark.

    Per arrival per cell the step body costs ~``k_max * (3 * n_servers
    + 12) + 10`` flops (one-hot gather + scatter dominate at
    ``O(k * N)``; the selects/compares of the policy branches are the
    rest), plus ``2 * n_bins`` flops per histogrammed arrival for the
    one-hot bin add over the (n_bins / 128, 128) accumulator. HBM bytes count one read+write of the
    per-cell carry per chunk plus one pass over the seed-level sampled
    inputs — the kernel's whole point is that the carry term is per
    CHUNK, not per arrival.
    """
    n_svc = k_max if n_svc is None else n_svc
    chunk = n_arrivals if chunk is None else min(chunk, n_arrivals)
    n_chunks = -(-n_arrivals // chunk)
    step_flops = k_max * (3 * n_servers + 12) + 10
    hist_flops = 2 * n_bins if need_hist else 0
    flops = float(n_cells) * n_arrivals * (step_flops + hist_flops)
    carry_floats = n_servers + 2 + (n_bins if need_hist else 0)
    carry_bytes = 2 * n_cells * carry_floats * 4          # r+w per chunk
    input_bytes = n_seeds * chunk * (1 + k_max + n_svc) * 4
    hbm_bytes = float(n_chunks) * (carry_bytes + input_bytes)
    return {"flops": flops, "hbm_bytes": hbm_bytes,
            "intensity": flops / hbm_bytes}
