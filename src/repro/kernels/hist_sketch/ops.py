"""Public histogram-sketch ops: log binning, padded kernel dispatch, and
percentile read-out.

This package owns the sketch geometry (``HIST_LO`` / ``HIST_HI`` /
``DEFAULT_BINS``): ``n_bins`` log-spaced buckets spanning [HIST_LO,
HIST_HI]; values outside clamp to the edge bins. ``repro.core.queueing``
re-exports the constants for backwards compatibility.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.hist_sketch.kernel import LANE, hist_accum_tc
from repro.kernels.hist_sketch.ref import hist_accum_ref

# Unit-mean service times => responses live well inside [1e-3, 1e5].
HIST_LO = 1e-3
HIST_HI = 1e5
DEFAULT_BINS = 2048

def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (the compiled kernels'
    only target)."""
    return jax.default_backend() == "tpu"


def _log_scale(n_bins: int, lo: float, hi: float):
    log_lo = jnp.log(jnp.float32(lo))
    scale = (n_bins - 1) / (jnp.log(jnp.float32(hi)) - log_lo)
    return log_lo, scale


def bin_indices(values: jax.Array, warm: jax.Array | None = None, *,
                n_bins: int = DEFAULT_BINS, lo: float = HIST_LO,
                hi: float = HIST_HI) -> jax.Array:
    """Log-bin indices (same shape as ``values``, int32 in [-1, n_bins)).

    Entries where ``warm`` (broadcastable 0/1 weight) is zero are encoded
    as -1, which the accumulators skip.
    """
    log_lo, scale = _log_scale(n_bins, lo, hi)
    idx = ((jnp.log(values) - log_lo) * scale).astype(jnp.int32)
    idx = jnp.clip(idx, 0, n_bins - 1)
    if warm is not None:
        idx = jnp.where(jnp.broadcast_to(warm, values.shape) > 0, idx, -1)
    return idx


def hist_accum(idx: jax.Array, *, n_bins: int = DEFAULT_BINS,
               block_t: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """idx (T, C) int32 in [-1, n_bins) -> per-cell counts (C, n_bins) f32.

    Pads the step axis up to a multiple of the (lane-aligned) block with
    skip entries and dispatches the Pallas kernel. ``interpret``: None
    picks the compiled kernel on a TPU and the interpreter elsewhere,
    and the jnp reference when ``n_bins`` is not a multiple of the 128
    lane width; True runs the interpreter; False the compiled kernel,
    which raises without a TPU. Only None may choose the reference: an
    explicit kernel request with unaligned ``n_bins`` raises.
    """
    if n_bins % LANE != 0:
        if interpret is None:
            return hist_accum_ref(idx, n_bins=n_bins)
        raise ValueError(f"the hist_sketch kernel needs n_bins % {LANE} "
                         f"== 0, got n_bins={n_bins}")
    if interpret is None:
        interpret = not on_tpu()
    elif not interpret and not on_tpu():
        raise RuntimeError("the compiled hist_sketch kernel needs a TPU; "
                           "pass interpret=True to run the interpreter")
    t, _ = idx.shape
    bt = -(-min(block_t, t) // LANE) * LANE
    pad = (-t) % bt
    if pad:
        idx = jnp.concatenate(
            [idx, jnp.full((pad, idx.shape[1]), -1, idx.dtype)], axis=0)
    return hist_accum_tc(idx, n_bins=n_bins, block_t=bt, interpret=interpret)


def hist_sketch(values: jax.Array, warm: jax.Array | None = None, *,
                n_bins: int = DEFAULT_BINS, lo: float = HIST_LO,
                hi: float = HIST_HI, block_t: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """Log-histogram counts (C, n_bins) of a (T, C) block of values."""
    idx = bin_indices(values, warm, n_bins=n_bins, lo=lo, hi=hi)
    return hist_accum(idx, n_bins=n_bins, block_t=block_t,
                      interpret=interpret)


def sketch_quantiles(hist: jax.Array, qs: jax.Array, *, lo: float = HIST_LO,
                     hi: float = HIST_HI) -> jax.Array:
    """Percentiles (Q, ...) read from histogram counts (..., n_bins).

    Returns the geometric midpoint of the first bin at which the cdf
    reaches the target mass — relative error is at most one log-bin width
    (~0.5% at the default 2048 bins over 8 decades).
    """
    n_bins = hist.shape[-1]
    log_lo, scale = _log_scale(n_bins, lo, hi)
    cdf = jnp.cumsum(hist, axis=-1)                       # (..., n_bins)
    count = cdf[..., -1:]                                 # (..., 1)
    qs = jnp.asarray(qs, jnp.float32)
    targets = qs.reshape((-1,) + (1,) * hist.ndim) / 100.0 * count[None]
    bin_idx = jnp.argmax(cdf[None] >= targets, axis=-1)   # (Q, ...)
    return jnp.exp(log_lo + (bin_idx + 0.5) / scale)
