"""Pallas TPU histogram-sketch kernel.

The sweep engine's percentile sketch needs, per grid cell, a count of
responses falling into each of ``n_bins`` log-spaced buckets. The obvious
per-step ``hist.at[idx].add(w)`` scatter is the one op class TPUs hate —
PR 2 paid for it on every arrival. This kernel replaces the scatter with
MXU-friendly dense algebra over a *block of steps*:

    one-hot(idx)[t, b] = [idx_hi[t] == b // LANE] * [idx_lo[t] == b % LANE]

with ``LANE = 128`` (the TPU lane width), so the (block_t, n_bins) one-hot
never materializes. Instead two skinny indicator matrices

    A[t, h] = [idx[t] // LANE == h]        (block_t, n_bins // LANE)
    B[t, l] = [idx[t] %  LANE == l]        (block_t, LANE)

are contracted over the step axis, ``acc += A^T @ B`` — one small matmul
per (cell, step-block) — and the (n_bins // LANE, LANE) accumulator lives
in VMEM scratch for the whole pass over steps (the grid's step axis is
innermost, hence sequential on a TPU core).

Layout: the wrapper re-lays ``idx`` (T, C) as (C, 1, T) — cell axis
leading with a unit block, steps on the lanes — so each grid step reads
a (1, block_t) row (Mosaic needs the two minor block dims to be
multiples of (8, 128) or to span the array). The indicators are built
transposed from that row, ``A^T`` (n_hi, block_t) and ``B^T`` (LANE,
block_t), and contracted over their lane axes. A (T, C, 1) column
layout would pad the unit lane axis to 128 lanes in HBM.

Masking rides on the index encoding: callers pass ``idx = -1`` for steps
that must not count (warmup, chunk padding). The arithmetic shift maps
-1 to ``hi = -1``, which matches no histogram row, so masked steps
contribute exactly zero — no weights input needed.

Counts are accumulated in float32; 0/1 matmuls are exact until a single
(cell, bin) exceeds 2**24 entries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
LANE_SHIFT = 7          # log2(LANE): idx >> LANE_SHIFT == idx // LANE


def _hist_kernel(idx_ref, out_ref, acc_ref, *, n_hi: int, block_t: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0]                         # (1, block_t) int32
    hi = jnp.right_shift(idx, LANE_SHIFT)    # -1 -> -1: matches no row
    lo = jnp.bitwise_and(idx, LANE - 1)      # in [0, LANE)
    a_t = (hi == jax.lax.broadcasted_iota(
        jnp.int32, (n_hi, block_t), 0)).astype(jnp.float32)
    b_t = (lo == jax.lax.broadcasted_iota(
        jnp.int32, (LANE, block_t), 0)).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        a_t, b_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (n_hi, LANE)

    @pl.when(it == pl.num_programs(1) - 1)
    def _finish():
        out_ref[0] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "block_t", "interpret"))
def hist_accum_tc(idx: jax.Array, *, n_bins: int, block_t: int = 512,
                  interpret: bool = False) -> jax.Array:
    """idx (T, C) int32 in [-1, n_bins) -> per-cell counts (C, n_bins) f32.

    ``idx == -1`` entries are skipped. Requires ``T % block_t == 0``,
    ``block_t % 128 == 0`` and ``n_bins % 128 == 0`` (``ops.hist_accum``
    pads the step axis and validates).
    """
    t, c = idx.shape
    assert t % block_t == 0, (t, block_t)
    assert block_t % LANE == 0, block_t
    assert n_bins % LANE == 0, n_bins
    n_hi = n_bins // LANE
    grid = (c, t // block_t)

    kernel = functools.partial(_hist_kernel, n_hi=n_hi, block_t=block_t)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, block_t), lambda ic, it: (ic, 0, it))],
        out_specs=pl.BlockSpec((1, n_hi, LANE), lambda ic, it: (ic, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, n_hi, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_hi, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx.T.reshape(c, 1, t))
    return out.reshape(c, n_bins)
