"""Pallas TPU kernel: one chunk of the sweep engine's cell update, fused.

The scan-body reference (``ref.cell_update_ref``) round-trips the whole
per-cell carry — the (C, N) server free-time grid, the Kahan (sum,
comp) pair, and the (C, n_bins) histogram counts — through HBM-backed
scan state on EVERY arrival. This kernel keeps all of it in VMEM for a
whole chunk and touches HBM once per (cell, chunk):

  grid = (C, T // block_t)        cells outer, time-blocks inner
                                  (innermost axis is sequential on a
                                  TPU core, so VMEM scratch persists
                                  across a cell's time-blocks)

  VMEM carry per cell             free_s  (1, N)        f32
  (scratch, init at it == 0,      ssum_s / comp_s / cnt_s (1, 1) f32
  flushed to HBM at the last      hist_s  (n_hi, 128)   f32
  time-block):                    (n_hi = n_bins / 128 — the
                                  hist_sketch accumulator layout)

  HBM traffic per (cell, chunk)   read + write of the carry blocks
                                  plus one pass over the seed-level
                                  inputs — vs O(T) carry round-trips
                                  in the scan body.

Block layout (Mosaic's rule: the two minor block dims are multiples of
(8, 128) or span the whole array). Every per-cell carry array gets a
leading cell axis with a unit block — free (C, 1, N), the Kahan pair and
count (C, 1, 1), hist (C, n_hi, 128) — and every per-step input puts
TIME ON THE LANE AXIS with a leading seed axis: cum (S, 1, T), warm /
valid (1, 1, T), servers (S, k_max, T) and services (S', n_svc, T), each
blocked (1, rows, block_t) with ``block_t % 128 == 0``. (A (T, k_max)
layout would pad the copy axis to 128 lanes in HBM.) The step loop runs
over 128-step lane groups: one aligned (rows, 128) load per input and
group, then each step picks its lane with a one-hot ``max(where(...))``
— an exact PICK, like the free-time gather — so no step needs a dynamic
lane slice, which Mosaic cannot lower. Inside a step the copy axis is
the SUBLANE axis: ``srv`` / ``svc`` / ``finish`` are (k_max, 1) columns
and the free-time gather is a (k_max, N) one-hot against the (1, N)
free row. VMEM per grid step: five double-buffered input blocks of at
most (8, block_t) f32 tiles — about 160 KiB at block_t = 512 — plus the
carry, far under the scoped limit.

Per-cell plan coordinates ride as SCALAR-PREFETCH operands (seed_idx,
k_count, policy_code, model_code, rates, overhead, mix, and the PR-7
degradation / timed-policy parameters p_slow, slow_factor, p_fail,
delay — see ``repro.core.cellplan``): the seed coordinate drives the
input BlockSpec index maps, so each cell's grid row streams exactly its
seed's (block_t,) slice of the sampled inputs and the (C, T) expansion
is never materialized — the same "gather by coordinate, not by
position" rule that makes sharded execution bit-identical.

Bit-identity with the scan body (the contract the parity tests pin in
interpret mode):

  * The step body mirrors ``ref.step_cell`` op-for-op; all float ops
    are elementwise or min/max over the tiny copy axis, so the
    (k, 1)-shaped retiling cannot change bits.
  * The free-time gather is a one-hot ``max(where(...))`` — an exact
    PICK of an element, no arithmetic on it.
  * The occupancy scatter is a Python-unrolled sequence of selects in
    copy order, matching XLA's last-wins ``.at[srv].set`` semantics
    (srv entries are distinct by construction, so order only matters
    for the masked no-op copies that rewrite their own old value).
  * The Kahan fold is ``ref.kahan_fold`` — literally the same
    function — gated so zero-weight (padding / pre-warmup) steps are
    bitwise no-ops. Its ``optimization_barrier`` guards against XLA's
    algebraic simplifier, so it is kept where the body runs through
    XLA (interpret mode) and left out of the Mosaic lowering, which has
    no such rewrite and no lowering for the barrier.
  * Histogram counts add a 0/1 one-hot of each step's bin per step,
    integers in f32 (exact below 2**24 per bin), so they equal the
    scan body's block-wise counts in any order; the bin indices come
    from the same ``hist_sketch.ops.bin_indices``.

On the chip the compiled kernel and the XLA scan body may still differ
in the last bit where the two compilers implement a transcendental
(``log`` in the bin index) or a division differently; the chip smoke
test reports whether they were bit-identical.

The CRN / fold_in contract is untouched: sampling stays host-side and
seed-level (see ``queueing.py``); the kernel only changes WHERE the
deterministic update runs. That includes the degradation model's CRN
contract (``ref.step_cell``'s design note): the per-copy failure /
straggler uniforms arrive as extra ``services`` columns drawn from the
dedicated ``_DEGRADE_FOLD`` branch, the kernel never samples, and a
healthy grid carries no such columns — so healthy cells keep their
pre-degradation bits through this kernel exactly as through the scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scenario import Policy, ServiceModel
from repro.kernels.cell_update.ref import kahan_fold, retry_offsets
from repro.kernels.hist_sketch import ops as hist_ops
from repro.kernels.hist_sketch.kernel import LANE, LANE_SHIFT


def _cell_kernel(seed_ref, kcnt_ref, pol_ref, mdl_ref, rate_ref, ovh_ref,
                 mix_ref, psl_ref, sfa_ref, pfl_ref, dly_ref,
                 free_in, ssum_in, comp_in, cnt_in, *rest, n_servers: int,
                 k_max: int, n_svc: int, block_t: int, n_hi: int,
                 need_hist: bool, has_shared: bool, interpret: bool):
    if need_hist:
        (hist_in, cum_ref, warm_ref, valid_ref, srv_ref, svc_ref,
         free_out, ssum_out, comp_out, cnt_out, hist_out,
         free_s, ssum_s, comp_s, cnt_s, hist_s) = rest
    else:
        (cum_ref, warm_ref, valid_ref, srv_ref, svc_ref,
         free_out, ssum_out, comp_out, cnt_out,
         free_s, ssum_s, comp_s, cnt_s) = rest
    ic = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        free_s[...] = free_in[0]
        ssum_s[...] = ssum_in[0]
        comp_s[...] = comp_in[0]
        cnt_s[...] = cnt_in[0]
        if need_hist:
            hist_s[...] = hist_in[0]

    # this cell's plan coordinates (scalar prefetch)
    rate = rate_ref[ic]
    ovh = ovh_ref[ic]
    mix = mix_ref[ic]
    kcnt = kcnt_ref[ic]
    psl = psl_ref[ic]
    sfa = sfa_ref[ic]
    pfl = pfl_ref[ic]
    dly = dly_ref[ic]
    is_sd = mdl_ref[ic] == int(ServiceModel.SERVER_DEPENDENT)
    is_cancel = pol_ref[ic] == int(Policy.CANCEL_ON_COMPLETE)
    is_idle = pol_ref[ic] == int(Policy.REPLICATE_TO_IDLE)
    is_retry = pol_ref[ic] == int(Policy.TIMEOUT_RETRY)
    is_timed = is_retry | (pol_ref[ic] == int(Policy.HEDGE_AFTER_DELAY))

    # the copy axis is the sublane axis: (k_max, 1) columns
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k_max, 1), 0)
    mask = iota_k < kcnt            # k_mask rows are prefixes by plan
    primary = iota_k == 0
    iota_n = jax.lax.broadcasted_iota(
        jnp.int32, (k_max, n_servers), 1).astype(jnp.float32)
    # timed-policy dispatch-time coefficients (see ref.step_cell).
    # Pallas kernels cannot capture non-scalar constants, so the backoff
    # offsets are assembled from scalar selects — exact small floats,
    # same values as the ref's literal array.
    retry_coeff = jnp.zeros((k_max, 1), jnp.float32)
    for j, off in enumerate(retry_offsets(k_max)):
        retry_coeff = jnp.where(iota_k == j, off, retry_coeff)
    coeff = jnp.where(is_retry, retry_coeff, iota_k.astype(jnp.float32))
    # TIMEOUT_RETRY's LAST in-budget attempt ignores its blackhole draw
    last_attempt = is_retry & (iota_k == kcnt - 1)
    n_base = k_max + (1 if has_shared else 0)
    has_degr = n_svc > n_base
    iota_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    if need_hist:
        iota_hi = jax.lax.broadcasted_iota(jnp.int32, (n_hi, 1), 0)

    def group(g, carry):
        # one aligned 128-step lane group of every per-step input
        off = pl.multiple_of(g * LANE, LANE)
        cum_g = cum_ref[0, :, pl.ds(off, LANE)]       # (1, 128)
        warm_g = warm_ref[0, :, pl.ds(off, LANE)]     # (1, 128)
        valid_g = valid_ref[0, :, pl.ds(off, LANE)]   # (1, 128)
        srv_g = srv_ref[0, :, pl.ds(off, LANE)]       # (k_max, 128)
        svc_g = svc_ref[0, :, pl.ds(off, LANE)]       # (n_svc, 128)

        def step(i, carry):
            if need_hist:
                free, ssum, comp, cnt, hist = carry
            else:
                free, ssum, comp, cnt = carry
            lane = iota_lane == i

            def pick(x):             # exact one-hot pick of lane i
                return jnp.max(jnp.where(lane, x, -jnp.inf), axis=1,
                               keepdims=True)

            t = pick(cum_g) / rate                         # (1, 1)
            srv = pick(srv_g)                              # (k, 1)
            svc_col = pick(svc_g)                          # (n_svc, 1)
            shared = (svc_col[k_max:k_max + 1] if has_shared
                      else svc_col[0:1])
            degr = (svc_col[n_base:n_base + k_max] if has_degr
                    else jnp.zeros((k_max, 1), jnp.float32))
            svc = svc_col[:k_max]
            w = pick(warm_g)
            # padding steps zero the effective delay (see ref.step_cell)
            dly_eff = jnp.where(pick(valid_g) > 0, dly, 0.0)
            # exact gather: one-hot pick of free[srv] (no arithmetic)
            oh = srv == iota_n                              # (k, N)
            cur = jnp.max(jnp.where(oh, free, -jnp.inf), axis=1,
                          keepdims=True)                    # (k, 1)
            # step_cell, op-for-op on (k, 1) sublanes
            svc = jnp.where(is_sd, mix * shared + (1.0 - mix) * svc, svc)
            svc = jnp.where(degr >= 1.0 - psl, svc * sfa, svc)
            alive = degr >= pfl
            start = jnp.maximum(cur, t)
            finish = start + svc
            t_win = jnp.min(jnp.where(mask & alive, finish, jnp.inf),
                            axis=0, keepdims=True)
            dispatch = mask & (primary | (cur <= t))
            val_all = jnp.where(mask & alive, finish, cur)
            val_cancel = jnp.where(mask & alive, jnp.maximum(cur, t_win),
                                   cur)
            val_idle = jnp.where(dispatch & alive, finish, cur)
            # timed policies: sequential dispatch, unrolled in copy order
            # with static row slices (mirrors ref.step_cell's loop)
            disp_t = t + dly_eff * coeff
            alive_eff = alive | last_attempt
            fired_finish = jnp.maximum(cur, disp_t) + svc
            fire_all = dly_eff <= 0.0
            best = jnp.full((1, 1), jnp.inf, jnp.float32)
            made = jnp.zeros((k_max, 1), bool)
            for j in range(k_max):
                row = slice(j, j + 1)
                made_j = mask[row] if j == 0 else (
                    mask[row] & (fire_all | (best > disp_t[row])))
                best = jnp.minimum(
                    best, jnp.where(made_j & alive_eff[row],
                                    fired_finish[row], jnp.inf))
                made = made | ((iota_k == j) & made_j)
            val_timed = jnp.where(made & alive_eff, fired_finish, cur)
            new_val = jnp.where(
                is_cancel, val_cancel,
                jnp.where(is_idle, val_idle,
                          jnp.where(is_timed, val_timed, val_all)))
            # scatter: unrolled selects in copy order == XLA's last-wins
            # .at[srv].set (srv entries distinct; masked copies rewrite
            # their own old value either way)
            for j in range(k_max):
                free = jnp.where(oh[j:j + 1], new_val[j:j + 1], free)
            resp_win = t_win - t + ovh
            resp_idle = (jnp.min(jnp.where(dispatch & alive, finish,
                                           jnp.inf), axis=0, keepdims=True)
                         - t + ovh)
            resp_timed = best - t + ovh
            resp = jnp.where(is_idle, resp_idle,
                             jnp.where(is_timed, resp_timed, resp_win))
            w_live = w * jnp.isfinite(resp).astype(jnp.float32)
            ssum, comp = kahan_fold(ssum, comp, resp, w_live,
                                    barrier=interpret)
            cnt = cnt + w_live
            if not need_hist:
                return free, ssum, comp, cnt
            # hist_sketch accumulation (see that kernel's design note):
            # idx == -1 (padding / pre-warmup / incomplete) matches no
            # row — the completed weight, not the raw warmup weight,
            # gates the bins (same as the ref's w_live)
            idx = hist_ops.bin_indices(resp, w_live, n_bins=n_hi * LANE)
            hi = jnp.right_shift(idx, LANE_SHIFT)
            lo = jnp.bitwise_and(idx, LANE - 1)
            # one-hot as (n_hi, 1) row-match & (1, 128) lane-match:
            # Mosaic broadcasts one axis at a time
            hist = hist + ((iota_hi == hi) & (iota_lane == lo)).astype(
                jnp.float32)
            return free, ssum, comp, cnt, hist

        return jax.lax.fori_loop(0, LANE, step, carry)

    carry = (free_s[...], ssum_s[...], comp_s[...], cnt_s[...])
    if need_hist:
        carry += (hist_s[...],)
    carry = jax.lax.fori_loop(0, block_t // LANE, group, carry)
    free_s[...] = carry[0]
    ssum_s[...] = carry[1]
    comp_s[...] = carry[2]
    cnt_s[...] = carry[3]
    if need_hist:
        hist_s[...] = carry[4]

    @pl.when(it == pl.num_programs(1) - 1)
    def _flush():
        free_out[0] = free_s[...]
        ssum_out[0] = ssum_s[...]
        comp_out[0] = comp_s[...]
        cnt_out[0] = cnt_s[...]
        if need_hist:
            hist_out[0] = hist_s[...]


@functools.partial(jax.jit, static_argnames=("n_servers", "n_bins",
                                             "block_t", "interpret",
                                             "has_shared", "has_dists"))
def cell_update_tc(free: jax.Array, ssum: jax.Array, comp: jax.Array,
                   cnt: jax.Array, hist: jax.Array, cum: jax.Array,
                   warm: jax.Array, valid: jax.Array,
                   servers: jax.Array, services: jax.Array,
                   seed_idx: jax.Array, k_count: jax.Array,
                   policy: jax.Array, model: jax.Array, rates: jax.Array,
                   ovh: jax.Array, mix: jax.Array, p_slow: jax.Array,
                   slow_factor: jax.Array, p_fail: jax.Array,
                   delay: jax.Array, svc_idx: jax.Array = None, *,
                   n_servers: int,
                   n_bins: int, block_t: int, interpret: bool = False,
                   has_shared: bool = False, has_dists: bool = False):
    """One chunk of the fused cell update. Carry free (C,N) / ssum, comp,
    cnt (C,) / hist (C, n_bins) (shape (0,0) skips the sketch); inputs
    cum (S,T) cumulative offsets, warm (T,) 0/1 post-warmup weights,
    valid (T,) 0/1 real-step flags, servers (S,T,k_max), services
    (S,T,n_svc) laid out ``[copies][shared if has_shared][degradation
    uniforms if present]``; per-cell scalar-prefetch coordinates (C,)
    each (the degradation / timed-policy parameters ride the same
    prefetch path as the policy codes). Requires ``T % block_t == 0``,
    ``block_t % 128 == 0`` and (with the sketch) ``n_bins % 128 == 0``
    — ``ops.cell_update`` validates. Returns the updated carry, free NOT
    yet rebased (the caller rebases, same as the ref). The inputs are
    re-laid time-minor here (see the module note on block layout).

    ``has_dists`` (static) is the heterogeneous-grid path: ``services``
    stacks one (n_seeds, T, n_svc) table per dist-union member along
    axis 0 and ``svc_idx`` (C,) joins the scalar-prefetch operands SOLELY
    to drive the services BlockSpec index map — the kernel BODY never
    reads it (exactly like ``seed_idx``), each cell's grid row simply
    streams its system's service slice. ``has_dists=False`` keeps the
    11-operand prefetch layout, so homogeneous grids compile the exact
    pre-dist_id program.
    """
    c_cells = free.shape[0]
    n_seed_rows, t_total = cum.shape
    k_max = servers.shape[-1]
    n_svc = services.shape[-1]
    need_hist = hist.size > 0
    assert t_total % block_t == 0, (t_total, block_t)
    assert block_t % LANE == 0, block_t
    n_tb = t_total // block_t
    n_hi = (n_bins // LANE) if need_hist else 0

    kernel = functools.partial(
        _cell_kernel, n_servers=n_servers, k_max=k_max, n_svc=n_svc,
        block_t=block_t, n_hi=n_hi, need_hist=need_hist,
        has_shared=has_shared, interpret=interpret)
    if has_dists:
        # svc_idx is prefetch operand 1, for the services index map
        # only; the body is the homogeneous kernel unchanged.
        base_kernel = kernel

        def kernel(seed_ref, svcid_ref, *rest):
            return base_kernel(seed_ref, *rest)

        def svc_time(ic, it, seed, svcid, *_):
            return (svcid[ic], 0, it)
    else:
        def svc_time(ic, it, seed, *_):
            return (seed[ic], 0, it)

    def cell_blk(ic, it, *_):
        return (ic, 0, 0)

    def seed_time(ic, it, seed, *_):
        return (seed[ic], 0, it)

    def shared_time(ic, it, *_):
        return (0, 0, it)

    carry_specs = [
        pl.BlockSpec((1, 1, n_servers), cell_blk),               # free
        pl.BlockSpec((1, 1, 1), cell_blk),                       # ssum
        pl.BlockSpec((1, 1, 1), cell_blk),                       # comp
        pl.BlockSpec((1, 1, 1), cell_blk),                       # cnt
    ]
    carry_shape = [
        jax.ShapeDtypeStruct((c_cells, 1, n_servers), jnp.float32),
        jax.ShapeDtypeStruct((c_cells, 1, 1), jnp.float32),
        jax.ShapeDtypeStruct((c_cells, 1, 1), jnp.float32),
        jax.ShapeDtypeStruct((c_cells, 1, 1), jnp.float32),
    ]
    scratch = [pltpu.VMEM((1, n_servers), jnp.float32),
               pltpu.VMEM((1, 1), jnp.float32),
               pltpu.VMEM((1, 1), jnp.float32),
               pltpu.VMEM((1, 1), jnp.float32)]
    if need_hist:
        carry_specs.append(pl.BlockSpec((1, n_hi, LANE), cell_blk))
        carry_shape.append(
            jax.ShapeDtypeStruct((c_cells, n_hi, LANE), jnp.float32))
        scratch.append(pltpu.VMEM((n_hi, LANE), jnp.float32))
    in_specs = carry_specs + [
        pl.BlockSpec((1, 1, block_t), seed_time),                # cum
        pl.BlockSpec((1, 1, block_t), shared_time),              # warm
        pl.BlockSpec((1, 1, block_t), shared_time),              # valid
        pl.BlockSpec((1, k_max, block_t), seed_time),            # servers
        pl.BlockSpec((1, n_svc, block_t), svc_time),             # services
    ]

    operands = [free.reshape(c_cells, 1, n_servers),
                ssum.reshape(c_cells, 1, 1), comp.reshape(c_cells, 1, 1),
                cnt.reshape(c_cells, 1, 1)]
    if need_hist:
        operands.append(hist.reshape(c_cells, n_hi, LANE))
    operands += [cum.reshape(n_seed_rows, 1, t_total),
                 warm.reshape(1, 1, t_total), valid.reshape(1, 1, t_total),
                 # server ids as exact small floats: the one-hot picks
                 # run on f32 lanes
                 jnp.swapaxes(servers, 1, 2).astype(jnp.float32),
                 jnp.swapaxes(services, 1, 2)]

    prefetch = [seed_idx]
    if has_dists:
        prefetch.append(svc_idx)
    prefetch += [k_count, policy, model, rates, ovh, mix, p_slow,
                 slow_factor, p_fail, delay]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(c_cells, n_tb),
        in_specs=in_specs,
        out_specs=carry_specs,
        scratch_shapes=scratch)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=carry_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret)(*prefetch, *operands)
    free_o, ssum_o, comp_o, cnt_o = (out[0][:, 0], out[1][:, 0, 0],
                                     out[2][:, 0, 0], out[3][:, 0, 0])
    hist_o = out[4].reshape(c_cells, n_hi * LANE) if need_hist else hist
    return free_o, ssum_o, comp_o, cnt_o, hist_o
