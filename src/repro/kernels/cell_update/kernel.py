"""Pallas TPU kernel: one chunk of the sweep engine's cell update, with
cells on the lanes.

The scan-body reference (``ref.cell_update_ref``) advances every cell by
one arrival per scan step, vmapped over the cell axis. This kernel takes
the same layout — each vector op of a step serves a whole block of cells
— and keeps the per-cell carry in VMEM for a whole chunk, so HBM sees it
once per chunk instead of once per arrival.

Lane layout. The C cells are padded to ``lanes = n_cb * cr * 128``
(``lane_blocks``): ``cr = min(ceil(C / 128), 8)`` sublane rows of 128
lanes make one ``(cr, 128)`` tile, one vreg, and ``n_cb`` such tiles
cover the grid. Every per-cell quantity of ``ref.step_cell`` is one
``(cr, 128)`` array; several of them stack along the sublane axis of one
operand, row ``r`` at sublanes ``[r * cr, (r + 1) * cr)``:

  grid = (n_cb, T // block_t)   cell blocks outer, time blocks inner
                                (the inner axis is sequential on a TPU
                                core, so the resident carry blocks
                                persist across a block's time blocks)

  prm  (n_cb, 9 * cr, 128)      per-cell parameters: overhead, mix, copy
                                count, policy and model codes, p_slow,
                                slow_factor, p_fail, delay (``PARAMS``)
  free (n_cb, N * cr, 128)      server free times, servers on rows
  acc  (n_cb, 3 * cr, 128)      Kahan sum, compensation, completed count
  x    (T, n_cb, rows * cr, 128) per step: arrival time, the k_max
                                server ids (exact small floats), the
                                n_svc service columns; rows = 1 + k_max
                                + n_svc, time on the leading untiled axis
                                so step ``i`` reads ``x[i]`` by a dynamic
                                leading index
  code (T / block_t, 1, block_t) per step: 1 if a real step, + 2 if
       int32, SMEM              it counts (post-warm-up); the unit axis
                                meets the block rule
  rw   (T, n_cb, 2 * cr, 128)   out, with the sketch on: each step's
                                response and completed weight

``free`` and ``acc`` are output blocks whose index does not move along
the time axis: they are the carry, initialised from their inputs at the
first time block and written back once per chunk. Inside a time block
the carry is N + 3 vregs threaded through the step loop. The wrapper
(``ops.cell_update``) gathers each cell's inputs (``cum[seed_idx] /
rates``, ``servers[seed_idx]``, ``services[svc_idx or seed_idx]``) —
exact copies, the same gather the scan does per step — and folds ``rw``
into the histogram with ``hist_sketch``, as the scan does per block. A
step row is one (8, 128)-padded tile per ``8 / cr`` rows, so ``block_t``
is sized to keep the double-buffered ``x`` and ``rw`` blocks near 8 MiB
of VMEM (``time_block``).

Bit-identity with the scan body (the contract the parity tests pin in
interpret mode):

  * The step mirrors ``ref.step_cell`` op-for-op, one lane per cell; the
    copy axis is Python-unrolled, so its mins become elementwise mins,
    which are exact in any order.
  * The free-time gather selects ``free[n]`` where ``srv == n`` (chains
    of selects over groups of servers, then a max over the groups) — an
    exact PICK of one element, no arithmetic on it.
  * The occupancy scatter is ``free[n] = where(srv_j == n, new_j,
    free[n])`` in copy order, XLA's last-wins ``.at[srv].set`` (srv
    entries are distinct; masked copies rewrite their own old value).
  * The Kahan fold is ``ref.kahan_fold`` — literally the same function —
    gated so zero-weight steps are bitwise no-ops. Its
    ``optimization_barrier`` is kept in interpret mode (the body runs
    through XLA there) and left out of the Mosaic lowering, which has no
    algebraic simplifier to guard against and no lowering for it.
  * The timed-policy block, the server-dependent blend and the
    degradation selects are compiled only when the grid has such a
    variant (``has_timed``, ``has_shared``, degradation columns in
    ``x``); without one they are the identity, as in the scan.

One exception on the CPU: XLA's CPU backend contracts the
server-dependent blend ``mix * shared + (1 - mix) * svc`` into a fused
multiply-add, and which of the two products it fuses depends on the
program around it, so a server-dependent cell can differ by an ulp
between the interpreted kernel and the scan. On the chip the compiled
kernel and the XLA scan body may differ in the last bit wherever the
two compilers round an operation differently; the chip smoke test
reports whether they were identical.
The histogram's bin indices come from XLA's ``log`` on both paths: the
fold runs outside the kernel.

The CRN / fold_in contract is untouched: sampling stays host-side and
seed-level (see ``queueing.py``); the kernel only changes WHERE the
deterministic update runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scenario import Policy, ServiceModel
from repro.kernels.cell_update.ref import kahan_fold, retry_offsets
from repro.kernels.hist_sketch.kernel import LANE

SUBLANE = 8
# rows of the per-cell parameter block, in order
PARAMS = ("ovh", "mix", "k_count", "policy", "model", "p_slow",
          "slow_factor", "p_fail", "delay")
_TILE_BYTES = SUBLANE * LANE * 4
_VMEM_BLOCK_BUDGET = 8 << 20
# steps per trip of the step loop (Mosaic unrolls fully or not at all),
# and rows per select chain of the gather: the fastest on one v5e chip
_UNROLL = 4
_PICK_GROUP = 5


def lane_blocks(n_cells: int) -> tuple[int, int]:
    """(cell blocks, sublane rows per block) of ``n_cells`` on lanes."""
    rows = -(-n_cells // LANE)
    cr = min(rows, SUBLANE)
    return -(-rows // cr), cr


def time_block(block: int, rows: int, cr: int, need_hist: bool) -> int:
    """The largest halving of ``block`` whose double-buffered input and
    output step rows fit the VMEM budget."""
    tiles = -(-rows * cr // SUBLANE)
    if need_hist:
        tiles += -(-2 * cr // SUBLANE)
    bt = block
    while bt % 2 == 0 and 2 * bt * tiles * _TILE_BYTES > _VMEM_BLOCK_BUDGET:
        bt //= 2
    return bt


def _pick(eq, rows):
    """``rows[n]`` where ``eq[n]``, exactly one ``eq`` true per lane: a
    select chain per group of ``_PICK_GROUP`` rows (-inf where the group
    misses), then a max over the groups — an exact pick, no arithmetic
    on the value, in fewer dependent steps than one chain."""
    parts = []
    for g0 in range(0, len(rows), _PICK_GROUP):
        part = jnp.where(eq[g0], rows[g0], -jnp.inf)
        for n in range(g0 + 1, min(g0 + _PICK_GROUP, len(rows))):
            part = jnp.where(eq[n], rows[n], part)
        parts.append(part)
    return functools.reduce(jnp.maximum, parts)


def _cell_kernel(code_ref, prm_ref, free_in, acc_in, x_ref, free_out,
                 acc_out, *rw_out, n_servers: int, k_max: int, n_svc: int,
                 cr: int, block_t: int, has_shared: bool, has_timed: bool,
                 interpret: bool):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        free_out[...] = free_in[...]
        acc_out[...] = acc_in[...]

    def rows(ref, n, *lead):
        return [ref[(*lead, 0, pl.ds(r * cr, cr), slice(None))]
                for r in range(n)]

    (ovh, mix, kcnt, pol, mdl, psl, sfa, pfl, dly) = rows(prm_ref,
                                                          len(PARAMS))
    is_sd = mdl == float(ServiceModel.SERVER_DEPENDENT)
    is_cancel = pol == float(Policy.CANCEL_ON_COMPLETE)
    is_idle = pol == float(Policy.REPLICATE_TO_IDLE)
    is_retry = pol == float(Policy.TIMEOUT_RETRY)
    is_timed = is_retry | (pol == float(Policy.HEDGE_AFTER_DELAY))
    mask = [kcnt > j for j in range(k_max)]     # k_mask rows are prefixes
    n_base = k_max + (1 if has_shared else 0)
    has_degr = n_svc > n_base
    if has_timed:
        coeff = [jnp.where(is_retry, off, float(j))
                 for j, off in enumerate(retry_offsets(k_max))]
        # TIMEOUT_RETRY's LAST in-budget attempt ignores its blackhole draw
        last = [is_retry & (kcnt == float(j + 1)) for j in range(k_max)]

    def step(i, carry):
        free, ssum, comp, cnt = carry
        xs = rows(x_ref, 1 + k_max + n_svc, i)
        t, srv, svc_cols = xs[0], xs[1:1 + k_max], xs[1 + k_max:]
        code = jnp.full((cr, LANE), code_ref[0, 0, i], jnp.int32)
        w = jnp.where(code >= 2, 1.0, 0.0)
        shared = svc_cols[k_max] if has_shared else svc_cols[0]
        eq = [[s == float(n) for n in range(n_servers)] for s in srv]
        cur = [_pick(eq_j, free) for eq_j in eq]            # free[srv]
        # ref.step_cell, op-for-op, one lane per cell
        svc, alive = [], []
        for j in range(k_max):
            s = svc_cols[j]
            if has_shared:
                s = jnp.where(is_sd, mix * shared + (1.0 - mix) * s, s)
            if has_degr:
                degr = svc_cols[n_base + j]
                s = jnp.where(degr >= 1.0 - psl, s * sfa, s)
                alive.append(degr >= pfl)
            else:
                alive.append(True)
            svc.append(s)
        finish = [jnp.maximum(c, t) + s for c, s in zip(cur, svc)]
        live = [m & a for m, a in zip(mask, alive)]
        t_win = functools.reduce(jnp.minimum, [
            jnp.where(lv, f, jnp.inf) for lv, f in zip(live, finish)])
        dispatch = [mask[0]] + [m & (c <= t)
                                for m, c in zip(mask[1:], cur[1:])]
        disp_alive = [d & a for d, a in zip(dispatch, alive)]
        # per-policy occupancy updates, as in ref.step_cell
        new_val = [jnp.where(lv, f, c)                      # replicate all
                   for lv, f, c in zip(live, finish, cur)]
        if has_timed:
            # sequential dispatch over the copy budget (ref.step_cell)
            dly_eff = jnp.where((code & 1) == 1, dly, 0.0)
            fire_all = dly_eff <= 0.0
            best = jnp.full((cr, LANE), jnp.inf, jnp.float32)
            for j in range(k_max):
                disp_t = t + dly_eff * coeff[j]
                alive_eff = alive[j] | last[j]
                fired = jnp.maximum(cur[j], disp_t) + svc[j]
                made = mask[j] if j == 0 else (
                    mask[j] & (fire_all | (best > disp_t)))
                best = jnp.minimum(best, jnp.where(made & alive_eff,
                                                   fired, jnp.inf))
                new_val[j] = jnp.where(
                    is_timed, jnp.where(made & alive_eff, fired, cur[j]),
                    new_val[j])
        for j in range(k_max):
            val_cancel = jnp.where(live[j], jnp.maximum(cur[j], t_win),
                                   cur[j])
            val_idle = jnp.where(disp_alive[j], finish[j], cur[j])
            new_val[j] = jnp.where(is_cancel, val_cancel,
                                   jnp.where(is_idle, val_idle, new_val[j]))
        # scatter in copy order == XLA's last-wins .at[srv].set
        free = list(free)
        for j in range(k_max):
            free = [jnp.where(e, new_val[j], f) for e, f in zip(eq[j], free)]
        resp = t_win - t + ovh
        if has_timed:
            resp = jnp.where(is_timed, best - t + ovh, resp)
        resp_idle = (functools.reduce(jnp.minimum, [
            jnp.where(da, f, jnp.inf) for da, f in zip(disp_alive, finish)])
            - t + ovh)
        resp = jnp.where(is_idle, resp_idle, resp)
        w_live = w * jnp.isfinite(resp).astype(jnp.float32)
        ssum, comp = kahan_fold(ssum, comp, resp, w_live, barrier=interpret)
        cnt = cnt + w_live
        if rw_out:
            rw_out[0][i, 0, pl.ds(0, cr), :] = resp
            rw_out[0][i, 0, pl.ds(cr, cr), :] = w_live
        return tuple(free), ssum, comp, cnt

    carry = (tuple(rows(free_out, n_servers)), *rows(acc_out, 3))
    unroll = _UNROLL if block_t % _UNROLL == 0 else 1

    def steps(g, carry):
        for u in range(unroll):
            carry = step(g * unroll + u, carry)
        return carry

    free, ssum, comp, cnt = jax.lax.fori_loop(0, block_t // unroll, steps,
                                              carry)
    for n, f in enumerate(free):
        free_out[0, pl.ds(n * cr, cr), :] = f
    for r, v in enumerate((ssum, comp, cnt)):
        acc_out[0, pl.ds(r * cr, cr), :] = v


@functools.partial(jax.jit, static_argnames=(
    "k_max", "block_t", "need_hist", "has_shared", "has_timed",
    "interpret"))
def cell_update_tc(code: jax.Array, prm: jax.Array, free: jax.Array,
                   acc: jax.Array, x: jax.Array, *, k_max: int,
                   block_t: int, need_hist: bool, has_shared: bool = False,
                   has_timed: bool = False, interpret: bool = False):
    """One chunk of the cell update on the lane layout (module note):
    ``code`` (T,) int32, ``prm`` (n_cb, 9 cr, 128), ``free`` (n_cb, N cr,
    128), ``acc`` (n_cb, 3 cr, 128), ``x`` (T, n_cb, rows cr, 128) ->
    updated ``free``, ``acc`` and, with ``need_hist``, ``rw`` (T, n_cb,
    2 cr, 128). ``free`` is NOT yet rebased (the caller rebases, same as
    the ref). Requires ``T % block_t == 0``; ``ops.cell_update`` lays the
    operands out and validates."""
    n_cb, p_rows, _ = prm.shape
    cr = p_rows // len(PARAMS)
    n_servers = free.shape[1] // cr
    t_total, _, x_rows, _ = x.shape
    n_svc = x_rows // cr - 1 - k_max
    assert t_total % block_t == 0, (t_total, block_t)

    kernel = functools.partial(
        _cell_kernel, n_servers=n_servers, k_max=k_max, n_svc=n_svc, cr=cr,
        block_t=block_t, has_shared=has_shared, has_timed=has_timed,
        interpret=interpret)

    def cells(rows):
        return pl.BlockSpec((1, rows * cr, LANE), lambda cb, it: (cb, 0, 0))

    def steps(rows):
        return pl.BlockSpec((block_t, 1, rows * cr, LANE),
                            lambda cb, it: (it, cb, 0, 0))

    out_specs = [cells(n_servers), cells(3)]
    out_shape = [jax.ShapeDtypeStruct(free.shape, jnp.float32),
                 jax.ShapeDtypeStruct(acc.shape, jnp.float32)]
    if need_hist:
        out_specs.append(steps(2))
        out_shape.append(jax.ShapeDtypeStruct((t_total, n_cb, 2 * cr, LANE),
                                              jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(n_cb, t_total // block_t),
        in_specs=[pl.BlockSpec((1, 1, block_t), lambda cb, it: (it, 0, 0),
                               memory_space=pltpu.SMEM),
                  cells(len(PARAMS)), cells(n_servers), cells(3),
                  steps(1 + k_max + n_svc)],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret)(code.reshape(-1, 1, block_t), prm, free, acc,
                             x)
