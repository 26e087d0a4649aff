"""Reference implementation of the fused cell update.

``step_cell`` is THE single-arrival physics of the replication DES —
free-time gather, policy/model selects, occupancy scatter, response
min — shared by every execution path (``queueing.simulate*``, the
sweep engine's scan body below, and the Pallas kernel, which mirrors
it op-for-op). ``cell_update_ref`` is the ``lax.scan`` chunk body the
kernel must match BIT FOR BIT; it is also the dispatch fallback
(``use_kernel="off"``), so CPU/CI runs and TPU kernel runs are anchored
to the same bits.

Bit-exactness ground rules shared with ``kernel.py``:

  * Every floating-point op sequence here is elementwise or a
    min/max reduction over the tiny copy axis — no order-sensitive
    float reductions — so the kernel can re-tile shapes freely without
    changing bits.
  * The Kahan update is GATED on the warmup weight via selects: a
    zero-weight step leaves (ssum, comp) bitwise untouched (not just
    algebraically — the ungated update would fold the compensation
    term into the sum). That makes the summaries invariant to trailing
    zero-weight padding, which the kernel path relies on (it always
    pads chunks to a block multiple) and which keeps padded and
    unpadded layouts bit-identical.
  * ``optimization_barrier`` hides the compensated sum from XLA's
    algebraic simplifier exactly as in the pre-kernel engine (see the
    inline comment).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.scenario import Policy, ServiceModel
from repro.kernels.hist_sketch import ops as hist_ops

Array = jax.Array

# TIMEOUT_RETRY exponential backoff: attempt j dispatches at
# t + delay * sum_{i<j} min(2**i, _BACKOFF_CAP). The cap bounds the
# inter-attempt wait at 8 deadlines (offsets 0, 1, 3, 7, 15, 23, ...).
_BACKOFF_CAP = 8.0


def retry_offsets(k_max: int) -> list[float]:
    """Static backoff-offset coefficients per attempt (exact small
    floats, shared by the scan body, the Pallas kernel and
    ``analytic.retry_mean_light`` so all three agree bit-for-bit)."""
    c, out = 0.0, []
    for j in range(k_max):
        out.append(c)
        c += min(2.0 ** j, _BACKOFF_CAP)
    return out


def step_cell(free: Array, t: Array, srv: Array, svc: Array,
              svc_shared: Array, degr_u: Array, mask: Array, overhead: Array,
              policy: Array, model: Array, mix: Array, p_slow: Array,
              slow_factor: Array, p_fail: Array, delay: Array,
              valid: Array = True, *,
              has_timed: bool = False) -> tuple[Array, Array]:
    """One arrival at one (seed, load, variant) grid cell. free (N,), t /
    svc_shared / overhead / policy / model / mix / p_slow / slow_factor /
    p_fail / delay / valid scalars, srv/svc/degr_u/mask (k_max,) ->
    (new free, response). ``valid`` is False only on chunk-padding
    steps: it zeroes the effective delay there, forcing the timed
    policies' ``fire_all`` arm. A deferred dispatch at
    ``t + delay * coeff`` could otherwise let a zero-service padding
    step bump a server's free time past the chunk-end arrival time,
    where a next-chunk arrival WOULD observe it — every other policy's
    padding write is bounded by ``max(cur, t_chunk_end)``, which later
    arrivals cannot see, and with ``delay = 0`` the timed write is too.
    On real steps ``jnp.where(True, delay, 0)`` is bitwise ``delay``.

    ``policy`` / ``model`` are the cell's ``scenario.Policy`` /
    ``scenario.ServiceModel`` codes; every variant's update is computed
    and the codes select one (mixed grids share this single trace). The
    ``Policy.REPLICATE_ALL`` + ``ServiceModel.IID`` path is the paper's
    model, op-for-op identical to the pre-scenario engine (the bit-
    identity anchor of ``Scenario.paper_default``).

    Degradation-model CRN design note (the PR-7 contract): ``degr_u``
    is one uniform per copy drawn from a DEDICATED ``fold_in`` index
    (``queueing._DEGRADE_FOLD``), sampled only when a grid contains a
    degraded variant — the service/arrival key streams are untouched,
    so healthy cells keep their pre-degradation bits exactly. One draw
    drives both events on disjoint intervals (``u < p_fail`` blackhole,
    ``u >= 1 - p_slow`` straggler; healthy cells pass zeros, making
    both selects inert). A blackholed copy is lost in transit: it never
    occupies its server (its free-time entry keeps the old value, like
    a masked copy) and never responds; a request with no surviving copy
    yields ``resp = inf``, which the caller excludes from the mean /
    histogram and from the per-cell completed count.

    Timed policies (``TIMEOUT_RETRY`` / ``HEDGE_AFTER_DELAY``) share a
    sequential dispatch loop over the copy budget: copy ``j`` fires at
    ``t + delay * coeff_j`` (backoff offsets for retry, ``j * delay``
    for hedging) ONLY if no earlier surviving copy has finished by its
    dispatch time. ``delay <= 0`` forces every copy to fire — which is
    what makes ``HEDGE_AFTER_DELAY(delay=0)`` bit-identical to
    ``REPLICATE_ALL`` (same dispatch set, same ``max(cur, t) + svc``
    finishes, and min-folds are exact so the sequential best equals the
    reduction ``t_win`` bit-for-bit). TIMEOUT_RETRY's LAST in-budget
    attempt ignores its blackhole draw (out-of-band escalation), so
    retry cells always complete.

    ``has_timed`` is STATIC: the timed-policy block (and its extra
    select in the policy chains) is compiled only when the grid
    actually contains a TIMED_POLICIES variant. This is a bit-identity
    requirement, not an optimisation — merely having the extra select
    live in the traced graph shifts XLA's fusion choices around the
    free-time scatter, which was observed to move a saturated cell's
    sample path by 1 ULP. Gating it out keeps every non-timed grid on
    the exact pre-timed compiled program; timed grids are verified
    scan-vs-kernel bit-identical separately (tests/test_faults.py).
    """
    k_max = srv.shape[0]
    iota = jnp.arange(k_max)
    cur = free[srv]
    # SERVER_DEPENDENT (Shah et al.): blend the shared request component
    # into every copy. mix=0 (and the IID select arm) is bit-exact svc.
    svc = jnp.where(model == int(ServiceModel.SERVER_DEPENDENT),
                    mix * svc_shared + (1.0 - mix) * svc, svc)
    # Degradation: straggler inflation on the served time, blackhole
    # aliveness. Healthy cells (p_slow = p_fail = 0, degr_u = 0) keep
    # svc and alive = True through both selects — bitwise inert.
    svc = jnp.where(degr_u >= 1.0 - p_slow, svc * slow_factor, svc)
    alive = degr_u >= p_fail
    start = jnp.maximum(cur, t)
    finish = start + svc
    t_win = jnp.min(jnp.where(mask & alive, finish, jnp.inf))
    # REPLICATE_TO_IDLE dispatches the primary always, extras only to
    # servers idle at the arrival instant.
    dispatch = mask & ((iota == 0) | (cur <= t))
    # Per-policy server-occupancy updates (masked copies rewrite their own
    # old value — a no-op; srv entries are distinct by construction):
    #   REPLICATE_ALL      every surviving copy runs to completion.
    #   CANCEL_ON_COMPLETE losers vacate at the winner's finish: a loser
    #                      in service frees at t_win, a queued loser
    #                      (cur >= t_win) never starts — max(cur, t_win)
    #                      covers both (and equals finish for the winner).
    #                      t_win = inf only when NO copy survives, and
    #                      then no copy selects it.
    #   REPLICATE_TO_IDLE  only dispatched surviving copies occupy.
    #   TIMED (retry/hedge) only fired surviving copies occupy.
    val_all = jnp.where(mask & alive, finish, cur)
    val_cancel = jnp.where(mask & alive, jnp.maximum(cur, t_win), cur)
    val_idle = jnp.where(dispatch & alive, finish, cur)
    if has_timed:
        # Timed policies: sequential dispatch over the copy budget.
        delay = jnp.where(valid, delay, 0.0)  # padding: see docstring
        is_retry = policy == int(Policy.TIMEOUT_RETRY)
        is_timed = is_retry | (policy == int(Policy.HEDGE_AFTER_DELAY))
        kc = jnp.sum(mask)  # prefix mask -> attempt budget
        coeff = jnp.where(is_retry,
                          jnp.asarray(retry_offsets(k_max), jnp.float32),
                          iota.astype(jnp.float32))
        disp_t = t + delay * coeff
        alive_eff = alive | (is_retry & (iota == kc - 1))
        fired_finish = jnp.maximum(cur, disp_t) + svc
        fire_all = delay <= 0.0
        best = jnp.asarray(jnp.inf, fired_finish.dtype)
        made_cols = []
        for j in range(k_max):
            made_j = mask[j] if j == 0 else (
                mask[j] & (fire_all | (best > disp_t[j])))
            best = jnp.minimum(best, jnp.where(made_j & alive_eff[j],
                                               fired_finish[j], jnp.inf))
            made_cols.append(made_j)
        made = jnp.stack(made_cols)
        val_timed = jnp.where(made & alive_eff, fired_finish, cur)
        base_val = jnp.where(is_timed, val_timed, val_all)
    else:
        base_val = val_all
    new_val = jnp.where(
        policy == int(Policy.CANCEL_ON_COMPLETE), val_cancel,
        jnp.where(policy == int(Policy.REPLICATE_TO_IDLE), val_idle,
                  base_val))
    free = free.at[srv].set(new_val)
    resp_win = t_win - t + overhead
    resp_idle = (jnp.min(jnp.where(dispatch & alive, finish, jnp.inf))
                 - t + overhead)
    if has_timed:
        base_resp = jnp.where(is_timed, best - t + overhead, resp_win)
    else:
        base_resp = resp_win
    resp = jnp.where(policy == int(Policy.REPLICATE_TO_IDLE), resp_idle,
                     base_resp)
    return free, resp


def kahan_fold(ssum: Array, comp: Array, resp: Array, w: Array, *,
               barrier: bool = True) -> tuple[Array, Array]:
    """One gated Kahan step, shared verbatim by the scan body and the
    Pallas kernel (same ops => same bits in both). ``barrier=False`` is
    for the compiled Pallas kernel only: Mosaic has no lowering for
    ``optimization_barrier`` and no algebraic simplifier to guard
    against.

    Kahan-compensated sum: sequential f32 accumulation over ~1e5+
    terms would otherwise cost ~1e-4 relative error on the mean,
    which is the signal threshold bisection keys on. Three guards
    keep the update's rounding EXACTLY the same in every compilation
    (the sharded-vs-unsharded and kernel-vs-scan bit-identity
    contracts):

      * the 0/1 warmup weight gates the WHOLE update via selects (a
        ``resp * w - comp`` multiply-subtract invites FMA
        contraction, and an ungated ``y = 0 - comp`` step would fold
        the compensation into the sum — making the bits depend on
        how much zero-weight padding trails the chunk);
      * an ``optimization_barrier`` hides ``tot`` from XLA's
        algebraic simplifier, which would otherwise rewrite
        ``(tot - ssum) - y`` — compensation terms it sees as
        algebraically zero — depending on the surrounding fusion
        context.
    """
    y = resp - comp
    tot = ssum + y
    tot_b, y_b = (jax.lax.optimization_barrier((tot, y)) if barrier
                  else (tot, y))
    comp_new = (tot_b - ssum) - y_b
    live = w > 0
    return jnp.where(live, tot_b, ssum), jnp.where(live, comp_new, comp)


def cell_update_ref(free: Array, ssum: Array, comp: Array, cnt: Array,
                    hist: Array, cum: Array, warm: Array, valid: Array,
                    servers: Array, services: Array, seed_idx: Array,
                    rates: Array, k_mask: Array, ovh: Array,
                    policy_code: Array, model_code: Array, mix: Array,
                    p_slow: Array, slow_factor: Array, p_fail: Array,
                    delay: Array, svc_idx: Array = None, *,
                    n_servers: int | None = None,
                    n_bins: int, block: int, has_shared: bool = False,
                    has_timed: bool = False, has_dists: bool = False
                    ) -> tuple[Array, Array, Array, Array, Array]:
    """Scan-body reference for one chunk on the flat cell axis.

    ``cum`` (S,T) are cumulative arrival offsets from the chunk start
    (already masked for padding), ``warm`` (T,) the 0/1 post-warmup
    weights, ``valid`` (T,) the 0/1 real-step flags (0 only on padding
    steps — distinct from ``warm``, which is also 0 on real pre-warmup
    arrivals; see ``step_cell`` on why timed policies need it),
    ``servers`` (S,T,k_max) / ``services`` (S,T,n_svc) the
    sampled inputs (padding steps zeroed); the remaining args are the
    per-cell carry and plan parameters of
    ``queueing._sweep_chunk_cells``, which documents them. The
    ``services`` column layout is ``[k_max per-copy draws][shared
    component if has_shared][k_max degradation uniforms if present]`` —
    ``has_shared`` is a static flag (the column count alone is
    ambiguous at k_max=1) and the degradation columns' presence is
    derived from what remains. ``cnt`` accumulates the per-cell count
    of COMPLETED post-warmup responses: incomplete requests (every
    dispatched copy blackholed -> ``resp = inf``) are excluded from the
    Kahan mean, the histogram, and the count by zeroing their warmup
    weight — for healthy cells the weight is untouched (``w * 1.0``) so
    summaries keep their pre-degradation bits. Returns the updated
    carry with ``free`` NOT yet rebased (the caller rebases).
    ``n_servers`` is accepted (dispatch-signature parity with
    ``ops.cell_update``) but implied by ``free``. ``has_shared`` /
    ``has_timed`` are the static layout / compiled-program flags from
    the variant list (see ``step_cell`` on why ``has_timed`` gates the
    timed block at trace time).

    ``has_dists`` (static) routes the per-step SERVICE gather through
    ``svc_idx`` (C,) instead of ``seed_idx`` — heterogeneous grids stack
    one service table per dist-union member along the seed axis and
    ``svc_idx = dist_id * n_seeds + seed_idx`` picks each cell's table
    row; arrivals/servers/time stay ``seed_idx``-keyed (CRN across
    systems). ``has_dists=False`` never touches ``svc_idx``, keeping the
    homogeneous trace unchanged.
    """
    del n_servers
    k_max = k_mask.shape[1]
    n_base = k_max + (1 if has_shared else 0)
    has_degr = services.shape[-1] > n_base
    need_hist = hist.size > 0
    T = cum.shape[1]
    if need_hist:
        assert T % block == 0, (T, block)

    cell_c = jax.vmap(partial(step_cell, has_timed=has_timed),
                      in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
                               0, 0, 0, 0, 0, None))

    def step(carry, inp):
        free, ssum, comp, cnt = carry
        c, w, v, srv, svc = inp                # (S,), (), (), (S,k), (S,n_svc)
        t = c[seed_idx] / rates                       # (C,)
        svc_c = svc[svc_idx if has_dists else seed_idx]  # (C, n_svc)
        shared_c = svc_c[:, k_max] if has_shared else svc_c[:, 0]
        degr_c = (svc_c[:, n_base:n_base + k_max] if has_degr
                  else jnp.zeros_like(svc_c[:, :k_max]))
        free, resp = cell_c(free, t, srv[seed_idx], svc_c[:, :k_max],
                            shared_c, degr_c, k_mask, ovh, policy_code,
                            model_code, mix, p_slow, slow_factor, p_fail,
                            delay, v > 0)
        w_live = w * jnp.isfinite(resp).astype(jnp.float32)   # (C,)
        ssum, comp = kahan_fold(ssum, comp, resp, w_live)
        cnt = cnt + w_live
        return (free, ssum, comp, cnt), ((resp, w_live) if need_hist
                                         else None)

    xs = (cum.T, warm, valid, jnp.moveaxis(servers, 1, 0),
          jnp.moveaxis(services, 1, 0))
    if need_hist:
        xs = jax.tree.map(
            lambda x: x.reshape((T // block, block) + x.shape[1:]), xs)

        def outer(carry, xs_blk):
            free, ssum, comp, cnt, hist = carry
            (free, ssum, comp, cnt), (resp, w_live) = jax.lax.scan(
                step, (free, ssum, comp, cnt), xs_blk)
            idx = hist_ops.bin_indices(resp, w_live, n_bins=n_bins)
            hist = hist + hist_ops.hist_accum(idx, n_bins=n_bins,
                                              block_t=block)
            return (free, ssum, comp, cnt, hist), None

        (free, ssum, comp, cnt, hist), _ = jax.lax.scan(
            outer, (free, ssum, comp, cnt, hist), xs)
    else:
        (free, ssum, comp, cnt), _ = jax.lax.scan(
            step, (free, ssum, comp, cnt), xs)
    return free, ssum, comp, cnt, hist
