"""Fused Pallas cell-update kernel: bit-identity against the scan body.

The contract (``repro.kernels.cell_update``): for the same inputs the
kernel path through the Pallas interpreter (``kernel="interpret"``,
the same jnp ops as the compiled kernel; ``kernel="on"`` compiles it
and needs a TPU) and the ``lax.scan`` reference (``kernel="off"``)
agree BIT FOR BIT — every policy x service-model
code, mixed grids, pad cells, chunked and unchunked layouts, histogram
on and off. On CPU the kernel runs in interpret mode, which is exactly
why these tests can pin the contract in every tier-1 run; the sharded
job in ``test_sweep_shard.py`` pins it under ``shard_map`` at 8
devices.
"""
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import cellplan, distributions as dists, queueing, threshold
from repro.core import scenario as scenario_mod
from repro.core.scenario import (CANCEL_ON_COMPLETE, IID, REPLICATE_ALL,
                                 REPLICATE_TO_IDLE, SERVER_DEPENDENT,
                                 Degradation, Policy, Scenario, combine,
                                 variant_codes)
from repro.kernels.cell_update import ops as cell_ops

CFG = queueing.SimConfig(n_servers=10, n_arrivals=3_000)
RHOS = jnp.asarray([0.1, 0.35])


def _assert_bits(a, b, fields=("mean", "p50", "p99")):
    assert a["count"] == b["count"]
    for f in fields:
        assert jnp.array_equal(a[f], b[f]), f


def _both(key, scn, rhos, cfg, **kw):
    off = queueing.run(key, scn, rhos, cfg, kernel="off", **kw)
    on = queueing.run(key, scn, rhos, cfg, kernel="interpret", **kw)
    return off, on


class TestKernelModeResolution:
    def test_auto_off_tpu_is_off(self):
        # this suite runs on CPU: auto must stay on the scan body, and
        # "on" must refuse rather than quietly interpret
        assert cell_ops.resolve_kernel_mode("auto") in ("off", "on")
        if jax.devices()[0].platform != "tpu":
            assert cell_ops.resolve_kernel_mode("auto") == "off"
            for on in ("on", True):
                with pytest.raises(RuntimeError, match="TPU"):
                    cell_ops.resolve_kernel_mode(on)
            with pytest.raises(RuntimeError, match="TPU"):
                queueing.run(jax.random.PRNGKey(0),
                             Scenario.paper_default(dists.exponential()),
                             RHOS, CFG, kernel="on")
        assert cell_ops.resolve_kernel_mode("off") == "off"
        assert cell_ops.resolve_kernel_mode("interpret") == "interpret"
        assert cell_ops.resolve_kernel_mode(None) == "off"
        assert cell_ops.resolve_kernel_mode(False) == "off"
        # only "auto" may pick the reference for an unaligned sketch
        assert cell_ops.resolve_kernel_mode("auto", n_bins=100) == "off"
        with pytest.raises(ValueError, match="n_bins"):
            cell_ops.resolve_kernel_mode("interpret", n_bins=100)

    def test_rejects_unknown_mode(self):
        with pytest.raises(ValueError, match="kernel"):
            cell_ops.resolve_kernel_mode("sometimes")
        with pytest.raises(ValueError, match="kernel"):
            queueing.run(jax.random.PRNGKey(0),
                         Scenario.paper_default(dists.exponential()), RHOS,
                         CFG, kernel="sometimes")


class TestKernelParity:
    @pytest.mark.parametrize("policy", [REPLICATE_ALL, CANCEL_ON_COMPLETE,
                                        REPLICATE_TO_IDLE])
    @pytest.mark.parametrize("model", [IID, SERVER_DEPENDENT])
    def test_every_policy_model_code(self, policy, model):
        key = jax.random.PRNGKey(0)
        scn = Scenario(dists=dists.exponential(), policy=policy,
                       service_model=model,
                       mix=0.6 if model is SERVER_DEPENDENT else 0.0,
                       ks=(1, 2))
        off, on = _both(key, scn, RHOS, CFG, n_seeds=1, chunk_size=1_300)
        _assert_bits(off, on)

    def test_mixed_grid_chunked_ragged(self):
        # all policies and both models in ONE plan, ragged chunks
        key = jax.random.PRNGKey(1)
        d = dists.exponential()
        scns = (Scenario.paper_default(d, ks=(1, 2)),
                Scenario(dists=d, policy=CANCEL_ON_COMPLETE, ks=(2,)),
                Scenario(dists=d, policy=REPLICATE_TO_IDLE, ks=(2,)),
                Scenario(dists=d, service_model=SERVER_DEPENDENT, mix=0.7,
                         ks=(2,)))
        off, on = _both(key, scns, RHOS, CFG, n_seeds=2, chunk_size=1_300)
        _assert_bits(off, on)

    def test_unchunked_with_overhead(self):
        key = jax.random.PRNGKey(2)
        cfg = queueing.SimConfig(n_servers=7, n_arrivals=2_500,
                                 client_overhead=0.2)
        scn = Scenario.paper_default(dists.pareto(2.5), ks=(1, 3),
                                     client_overhead=0.2)
        off, on = _both(key, scn, RHOS, cfg, n_seeds=2)
        _assert_bits(off, on)

    def test_hist_off_kernel_padding_is_bit_noop(self):
        # percentiles=(): the scan body runs UNPADDED, the kernel pads
        # the chunk to a block multiple — identical mean bits proves
        # zero-weight padding steps are bitwise no-ops on the Kahan state
        key = jax.random.PRNGKey(3)
        scn = Scenario.paper_default(dists.exponential(), ks=(1, 2))
        off, on = _both(key, scn, RHOS, CFG, n_seeds=1, percentiles=(),
                        chunk_size=900)
        _assert_bits(off, on, fields=("mean",))

    def test_interpret_equals_on(self):
        # the kernel-path mode this host can run ("on" on a TPU, the
        # interpreter elsewhere) against an explicit interpreter run
        key = jax.random.PRNGKey(4)
        scn = Scenario.paper_default(dists.weibull(0.7), ks=(1, 2))
        on = queueing.run(key, scn, RHOS, CFG, n_seeds=1,
                          kernel=cell_ops.kernel_path_mode())
        interp = queueing.run(key, scn, RHOS, CFG, n_seeds=1,
                              kernel="interpret")
        _assert_bits(on, interp)

    def test_threshold_bisect_kernel_identical(self):
        key = jax.random.PRNGKey(5)
        kw = dict(iters=3, n_seeds=1, chunk_size=1_500)
        t_off = threshold.threshold_bisect(key, dists.exponential(), CFG,
                                           kernel="off", **kw)
        t_on = threshold.threshold_bisect(key, dists.exponential(), CFG,
                                          kernel="interpret", **kw)
        assert t_off == t_on


def _chunk_carry_parity(key, scns, rhos, cfg, *, n_seeds, pad_to=1):
    """Drive the chunk body directly, scan against interpreted kernel,
    on the plan ``queueing.run`` would build for ``scns``: EVERY carry
    component — free grid, Kahan state, counts, histogram rows, pad
    rows included — must match bit for bit."""
    dist_list, warmup_frac, variants = combine(scns)
    pol, mdl = variant_codes(variants)
    has_dists = scenario_mod.any_dist_ids(variants)
    with_shared = scenario_mod.any_server_dependent(variants)
    with_degr = scenario_mod.any_degraded(variants)
    plan = cellplan.make_cell_plan(
        n_seeds, rhos.shape[0], len(variants), pad_to=pad_to, policies=pol,
        models=mdl, dist_ids=scenario_mod.variant_dist_ids(variants))
    assert plan.n_padded > plan.n_cells or pad_to == 1
    params = queueing._plan_cell_params(plan, rhos, cfg, variants)
    k_max = max(v.k for v in variants)
    sample = (queueing._dist_table_sampler if has_dists
              else queueing._sweep_sampler)
    sampler = sample(key, dist_list if has_dists else dist_list[0], cfg,
                     k_max, n_seeds, None, with_shared=with_shared,
                     with_degr=with_degr)
    pad = (-cfg.n_arrivals) % 512
    inputs = queueing._pad_chunk_inputs(*sampler(0, cfg.n_arrivals), pad)
    state = queueing._init_cell_state(plan, cfg, queueing.DEFAULT_BINS,
                                      True)
    (rates_c, k_mask_c, ovh_c, mix_c, pslow_c, sfac_c, pfail_c,
     delay_c) = params
    args = (*state, *inputs, jnp.asarray(0), jnp.asarray(cfg.n_arrivals),
            jnp.asarray(int(cfg.n_arrivals * warmup_frac)), plan.seed_idx,
            rates_c, k_mask_c, ovh_c, plan.policy_code, plan.model_code,
            mix_c, pslow_c, sfac_c, pfail_c, delay_c,
            plan.dist_id * n_seeds + plan.seed_idx if has_dists else None)
    kw = dict(n_servers=cfg.n_servers, n_bins=queueing.DEFAULT_BINS,
              block=512, has_shared=with_shared,
              has_timed=scenario_mod.any_timed(variants),
              has_dists=has_dists)
    out_off = queueing._sweep_chunk_cells(*args, use_kernel="off", **kw)
    out_on = queueing._sweep_chunk_cells(*args, use_kernel="interpret",
                                         **kw)
    for name, a, b in zip(("free", "ssum", "comp", "cnt", "hist"),
                          out_off, out_on):
        assert a.shape == b.shape, name
        assert jnp.array_equal(a, b), name
    return plan


def _timed_degraded(d):
    # no server-dependent cell: XLA's CPU backend contracts that blend
    # into a fused multiply-add differently in the two programs (the
    # kernel module note)
    degr = Degradation(p_slow=0.1, slow_factor=3.0, p_fail=0.05)
    return (Scenario(dists=d, policy=Policy.TIMEOUT_RETRY, delay=1.5,
                     ks=(2, 3), degradation=degr),
            Scenario(dists=d, policy=Policy.HEDGE_AFTER_DELAY, delay=0.7,
                     ks=(2,), degradation=degr),
            Scenario(dists=d, policy=CANCEL_ON_COMPLETE, ks=(2,),
                     degradation=degr))


def _two_systems(d):
    return (Scenario.paper_default(d, ks=(1, 2)),
            Scenario(dists=dists.two_point(0.9), policy=REPLICATE_TO_IDLE,
                     ks=(2,)))


class TestPadCellIsolation:
    def test_padded_plan_full_carry_bit_identity(self):
        # a plan with pad cells (n_cells=6 padded to 8): pad lanes in
        # one sublane row of the kernel's lane layout
        cfg = queueing.SimConfig(n_servers=7, n_arrivals=2_500)
        plan = _chunk_carry_parity(
            jax.random.PRNGKey(6), Scenario.paper_default(
                dists.pareto(2.5), ks=(1, 2)),
            jnp.asarray([0.1, 0.25, 0.4]), cfg, n_seeds=1, pad_to=4)
        assert (plan.n_cells, plan.n_padded) == (6, 8)

    @pytest.mark.parametrize("n_seeds,n_loads,n_arrivals,scns,cells", [
        # two sublane rows of lanes
        pytest.param(5, 13, 700, Scenario.paper_default(
            dists.pareto(2.5), ks=(1, 2)), 130, id="c130"),
        # two cell blocks of eight rows, the second mostly padding
        pytest.param(5, 103, 300, Scenario.paper_default(
            dists.exponential(), ks=(1, 2)), 1030, id="c1030"),
        # timed policies, degradation columns and the shared draw
        pytest.param(2, 4, 900, _timed_degraded(dists.exponential()), 32,
                     id="timed_degraded"),
        # heterogeneous grid: the service gather routed by dist_id
        pytest.param(2, 3, 900, _two_systems(dists.exponential()), 18,
                     id="has_dists"),
    ])
    def test_lane_block_full_carry_bit_identity(self, n_seeds, n_loads,
                                                n_arrivals, scns, cells):
        cfg = queueing.SimConfig(n_servers=7, n_arrivals=n_arrivals)
        rhos = jnp.linspace(0.05, 0.45, n_loads)
        plan = _chunk_carry_parity(jax.random.PRNGKey(11), scns, rhos, cfg,
                                   n_seeds=n_seeds)
        assert plan.n_cells == cells


class TestDeprecatedShims:
    """The legacy paper-default shims must warn AND stay bit-identical
    to ``run`` through the kernel path."""

    def test_sweep_warns_and_matches_run(self):
        key = jax.random.PRNGKey(7)
        with pytest.warns(DeprecationWarning, match="queueing.sweep"):
            shim = queueing.sweep(key, dists.exponential(), RHOS, CFG,
                                  ks=(1, 2), n_seeds=1,
                                  kernel="interpret")
        scn = Scenario.paper_default(dists.exponential(), ks=(1, 2))
        direct = queueing.run(key, scn, RHOS, CFG, n_seeds=1,
                              kernel="interpret")
        _assert_bits(shim, direct)
        # and the kernel path equals the scan path through the shim too
        with pytest.warns(DeprecationWarning):
            off = queueing.sweep(key, dists.exponential(), RHOS, CFG,
                                 ks=(1, 2), n_seeds=1, kernel="off")
        _assert_bits(shim, off)

    def test_sweep_dists_warns_and_matches_run(self):
        key = jax.random.PRNGKey(8)
        ds = (dists.exponential(), dists.two_point(0.9))
        with pytest.warns(DeprecationWarning, match="sweep_dists"):
            shim = queueing.sweep_dists(key, ds, RHOS, CFG, ks=(1, 2),
                                        n_seeds=1, percentiles=(),
                                        kernel="interpret")
        scn = Scenario.paper_default(ds, ks=(1, 2))
        direct = queueing.run(key, scn, RHOS, CFG, n_seeds=1,
                              percentiles=(), kernel="interpret")
        assert jnp.array_equal(shim["mean"], direct["mean"])

    def test_replication_gain_warns_and_matches_scan(self):
        key = jax.random.PRNGKey(9)
        with pytest.warns(DeprecationWarning, match="replication_gain"):
            g_on = queueing.replication_gain(key, dists.exponential(),
                                             RHOS, CFG, n_seeds=1,
                                             kernel="interpret")
        with pytest.warns(DeprecationWarning):
            g_off = queueing.replication_gain(key, dists.exponential(),
                                              RHOS, CFG, n_seeds=1,
                                              kernel="off")
        assert jnp.array_equal(g_on, g_off)

    def test_mean_response_does_not_warn(self):
        # not a deprecated shim: must stay warning-free
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            queueing.mean_response(jax.random.PRNGKey(10),
                                   dists.exponential(), RHOS, CFG, k=1)
