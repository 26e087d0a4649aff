"""Pallas hist_sketch kernel: interpret-mode parity vs the jnp reference
(bit-exact bin counts) and sketch-quantile accuracy vs exact quantiles."""
import math

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.hist_sketch import kernel, ops, ref


def _rand_idx(seed: int, t: int, c: int, n_bins: int) -> jax.Array:
    """Random indices including skip markers (-1) and both edge bins."""
    key = jax.random.PRNGKey(seed)
    idx = jax.random.randint(key, (t, c), -1, n_bins)
    # force edge coverage
    idx = idx.at[0, 0].set(0).at[-1, -1].set(n_bins - 1)
    return idx


class TestKernelParity:
    @pytest.mark.parametrize("t,c,n_bins", [
        (1024, 5, 2048),
        (512, 1, 128),
        (768, 16, 256),
    ])
    def test_bit_exact_vs_ref(self, t, c, n_bins):
        idx = _rand_idx(t + c, t, c, n_bins)
        out = ops.hist_accum(idx, n_bins=n_bins, interpret=True)
        expect = ref.hist_accum_ref(idx, n_bins=n_bins)
        assert out.shape == (c, n_bins)
        assert jnp.array_equal(out, expect)

    def test_non_multiple_block_t_padded(self):
        # T = 777 is not a multiple of any block size; ops pads with skips
        idx = _rand_idx(7, 777, 3, 256)
        out = ops.hist_accum(idx, n_bins=256, interpret=True)
        assert jnp.array_equal(out, ref.hist_accum_ref(idx, n_bins=256))

    def test_skip_entries_add_nothing(self):
        idx = jnp.full((512, 4), -1, jnp.int32)
        out = ops.hist_accum(idx, n_bins=128, interpret=True)
        assert float(out.sum()) == 0.0

    def test_total_mass_equals_valid_entries(self):
        idx = _rand_idx(3, 640, 6, 512)
        out = ops.hist_accum(idx, n_bins=512, interpret=True)
        assert float(out.sum()) == float((idx >= 0).sum())

    def test_kernel_direct_matches_ref(self):
        # exercise the jitted kernel wrapper without the ops padding layer
        idx = _rand_idx(11, 1024, 2, 1024)
        out = kernel.hist_accum_tc(idx, n_bins=1024, block_t=256,
                                   interpret=True)
        assert jnp.array_equal(out, ref.hist_accum_ref(idx, n_bins=1024))

    def test_non_lane_divisible_bins_falls_back_to_ref(self):
        # n_bins not divisible by the 128 lane width cannot use the
        # kernel: the automatic choice takes the reference, an explicit
        # kernel request raises instead of quietly doing the same
        idx = _rand_idx(5, 300, 2, 100)
        out = ops.hist_accum(idx, n_bins=100)
        assert jnp.array_equal(out, ref.hist_accum_ref(idx, n_bins=100))
        for interpret in (True, False):
            with pytest.raises(ValueError, match="n_bins"):
                ops.hist_accum(idx, n_bins=100, interpret=interpret)

    def test_compiled_kernel_off_tpu_raises(self):
        if ops.on_tpu():
            pytest.skip("checks the off-TPU refusal")
        idx = _rand_idx(6, 256, 2, 128)
        with pytest.raises(RuntimeError, match="TPU"):
            ops.hist_accum(idx, n_bins=128, interpret=False)

    def test_warm_weights_encoded_as_skips(self):
        vals = jax.random.exponential(jax.random.PRNGKey(0), (600, 3)) + 1e-3
        warm = (jnp.arange(600) >= 100).astype(jnp.float32)
        h = ops.hist_sketch(vals, warm[:, None], n_bins=256, interpret=True)
        assert float(h.sum()) == 500 * 3
        h_all = ops.hist_sketch(vals, None, n_bins=256, interpret=True)
        assert float(h_all.sum()) == 600 * 3


class TestSketchQuantileAccuracy:
    """Property: sketch quantiles are within one log-bin width of the exact
    sample quantile, for random samples from several distribution shapes."""

    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize("family", ["exponential", "lognormal", "pareto"])
    def test_quantile_error_within_one_log_bin(self, seed, family):
        key = jax.random.PRNGKey(seed)
        n = 40_000
        if family == "exponential":
            s = jax.random.exponential(key, (n,)) + 1e-3
        elif family == "lognormal":
            s = jnp.exp(jax.random.normal(key, (n,)) * 1.5)
        else:  # pareto tail index 2.1
            u = jax.random.uniform(key, (n,),
                                   minval=jnp.finfo(jnp.float32).tiny)
            s = 0.5 * u ** (-1.0 / 2.1)
        n_bins = ops.DEFAULT_BINS
        hist = ops.hist_sketch(s[:, None], n_bins=n_bins, interpret=True)
        qs = jnp.asarray([50.0, 90.0, 99.0, 99.9])
        sketch = ops.sketch_quantiles(hist, qs)[:, 0]
        log_bin = (math.log(ops.HIST_HI) - math.log(ops.HIST_LO)) / (n_bins - 1)
        for qi, p in enumerate([0.5, 0.9, 0.99, 0.999]):
            exact = float(jnp.quantile(s, p))
            err = abs(math.log(float(sketch[qi])) - math.log(exact))
            assert err <= log_bin * 1.001 + 1e-6, (family, p, err, log_bin)

    def test_clamped_outliers_land_in_edge_bins(self):
        s = jnp.asarray([1e-9, 1e9, 1.0])[:, None]
        h = ops.hist_sketch(s, n_bins=256, interpret=True)
        assert float(h[0, 0]) == 1.0 and float(h[0, -1]) == 1.0
        assert float(h.sum()) == 3.0
