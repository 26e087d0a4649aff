"""The plain reference against the engine at a tiny size on the CPU, on
both chunk bodies: the scan and the cell-update kernel through the
Pallas interpreter. The engine computes in float32 and the reference in
float64 from the same keys, so means agree to float32 rounding and each
percentile lies within one log-bin of the exact order statistic."""
import numpy as np
import pytest

import bench_testlib  # noqa: F401  (puts the repo and src on the path)
from bench import check, reference

CONFIG = {"n_servers": 20, "scenarios": [{
    "dists": [{"family": "exponential"}, {"family": "pareto", "args": [2.1]}],
    "ks": [1, 2]}]}
SYSTEMS = {"n_servers": 8, "scenarios": [
    {"dists": [{"name": "a", "table": [0.2, 0.6, 1.0, 1.4, 1.8]}],
     "ks": [1, 2], "client_overhead": 0.05},
    {"dists": [{"family": "exponential"}], "ks": [1, 2]}]}


def _engine(config, loads, kernel, key, n_seeds=2, m=1024, chunk=512,
            pct=(50.0, 99.0)):
    import jax.numpy as jnp

    from bench import spec
    from repro.core import queueing

    scn = spec.build_scenario(config)
    cfg = queueing.SimConfig(n_servers=config["n_servers"], n_arrivals=m)
    return queueing.run(key, scn, jnp.asarray(loads, jnp.float32), cfg,
                        n_seeds=n_seeds, chunk_size=chunk, percentiles=pct,
                        kernel=kernel)


@pytest.mark.parametrize("kernel", ["off", "interpret"])
@pytest.mark.parametrize("config", [CONFIG, SYSTEMS], ids=["stacked", "mixed"])
def test_reference_matches_engine(kernel, config):
    import jax

    from bench import spec

    key = jax.random.PRNGKey(3)
    loads = np.asarray([0.1, 0.3, 0.45], np.float32)
    out = _engine(config, loads, kernel, key)
    grid = reference.grid_of(config, spec.reference_laws(config))
    ref = reference.run_grids([(key, loads)], grid, 2, 1024, 512,
                              (50.0, 99.0))[0]
    gaps = check.compare_summaries(out, ref)
    assert gaps["mean_rel"] < 1e-4
    assert gaps["pct_bins"] <= 1.0
    assert gaps["completed_gap"] == 0.0


def test_reference_bisection_matches_engine():
    import jax

    from bench import spec
    from repro.core import queueing, threshold

    config = {"n_servers": 20, "scenarios": [
        {"dists": [{"family": "exponential"}], "ks": [1, 2]}]}
    key = jax.random.PRNGKey(11)
    cfg = queueing.SimConfig(n_servers=20, n_arrivals=2048)
    got = threshold.threshold_bisect(key, spec.build_scenario(config), cfg,
                                     k=2, iters=4, n_seeds=2)
    grid = reference.grid_of(config, spec.reference_laws(config))
    keys = jax.random.split(key, 5)

    def evaluate(call, loads):
        r = reference.run_grids([(keys[call], loads)], grid, 2, 2048,
                                None)[0]
        return list(reference.paired_gain(r["mean"]))

    assert reference.bisect(evaluate, 0.02, 0.499, 4) == got


def test_percentile_gap_reads_the_sketch_range():
    """Responses beyond the sketch's range (a 6e17 service time blocks a
    server; a response under 1e-3) sit in its edge bins: the gap to the
    order statistic, clamped alike, is under one bin. An in-range
    percentile read off the top bin is still a wide gap."""
    import jax.numpy as jnp

    from repro.kernels.hist_sketch.ops import hist_sketch, sketch_quantiles

    # in range, neighbouring order statistics lie well within one bin
    resp = np.stack([np.random.default_rng(i).permutation(
        np.geomspace(0.5, 2.0, 1000)) for i in range(2)])
    resp[0, :50] = 6.039112643178708e17            # p99, p99.9 beyond HIST_HI
    resp[1, :600] = 1e-5                           # p50 under HIST_LO
    pct = (50.0, 99.0, 99.9)
    hist = hist_sketch(jnp.asarray(resp.T, jnp.float32))
    prog = sketch_quantiles(hist, jnp.asarray(pct))
    prog = {"mean": resp.mean(axis=1),
            **{f"p{p:g}": np.asarray(prog[i]) for i, p in enumerate(pct)}}
    ref = {"mean": resp.mean(axis=1), **reference.order_stats(resp, pct)}
    assert ref["p99"][0] > reference.HIST_HI and ref["p50"][1] < reference.HIST_LO
    assert check.compare_summaries(prog, ref)["pct_bins"] <= 1.0
    prog["p50"] = np.full(2, np.float32(reference.HIST_HI))
    assert check.compare_summaries(prog, ref)["pct_bins"] > 100.0
