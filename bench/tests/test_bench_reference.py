"""The plain reference against the engine at a tiny size on the CPU, on
both chunk bodies: the scan and the cell-update kernel through the
Pallas interpreter. The engine computes in float32 and the reference in
float64 from the same keys, so means agree to float32 rounding and each
percentile lies within one log-bin of the exact order statistic."""
import hashlib

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


def _faulty(policy, k):
    """A scenario whose copies blackhole or straggle 8x, 10% each."""
    return {"dists": [{"family": "exponential"}], "policy": policy,
            "ks": [k], "delay": 0.7,
            "degradation": {"p_fail": 0.1, "p_slow": 0.1, "slow_factor": 8}}


# bare k=1, hedging k=2 and retry k=3 at one delay: retry's backoff
# offsets 0, 1, 3 differ from hedging's 0, 1, 2
FAULTS = {"n_servers": 8, "scenarios": [
    _faulty("replicate_all", 1), _faulty("hedge_after_delay", 2),
    _faulty("timeout_retry", 3)]}


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
@pytest.mark.parametrize("config", [CONFIG, SYSTEMS, FAULTS],
                         ids=["stacked", "mixed", "faults"])
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
    if config is FAULTS:
        done = np.asarray(ref["completed"])          # (S, B, variant)
        assert (done < out["count"]).any()           # blackholes lose some
        assert (done[..., 2] == out["count"]).all()  # retry loses none


@pytest.mark.parametrize("policy, model, named", [
    ("cancel_on_complete", "iid", "cancel_on_complete"),
    ("replicate_to_idle", "iid", "replicate_to_idle"),
    ("replicate_all", "server_dependent", "server_dependent")])
def test_grid_names_what_it_cannot_simulate(policy, model, named):
    config = {"n_servers": 4, "scenarios": [{
        "dists": [{"family": "exponential"}], "policy": policy,
        "service_model": model}]}
    with pytest.raises(NotImplementedError, match=named):
        reference.grid_of(config, config["scenarios"][0]["dists"])


def _digest(summaries) -> str:
    h = hashlib.sha256()
    for s in summaries:
        for name in sorted(s):
            h.update(name.encode())
            h.update(np.ascontiguousarray(s[name], np.float64).tobytes())
    return h.hexdigest()


# Taken at the commit before the reference learned the timed policies
# and the degradation model: the work of each cell at its own size, and
# the digest of the reference's summaries of its grid (float64, and the
# bfloat16 control) at 1024 arrivals in chunks of 256, 2 seeds.
PINNED = {
    "paper-20srv.tail-sweep": (
        156028416.0, 24297472.0,
        "68db0397e3cd70a745fc0d49b06171680d472b920d0d47b6b7c8530bc7a9e652",
        "4a8769614666332549db1503bc9fa8b29ec931b03c08431fcf40138d757d9404"),
    "paper-systems.tail-sweep": (
        175531968.0, 23795712.0,
        "7be02b754ea2024e2c76fc4a3f9234bf588c08fc0036d5450802a7cc59cbe826",
        "df3025b39b6d5ae7a07b58c1d383edc62d563ded76f121463184e9d401539479"),
    "paper-20srv.threshold-query": (
        59454324.0, 3951168.0,
        "49062fe981bb0ed3127fdbf99c8da8bea8191904bbcf78e9a90f00af3f1f0232",
        "5499310187a12284657acff1a9f81eddf244ae66033ac599e4e9be055f710b58"),
    "paper-20srv.tail-sweep-4chip": (
        624113664.0, 97189888.0,
        "68db0397e3cd70a745fc0d49b06171680d472b920d0d47b6b7c8530bc7a9e652",
        "4a8769614666332549db1503bc9fa8b29ec931b03c08431fcf40138d757d9404"),
}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_existing_cells_unchanged(workload):
    """The cells of the replicate-to-all grids keep their work counts and
    their reference summaries bit for bit."""
    from bench import control, drive, spec

    cell = spec.load_cell(workload)
    tr = cell.traffic
    if cell.entry == "run":
        grid = reference.grid_of(cell.config,
                                 spec.reference_laws(cell.config))
        call = drive.stream_call(grid, tr)
        loads = np.linspace(*tr["loads"][:2], int(tr["loads"][2]),
                            dtype=np.float32)
        args = (grid, 2, 1024, 256,
                tuple(float(p) for p in tr.get("percentiles", ())))
    else:
        law_cfg = drive.law_config(cell.config, 0)
        law_cfg["scenarios"][0]["ks"] = [1, int(tr["k"])]
        grid = reference.grid_of(law_cfg, spec.reference_laws(law_cfg))
        call = drive.stream_call(grid, tr, n_loads=18)
        loads = np.asarray([0.02, 0.3, 0.499], np.float32)
        args = (grid, 2, 1024, None, ())
    items = [(drive.query_key(2**40 + 17, q), loads) for q in (0, 1)]
    got = (call.ops, call.bytes, _digest(reference.run_grids(items, *args)),
           _digest(reference.run_grids(items, *args,
                                       dtype=control.CONTROL_DTYPE)))
    assert got == PINNED[workload]


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


def test_cell_with_nothing_completed_fails():
    """Every copy blackholes: the reference completes nothing, and the
    comparison reads inf for the mean instead of a NaN."""
    import jax

    from bench import spec

    config = {"n_servers": 4, "scenarios": [{
        "dists": [{"family": "exponential"}], "ks": [1],
        "degradation": {"p_fail": 1.0}}]}
    grid = reference.grid_of(config, spec.reference_laws(config))
    ref = reference.run_grids([(jax.random.PRNGKey(0), [0.2])], grid, 1,
                              64, None, (50.0,))[0]
    assert (ref["completed"] == 0).all()
    prog = {"mean": np.full_like(ref["mean"], np.nan),
            "completed": ref["completed"],
            "p50": np.full_like(ref["mean"], 1.0)}
    gaps = check.compare_summaries(prog, ref)
    assert gaps["mean_rel"] == np.inf
    assert gaps["completed_gap"] == 0.0
