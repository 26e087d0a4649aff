"""A later cell is new files plus new entries in BENCHMARK.json: a
configuration and a traffic mix added as files of their own in a copy
of the benchmark, with no existing file edited but BENCHMARK.json's
lists, run through the harness at a tiny size."""
import json

import bench_testlib


def test_cell_from_new_files(tmp_path):
    root = bench_testlib.tiny_copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "configs" / "ten-pareto.json").write_text(json.dumps({
        "n_servers": 10, "scenarios": [{
            "dists": [{"family": "pareto", "args": [1.8]}], "ks": [1, 2, 3],
            "client_overhead": 0.02}]}))
    (root / "bench" / "traffic" / "ten-pareto.burst.json").write_text(
        json.dumps({"entry": "run", "n_seeds": 2, "loads": [0.1, 0.3, 3],
                    "arrivals": 1024, "chunk": 256, "percentiles": [99],
                    "check": {"queries": 1, "limits": {
                        "mean_rel": 1e-4, "pct_bins": 1.0,
                        "completed_gap": 0.0}}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ten-pareto", "source": "test",
                             "file": "bench/configs/ten-pareto.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "ten-pareto.burst",
                               "config": "ten-pareto", "traffic": "burst",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "paper-20srv.tail-sweep" in m.get("workloads", ()):
            m["workloads"].append("ten-pareto.burst")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result, _ = bench_testlib.run_cell(root, "ten-pareto.burst")
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"copy_steps_per_s", "setup_s"}
    assert result["metrics"]["copy_steps_per_s"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())


LAW_MODULE = '''
import math

import numpy as np


def law(entry):
    from repro.core.distributions import weibull
    return weibull(entry["shape"])


def reference_sample(entry, key, shape):
    import jax
    import jax.numpy as jnp
    k = entry["shape"]
    lam = 1.0 / math.gamma(1.0 + 1.0 / k)
    u = np.asarray(jax.random.uniform(
        key, shape, minval=jnp.finfo(jnp.float32).tiny), np.float64)
    return lam * (-np.log(u)) ** (1.0 / k)
'''


def test_law_with_code_from_new_files(tmp_path):
    """A law that needs code brings a module of its own next to its
    config: the program's law and the reference's draws."""
    root = bench_testlib.tiny_copy(tmp_path)
    configs = root / "bench" / "configs"
    (configs / "weibull_law.py").write_text(LAW_MODULE)
    (configs / "weibull.json").write_text(json.dumps({
        "n_servers": 12, "scenarios": [{
            "dists": [{"module": "weibull_law.py", "shape": 0.7}],
            "ks": [1, 2]}]}))
    (root / "bench" / "traffic" / "weibull.sweep.json").write_text(
        json.dumps({"entry": "run", "n_seeds": 2, "loads": [0.1, 0.3, 2],
                    "arrivals": 1024, "chunk": 512, "percentiles": [50],
                    "check": {"queries": 1, "limits": {
                        "mean_rel": 1e-4, "pct_bins": 1.0,
                        "completed_gap": 0.0}}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "weibull", "source": "test",
                             "file": "bench/configs/weibull.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "weibull.sweep", "config": "weibull",
                               "traffic": "sweep", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result, _ = bench_testlib.run_cell(root, "weibull.sweep")
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s"}
