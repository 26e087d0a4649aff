"""The fault-masking cell ``hedge-faults.tail-sweep`` on its committed
files, at a tiny size: its own configuration (bare k=1, a hedge at
delay 3 and a three-attempt timeout-retry, under blackholes and 8x
stragglers) with 2 seeds, 3 loads over the cell's range and 2048 arrivals
in chunks of 512, through the harness and under the cell's own limits.
It comes out correct; with retry given hedging's offsets (0, 1, 2 in
place of the backoff's 0, 1, 3), which a budget of two copies could not
tell apart, it comes out as not correct. Every retry request completes,
and the witness (`bench/witness.py`) reads the same gaps on either set
of inputs, which on the CPU are one.
"""
import json

import pytest

import bench_testlib

CELL = "hedge-faults.tail-sweep"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_testlib.tiny_copy(tmp_path_factory.mktemp("bench"),
                                   arrivals=2048, chunk=512)
    path = root / "bench" / "traffic" / f"{CELL}.json"
    traffic = json.loads(path.read_text())
    traffic.update(n_seeds=2, loads=traffic["loads"][:2] + [3])
    path.write_text(json.dumps(traffic))
    return root


@pytest.fixture
def fresh_compiles():
    """Compiled programs dropped around a run, so that a fault planted in
    the step is traced."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def test_cell_is_correct_under_its_limits(root, fresh_compiles):
    result, _ = bench_testlib.run_cell(root, CELL, seed=3160000007)
    assert result["failed"] == 0
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"copy_steps_per_s", "setup_s"}


def test_retry_on_hedging_offsets_is_caught(root, fresh_compiles,
                                            monkeypatch):
    from repro.kernels.cell_update import kernel, ref

    def hedging(k_max):
        return [float(j) for j in range(k_max)]

    assert ref.retry_offsets(3) == [0.0, 1.0, 3.0]
    assert ref.retry_offsets(2) == hedging(2)  # a budget of 2 cannot tell
    monkeypatch.setattr(ref, "retry_offsets", hedging)
    monkeypatch.setattr(kernel, "retry_offsets", hedging)
    result, _ = bench_testlib.run_cell(root, CELL, seed=3160000007)
    assert result["failed"] == 0
    assert result["correct"] is False, result["checks"]


def test_every_retry_request_completes(root):
    import jax
    import numpy as np

    from bench import drive, spec

    cell = spec.load_cell(CELL, root)
    out = drive.make_queries(cell)(jax.random.PRNGKey(11))["out"]
    completed = np.asarray(out["completed"])        # (seeds, loads, 9)
    offered = float(out["count"])
    policies = [s["policy"] for s in cell.config["scenarios"]]
    faulty = ["degradation" in s for s in cell.config["scenarios"]]
    for v, (policy, degraded) in enumerate(zip(policies, faulty)):
        if policy == "timeout_retry" or not degraded:
            assert (completed[..., v] == offered).all(), (v, policy)
        elif policy == "replicate_all":   # a lone blackholed copy is lost
            assert (completed[..., v] < offered).all(), (v, policy)


def test_witness_on_the_cpu(root):
    """``bench/witness.py``: on the CPU the program's samplers draw the
    reference's own inputs bit for bit, so the reference on either set
    gives the program the same gaps."""
    from bench import spec, witness

    got = witness.witness(spec.load_cell(CELL, root), seed=3160000007)
    assert all(d["share_differing"] == 0.0 for d in got["inputs"].values())
    assert set(got["inputs"]) == {"gaps", "servers", "services",
                                  "degradation"}
    assert got["own_inputs"] == got["program_inputs"]
    assert len(got["per_variant"]["own_inputs"]) == 9
    assert got["own_inputs"]["completed_gap"] == 0.0
