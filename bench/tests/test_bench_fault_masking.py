"""A fault-masking cell is new files plus new entries in BENCHMARK.json:
a configuration with timed policies and a degradation model, and a
traffic mix, added to a copy of the benchmark and run through the
harness at a tiny size. It comes out correct; with the timed path
broken underneath it comes out as not correct, once for each fault the
cell can have, and so does the bfloat16 control.

The faults are planted in the program's single-arrival step
(``cell_update.ref.step_cell``) on the scan body, by wrapping it, and
the compiled programs are dropped around each run so that the wrapper
is traced."""
import json

import pytest

import bench_testlib

CELL = "hedge-faults-tiny.sweep"


def _faulty(policy: str, k: int) -> dict:
    """Copies blackhole or straggle 8x, 10% each; timed at delay 1."""
    return {"dists": [{"family": "exponential"}], "policy": policy,
            "ks": [k], "delay": 1.0,
            "degradation": {"p_fail": 0.1, "p_slow": 0.1, "slow_factor": 8}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_testlib.tiny_copy(tmp_path_factory.mktemp("bench"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "configs" / "hedge-faults-tiny.json").write_text(
        json.dumps({"n_servers": 10, "scenarios": [
            _faulty("replicate_all", 1), _faulty("hedge_after_delay", 2),
            _faulty("timeout_retry", 2)]}))
    (root / "bench" / "traffic" / f"{CELL}.json").write_text(json.dumps({
        "entry": "run", "n_seeds": 2, "loads": [0.1, 0.4, 3],
        "arrivals": 2048, "chunk": 512, "percentiles": [50, 99],
        "check": {"queries": 1, "limits": {
            "mean_rel": 1e-4, "pct_bins": 1.0, "completed_gap": 0.0}}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "hedge-faults-tiny", "source": "test",
                             "file": "bench/configs/hedge-faults-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "hedge-faults-tiny",
                               "traffic": "sweep", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "paper-20srv.tail-sweep" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())
    return root


@pytest.fixture
def scan_body(monkeypatch):
    """The scan body on fresh compiles, and fresh compiles after."""
    import jax

    from repro.core import queueing

    monkeypatch.setattr(queueing.cell_ops, "resolve_kernel_mode",
                        lambda kernel, n_bins=None: "off")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _wrap_step(monkeypatch, wrap):
    from repro.kernels.cell_update import ref

    monkeypatch.setattr(ref, "step_cell", wrap(ref.step_cell))


def _gate_always_fires(monkeypatch):
    """Hedge copy j fires at ``t + j * delay`` even where an earlier copy
    has finished by then."""
    import jax.numpy as jnp

    from repro.core.scenario import Policy

    def wrap(step):
        def fired_all(free, t, srv, svc, shared, degr_u, mask, overhead,
                      policy, model, mix, p_slow, slow_factor, p_fail,
                      delay, valid=True, **kw):
            new_free, resp = step(free, t, srv, svc, shared, degr_u, mask,
                                  overhead, policy, model, mix, p_slow,
                                  slow_factor, p_fail, delay, valid, **kw)
            svc = jnp.where(degr_u >= 1.0 - p_slow, svc * slow_factor, svc)
            live = mask & (degr_u >= p_fail)
            finish = jnp.maximum(free[srv], t + delay * jnp.arange(
                srv.shape[0], dtype=jnp.float32)) + svc
            hedge = policy == int(Policy.HEDGE_AFTER_DELAY)
            return (jnp.where(hedge, free.at[srv].set(
                        jnp.where(live, finish, free[srv])), new_free),
                    jnp.where(hedge, jnp.min(jnp.where(live, finish, jnp.inf))
                              - t + overhead, resp))
        return fired_all

    _wrap_step(monkeypatch, wrap)


def _no_retry_exemption(monkeypatch):
    """Retry's last attempt is lost to its blackhole draw. With a budget
    of 2, retry's offsets (0, 1) are hedging's, so retry cells run as
    hedge cells differ from them only by the exemption."""
    import jax.numpy as jnp

    from repro.core.scenario import Policy

    def wrap(step):
        def exemption_dropped(free, t, srv, svc, shared, degr_u, mask,
                              overhead, policy, *args, **kw):
            policy = jnp.where(policy == int(Policy.TIMEOUT_RETRY),
                               int(Policy.HEDGE_AFTER_DELAY), policy)
            return step(free, t, srv, svc, shared, degr_u, mask, overhead,
                        policy, *args, **kw)
        return exemption_dropped

    _wrap_step(monkeypatch, wrap)


def _stragglers_unscaled(monkeypatch):
    """A straggler is served at its drawn time, not ``slow_factor`` x."""
    import jax.numpy as jnp

    def wrap(step):
        def unscaled(free, t, srv, svc, shared, degr_u, mask, overhead,
                     policy, model, mix, p_slow, slow_factor, *args, **kw):
            return step(free, t, srv, svc, shared, degr_u, mask, overhead,
                        policy, model, mix, p_slow,
                        jnp.ones_like(slow_factor), *args, **kw)
        return unscaled

    _wrap_step(monkeypatch, wrap)


def test_fault_masking_cell_is_correct(root, scan_body):
    result, _ = bench_testlib.run_cell(root, CELL)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"copy_steps_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [_gate_always_fires, _no_retry_exemption,
                                   _stragglers_unscaled])
def test_timed_fault_is_caught(root, scan_body, fault, monkeypatch):
    fault(monkeypatch)
    result, _ = bench_testlib.run_cell(root, CELL)
    assert result["failed"] == 0
    assert result["correct"] is False, result["checks"]


def test_bfloat16_control_is_caught(root):
    import numpy as np

    from bench import control, spec

    cell = spec.load_cell(CELL, root)
    numbers = control.stream_numbers(cell, seed=5, n_queries=2)
    limits = cell.traffic["check"]["limits"]
    assert any(not (np.isfinite(v) and v <= limits[n])
               for n, v in numbers.items() if n in limits), numbers
