"""Every benchmark module's entry point imports and runs at tiny sizes
(the same ``smoke=True`` path CI exercises via ``benchmarks/run.py
--smoke``), so a refactor of the engine API cannot silently strand a
figure reproduction."""
import importlib
import sys
from pathlib import Path

import pytest

# benchmarks/ is a repo-root package (not under src/); make it importable
# the same way benchmarks/run.py is invoked from the repo root.
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# Fast modules run in full; the heavy simulators get a trimmed marker so a
# plain tier-1 run still covers every entry point without minutes of wall
# clock dominated by two modules.
MODULES = [
    "fig1_queueing",
    "fig2_threshold",
    "fig3_random",
    "fig4_overhead",
    "fig5_diskdb",
    "fig12_memcached",
    "fig15_dns",
    "tab_tcp",
    "serving_hedge",
    "roofline",
    "sweep_engine",
    "fig_policy_space",
    "fig14_network",
    "fig_fault_masking",
    "fig_cross_system",
]


@pytest.mark.parametrize("name", MODULES)
def test_benchmark_entry_runs_smoke(name):
    mod = importlib.import_module(f"benchmarks.{name}")
    rows = mod.run(smoke=True)
    assert isinstance(rows, list) and rows, name
    for row in rows:
        # rows may carry mesh-shape (4th) and scenario (5th) provenance
        label, us, derived = row[:3]
        assert isinstance(label, str) and label
        assert float(us) >= 0.0
        assert isinstance(derived, str)
        assert "ERROR" not in label, (label, derived)


def test_sweep_engine_sharded_rows_on_single_device_mesh():
    """The mesh-aware path emits sharded rows with mesh provenance even
    on a 1-device mesh (CI's multi-device job exercises 8)."""
    import benchmarks.sweep_engine as se
    from benchmarks.common import row_provenance
    from repro.launch.mesh import make_sweep_mesh
    rows = se.run(smoke=True, mesh=make_sweep_mesh(1))
    sharded = [r for r in rows if "sharded" in r[0]]
    assert sharded, [r[0] for r in rows]
    for row in sharded:
        mesh, _, _, _ = row_provenance(row)
        assert mesh == [1], row
        assert "bit_identical=True" in row[2], row


def test_fig_policy_space_scenario_provenance():
    """Every scenario row of the policy-space figure carries its policy /
    service-model / mix provenance (recorded per JSON row by run.py);
    the crossover summary row reports the Shah et al. sign flip."""
    import benchmarks.fig_policy_space as fps
    from benchmarks.common import row_provenance
    rows = fps.run(smoke=True)
    by_name = {r[0]: r for r in rows}
    _, scn, kernel, _ = row_provenance(by_name["fig_policy_space/iid"])
    assert scn["policy"] == "REPLICATE_ALL" and scn["mix"] == 0.0
    assert kernel in ("on", "off", "interpret")  # resolved, never "auto"
    _, scn, _, _ = row_provenance(by_name["fig_policy_space/server_dep_mix1"])
    assert scn["service_model"] == "SERVER_DEPENDENT" and scn["mix"] == 1.0
    _, scn, _, _ = row_provenance(by_name["fig_policy_space/cancel"])
    assert scn["policy"] == "CANCEL_ON_COMPLETE"
    assert "crossover=" in by_name["fig_policy_space/crossover"][2]


def test_sweep_engine_kernel_row():
    """The kernel on-vs-off row always exists, records the RESOLVED mode
    it measured, and holds a measured speedup + bit-identity flag in the
    derived field (the acceptance provenance for the fused kernel)."""
    import benchmarks.sweep_engine as se
    from benchmarks.common import row_provenance
    rows = se.run(smoke=True)
    by_name = {r[0]: r for r in rows}
    row = by_name["sweep_engine/kernel_on_vs_off"]
    _, _, kernel, _ = row_provenance(row)
    assert kernel in ("on", "interpret")  # never the scan fallback
    assert "bit_identical=True" in row[2], row
    assert "speedup=" in row[2] and "scan_s=" in row[2], row


def test_fig_fault_masking_chaos_acceptance():
    """The chaos demo's acceptance booleans (25% of replicas crashed
    mid-trace: hedged completes 100% within 2x its no-fault p99, the
    timeout-retry baseline degrades at least as much) hold even at
    smoke sizes — the JSON artifact records them per PR."""
    import benchmarks.fig_fault_masking as ffm
    rows = ffm.run(smoke=True)
    by_name = {r[0]: r for r in rows}
    chaos = by_name["fig_fault_masking/chaos"][2]
    assert "hedged_completes_all=True" in chaos, chaos
    assert "hedged_p99_within_2x=True" in chaos, chaos
    assert "retry_degrades_more=True" in chaos, chaos
    assert "masked=True" in chaos, chaos
    engine = by_name["fig_fault_masking/engine"][2]
    assert "retry_completes_all=True" in engine, engine
    assert "completion_order=True" in engine, engine


def test_serving_adaptive_vs_static_acceptance():
    """The adaptive-serving acceptance booleans hold at smoke sizes: a
    short deterministic diurnal replay where the controller's p99 is no
    worse than the best static k at every segment and strictly better
    on at least one (the 1M-request version is the slow-marked test in
    test_serving_adaptive.py). The policy-table row resolves its grid
    from ONE mixed-grid sweep."""
    import benchmarks.serving_hedge as sh
    from benchmarks.common import row_provenance
    rows = sh.run(smoke=True)
    by_name = {r[0]: r for r in rows}
    cmp = by_name["serving/adaptive_vs_static"][2]
    assert "no_worse=True" in cmp, cmp
    assert "strictly_better=True" in cmp, cmp
    _, scn, _, _ = row_provenance(by_name["serving/adaptive_vs_static"])
    assert scn["adaptive_no_worse"] is True
    assert scn["adaptive_strictly_better"] is True
    assert scn["controller"]["decisions"] > 0
    table = by_name["serving/policy_table"]
    assert "best@0.10=" in table[2] and "best@0.75=" in table[2]
    _, tab, _, _ = row_provenance(table)
    assert len(tab["k"]) == len(tab["delay"]) >= 2
    live = by_name["serving/batched_live"][2]
    assert "completions=" in live and "p99_ms=" in live


def test_fig_cross_system_crossover_row():
    """The cross-system figure's summary row reports one crossover load
    per system off a SINGLE mixed-grid gain call, the expected ordering
    (heavy-tailed disk and DNS cross later than overhead-dominated
    memcached), and a kernel-parity row pinning scan == kernel on the
    heterogeneous grid."""
    import benchmarks.fig_cross_system as fcs
    from benchmarks.common import row_provenance
    rows = fcs.run(smoke=True)
    by_name = {r[0]: r for r in rows}
    cross = by_name["fig_cross_system/crossover"][2]
    for system in ("disk", "memcached", "dns"):
        assert f"{system}=" in cross, cross
    assert "order=" in cross, cross
    assert cross.index("memcached=") > cross.index("disk="), cross
    _, scn, kernel, _ = row_provenance(by_name["fig_cross_system/disk"])
    assert scn["ks"] == [1, 2] and len(scn["dists"]) == 1
    assert kernel in ("on", "off", "interpret")
    parity = by_name["fig_cross_system/kernel_parity"][2]
    assert "bit_identical=True" in parity, parity


def test_fig12_accepts_chunked_engine_config():
    import benchmarks.fig12_memcached as fig12
    rows = fig12.run(smoke=True, chunk_size=1_024)
    assert rows and all("ERROR" not in r[0] for r in rows)


def test_run_harness_importable():
    import benchmarks.run as run_mod
    assert callable(run_mod.main)


def test_run_harness_exits_nonzero_when_a_module_raises(monkeypatch,
                                                        capsys):
    """A module that raises still leaves its ERROR row, and the harness
    exits non-zero instead of reporting success."""
    import benchmarks.run as run_mod
    import benchmarks.tab_tcp as tab_tcp
    from repro.launch import compile_cache

    def boom(smoke=False):
        raise RuntimeError("boom")

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(tab_tcp, "run", boom)
    monkeypatch.setattr(sys, "argv", ["run.py", "--only", "tab_tcp"])
    with pytest.raises(SystemExit) as exc:
        run_mod.main()
    assert exc.value.code not in (0, None)
    assert "tab_tcp/ERROR" in capsys.readouterr().out


def test_roofline_peaks_keyed_by_device_kind():
    """Off the TPU the rows carry the cost model and say "not measured";
    a device kind without published peaks is an error, not a default."""
    import types

    import benchmarks.roofline as rl
    for row in rl.run(smoke=True)[:2]:
        assert "not measured" in row[2] and "peak_frac" not in row[2]
    v5e = rl.device_peaks(types.SimpleNamespace(device_kind="TPU v5 lite"))
    assert v5e == {"flops": 197e12, "hbm_bw": 819e9}
    with pytest.raises(ValueError, match="no published peaks"):
        rl.device_peaks(types.SimpleNamespace(device_kind="TPU v9"))


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    otherwise the one fixed directory is <repo>/.jax_cache."""
    import jax

    from repro.launch import compile_cache
    repo = Path(__file__).resolve().parent.parent
    assert compile_cache.DEFAULT_DIR == repo / ".jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_without_tpu(capsys):
    """No TPU: the chip smoke test fails before any phase and prints no
    result line."""
    import jax

    import chip_smoke
    if jax.devices()[0].platform == "tpu":
        pytest.skip("checks the refusal off the TPU")
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
