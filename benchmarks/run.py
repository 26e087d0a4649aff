"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--only fig14`` runs one module
(repeatable: ``--only sweep_engine --only fig_policy_space``).
``--json PATH`` additionally writes the rows as a JSON list so the perf
trajectory is machine-readable across PRs (e.g. ``--json
BENCH_queueing.json``). Each JSON row records execution provenance next
to the measurement — ``backend`` / ``device_count`` / ``process_count``
of the runtime, the ``mesh`` shape the row ran under (``null`` for
unsharded rows), the ``scenario`` the row measured (policy / service
model / mix, from ``repro.core.scenario.provenance``; ``null`` for rows
that are not a queueing-scenario measurement), and the row's
``sampling`` provenance (``repro.core.chunkflow.stats_provenance``:
pipeline on/off, per-host sampled bytes vs the full block, locality
factor; ``null`` for non-engine rows) — so BENCH_*.json trajectories
are comparable across machines AND across points of the policy space,
and the multi-host sampling reduction is visible in the artifact.
``--smoke`` runs every module at tiny sizes — CI uses ``--json --smoke``
to refresh the perf-trajectory artifact on every push without paying for
full-size sweeps. ``--devices N`` builds an N-way ``"cells"`` sweep mesh
and hands it to mesh-aware modules (``sweep_engine`` plus the
empirical-system figures ``fig5_diskdb`` / ``fig12_memcached`` /
``fig15_dns`` / ``fig_cross_system``), which then emit sharded rows; on
CPU export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first.
``--kernel {auto,on,off}`` picks the engine's fused cell-update kernel
mode for kernel-aware modules (``sweep_engine``, ``fig_policy_space``;
``auto`` = kernel on TPU, scan elsewhere); each JSON row's ``kernel``
field records the RESOLVED mode the row actually executed under
(``on`` / ``off`` / ``interpret``, ``null`` for non-engine rows), so
trajectories never mix kernel-path and scan-path numbers silently.
Every row also names the device it ran on (``device_kind``), and the
harness exits non-zero when any module raised — after writing the rows
it did collect, the failing module's as an ``ERROR`` row. Compiled
programs persist in the one compile-cache directory
(``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
from pathlib import Path

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; make `from benchmarks import ...` work from any invocation.
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=None,
                    help="substring filter on module names (repeatable)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to PATH as a JSON list")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: exercise every module quickly")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="run mesh-aware modules through the sharded "
                         "cell-plan engine on an N-device 'cells' mesh")
    ap.add_argument("--kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="fused cell-update kernel mode for kernel-aware "
                         "modules (auto: kernel on TPU, scan elsewhere)")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh = None
    if args.devices:
        # clamp to the largest DIVISOR of the visible device count:
        # make_sweep_mesh validates divisibility, and a mesh over a
        # non-divisor would reject the request anyway
        avail = jax.device_count()
        n = next(d for d in range(min(args.devices, avail), 0, -1)
                 if avail % d == 0)
        if n < args.devices:
            print(f"# --devices {args.devices} clamped to {n} "
                  f"(largest divisor of the {avail} visible devices; on "
                  f"CPU set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count={args.devices})",
                  file=sys.stderr)
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh(n)

    from benchmarks import (fig1_queueing, fig2_threshold, fig3_random,
                            fig4_overhead, fig5_diskdb, fig12_memcached,
                            fig14_network, fig15_dns, fig_cross_system,
                            fig_fault_masking, fig_policy_space, roofline,
                            serving_hedge, sweep_engine, tab_tcp)
    from benchmarks.common import row_provenance
    modules = [sweep_engine, fig_policy_space, fig1_queueing,
               fig2_threshold, fig3_random, fig4_overhead, fig5_diskdb,
               fig12_memcached, fig14_network, fig15_dns,
               fig_cross_system, tab_tcp, fig_fault_masking,
               serving_hedge, roofline]

    provenance = {"backend": jax.default_backend(),
                  "device_kind": jax.devices()[0].device_kind,
                  "device_count": jax.device_count(),
                  "process_count": jax.process_count()}

    print("name,us_per_call,derived")
    collected: list[dict[str, object]] = []
    failed: list[str] = []
    t0 = time.time()
    for mod in modules:
        name = mod.__name__.split(".")[-1]
        if args.only and not any(o in name for o in args.only):
            continue
        kwargs = {"smoke": args.smoke}
        params = inspect.signature(mod.run).parameters
        if mesh is not None and "mesh" in params:
            kwargs["mesh"] = mesh
        if "kernel" in params:
            kwargs["kernel"] = args.kernel
        try:
            for row in mod.run(**kwargs):
                # rows are (name, us, derived[, mesh[, scenario
                # [, kernel[, sampling]]]]) — see benchmarks.common
                row_name, us, derived = row[:3]
                (row_mesh, row_scenario, row_kernel,
                 row_sampling) = row_provenance(row)
                print(f"{row_name},{us:.1f},{derived}", flush=True)
                collected.append({"name": row_name,
                                  "us_per_call": round(us, 1),
                                  "derived": derived,
                                  "mesh": row_mesh,
                                  "scenario": row_scenario,
                                  "kernel": row_kernel,
                                  "sampling": row_sampling,
                                  **provenance})
        except Exception as e:  # keep the harness going, fail at exit
            failed.append(name)
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            collected.append({"name": f"{name}/ERROR", "us_per_call": 0,
                              "derived": f"{type(e).__name__}:{e}",
                              "mesh": None, "scenario": None,
                              "kernel": None, "sampling": None,
                              **provenance})
            import traceback
            traceback.print_exc(file=sys.stderr)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(collected, f, indent=1)
        print(f"# wrote {len(collected)} rows to {args.json}",
              file=sys.stderr)
    if failed:
        sys.exit(f"# {len(failed)} module(s) raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
