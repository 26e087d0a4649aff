"""Sweep-engine speedup + chunk-streaming benchmarks.

Part 1 — pre-refactor sequential path vs fused engine on the Figure 2
threshold sweep (15 service-time families). The "old" path is a faithful
reimplementation of the pre-refactor code: one jitted ``lax.scan`` per
(seed, k) from Python — ``2 * n_seeds`` full passes per distribution —
with the distribution a static jit argument, so every family recompiles
both k-variants. The fused path estimates ALL 15 thresholds from one
distribution-agnostic engine call (``threshold.threshold_grid_batch``).

Part 2 — chunk-streamed vs pre-sampled engine: same thresholds via
``chunk_size=4096`` (thresholds must match within the load-grid
interpolation tolerance), wall clock for both, and the peak
randomness-input footprint each path materializes (the chunked path's is
independent of ``n_arrivals``). Finishes with a large-``n_arrivals``
streamed sweep (2M arrivals by default) that the pre-sampled path would
need ~40 MB/seed of inputs for — the chunked engine holds ~80 KB/seed.

Part 3 — sharded cell-plan execution (``mesh`` argument, wired through
``run.py --devices``): the same chunked sweep and the Fig 2 threshold
batch run through ``repro.distributed.sweep_shard`` on a 1-D "cells"
mesh, recording whether the bit-identity contract against the unsharded
engine held (``bit_identical=``) and carrying the mesh shape as JSON
provenance (the contract itself is enforced by tier-1 / CI tests, not
by the benchmark — a violation must still produce rows).

Part 4 — fused cell-update kernel on vs off (``kernel`` argument, wired
through ``run.py --kernel``): the same chunked sweep through the scan
body (``kernel="off"``) and through the Pallas kernel path (the
RESOLVED requested mode; where that is the scan body, the kernel path
this host can run — ``"on"`` on a TPU, ``"interpret"`` elsewhere — so a
kernel-path row always exists), wall clock both ways, bit-identity
recorded. The ``sweep_engine/kernel_on_vs_off`` row's derived field
carries ``scan_s= / kernel_s= / speedup=`` so BENCH_*.json trajectories
hold the measured kernel speedup as provenance; its 6th row element
(the ``kernel`` JSON field) is the mode the kernel leg executed under.

Part 5 — sampling/compute pipeline on vs off (``queueing.run``'s
``pipeline`` argument, ``repro.core.chunkflow``): the large streamed
sweep with serial per-chunk sampling vs the double-buffered producer
thread + fused jitted sampler, wall clock both ways, bit-identity
recorded, and the run's sampling provenance
(``chunkflow.stats_provenance``) as the row's 7th element — under a
multi-process runtime the same row shows the per-host sampled-bytes
reduction.

Emits per-family rows plus ``sweep_engine/total`` (end-to-end old-vs-fused
speedup, target >= 5x), ``sweep_engine/chunked*``,
``sweep_engine/kernel_on_vs_off``, ``sweep_engine/pipeline_on_vs_off``
and (with a mesh) ``sweep_engine/sharded*`` rows."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Row
from repro.core import distributions as dists
from repro.core import queueing, scenario as scn_mod, threshold
from repro.core.scenario import Scenario
from repro.kernels.cell_update import kernel_path_mode, resolve_kernel_mode

CFG = queueing.SimConfig(n_servers=20, n_arrivals=50_000)


def _paper_provenance(dist, ks=(1, 2)):
    """Scenario provenance of a legacy paper-default sweep row."""
    return scn_mod.provenance(Scenario.paper_default(dist, ks=ks))

FAMILY_PARAMS = {
    "pareto": (6.0, 3.0, 2.5, 2.2, 2.05),
    "weibull": (2.0, 1.0, 0.7, 0.5, 0.4),
    "two_point": (0.1, 0.5, 0.8, 0.95, 0.99),
}

CHUNK = 4096


def _entries(smoke: bool):
    params = ({fam: ps[:1] for fam, ps in FAMILY_PARAMS.items()} if smoke
              else FAMILY_PARAMS)
    return [(fam, x, dists.FAMILIES[fam](x))
            for fam, ps in params.items() for x in ps]


def _threshold_grid_reference(key, dist, cfg, *, k=2, rhos=None, n_seeds=2):
    """The pre-refactor path, verbatim: python loops of ``simulate_grid``
    scans over seeds x {1, k}, then crossing interpolation."""
    if rhos is None:
        rhos = jnp.linspace(0.05, 0.495, 24)
    keys = jax.random.split(key, n_seeds)
    gains = []
    for s in range(n_seeds):
        r1 = queueing.simulate_grid(keys[s], dist, rhos, cfg, 1)
        rk = queueing.simulate_grid(keys[s], dist, rhos, cfg, k)
        gains.append(jnp.mean(queueing._warm(r1, cfg), -1)
                     - jnp.mean(queueing._warm(rk, cfg), -1))
    g = jnp.mean(jnp.stack(gains), axis=0)
    return threshold._interp_crossing(rhos, g)


def _input_bytes(cfg: queueing.SimConfig, n: int, k_max: int = 2) -> int:
    """Bytes of pre-sampled randomness per seed for ``n`` arrivals: one f32
    gap + k_max i32 servers + k_max f32 services per arrival."""
    del cfg
    return n * 4 * (1 + 2 * k_max)


def _sharded_rows(key, cfg: queueing.SimConfig, mesh,
                  smoke: bool) -> list[Row]:
    """Sharded-vs-unsharded on the chunked sweep + threshold batch: wall
    clock both ways, bit-identity asserted, mesh shape as provenance."""
    from repro.distributed.sweep_shard import sweep_sharded

    shape = tuple(mesh.devices.shape)
    n_dev = mesh.devices.size
    rows: list[Row] = []

    rhos = jnp.linspace(0.1, 0.4, 3 if smoke else 8)
    n_seeds = 2
    d = dists.exponential()
    kw = dict(ks=(1, 2), n_seeds=n_seeds, chunk_size=CHUNK)
    t0 = time.perf_counter()
    un = queueing.sweep(key, d, rhos, cfg, **kw)
    jax.block_until_ready(un["mean"])
    un_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sh = sweep_sharded(key, d, rhos, cfg, mesh=mesh, **kw)
    jax.block_until_ready(sh["mean"])
    sh_s = time.perf_counter() - t0
    # bit_identical=False in a row is the signal a contract violation
    # leaves behind — never raise here, or the diagnostic row (and the
    # module's other rows) would be dropped before reaching the JSON
    # artifact. Tier-1 / the multi-device CI job enforce the contract.
    bit = all(bool(jnp.array_equal(un[f], sh[f]))
              for f in ("mean", "p50", "p99"))
    cells = n_seeds * rhos.shape[0] * 2
    rows.append((f"sweep_engine/sharded/sweep_d{n_dev}", sh_s * 1e6,
                 f"cells={cells};devices={n_dev};bit_identical={bit};"
                 f"unsharded_s={un_s:.2f};sharded_s={sh_s:.2f}", shape,
                 _paper_provenance(d)))

    fams = [dists.pareto(2.5), dists.weibull(0.7), dists.two_point(0.8)]
    t0 = time.perf_counter()
    th_un = threshold.threshold_grid_batch(key, fams, cfg, n_seeds=2,
                                           chunk_size=CHUNK)
    un_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    th_sh = threshold.threshold_grid_batch(key, fams, cfg, n_seeds=2,
                                           chunk_size=CHUNK, mesh=mesh)
    sh_s = time.perf_counter() - t0
    bit = th_un == th_sh
    rows.append((f"sweep_engine/sharded/thresholds_d{n_dev}", sh_s * 1e6,
                 f"families={len(fams)};devices={n_dev};"
                 f"bit_identical={bit};unsharded_s={un_s:.2f};"
                 f"sharded_s={sh_s:.2f}", shape))
    return rows


def _kernel_rows(key, cfg: queueing.SimConfig, kernel: str,
                 smoke: bool) -> list[Row]:
    """Fused cell-update kernel on-vs-off: wall clock for the scan body
    and for the kernel path on the same chunked sweep, bit-identity
    recorded, measured speedup in the derived field (JSON provenance).
    """
    # "auto" resolves to "off" off-TPU; take the interpreter leg then so
    # the row always holds a kernel-path measurement.
    mode = resolve_kernel_mode(kernel)
    if mode == "off":
        mode = kernel_path_mode()  # "on" on TPU, else "interpret"
    scn = Scenario.paper_default(dists.exponential(), ks=(1, 2))
    rhos = jnp.linspace(0.1, 0.4, 3)
    kw = dict(n_seeds=2, chunk_size=CHUNK)
    kcfg = (cfg if smoke
            else queueing.SimConfig(n_servers=20, n_arrivals=20_000))

    t0 = time.perf_counter()
    off = queueing.run(key, scn, rhos, kcfg, kernel="off", **kw)
    jax.block_until_ready(off["mean"])
    scan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    on = queueing.run(key, scn, rhos, kcfg, kernel=mode, **kw)
    jax.block_until_ready(on["mean"])
    kernel_s = time.perf_counter() - t0
    # like the sharded rows: record a violation, never raise
    bit = all(bool(jnp.array_equal(off[f], on[f]))
              for f in ("mean", "p50", "p99"))
    return [("sweep_engine/kernel_on_vs_off", kernel_s * 1e6,
             f"kernel={mode};arrivals={kcfg.n_arrivals};"
             f"scan_s={scan_s:.2f};kernel_s={kernel_s:.2f};"
             f"speedup={scan_s / kernel_s:.2f}x;bit_identical={bit}",
             None, scn_mod.provenance(scn), mode)]


def _pipeline_rows(key, kernel: str, smoke: bool) -> list[Row]:
    """Sampling/compute pipeline on-vs-off on the large streamed sweep
    (the ISSUE-9 acceptance row): wall clock both ways at 2M arrivals,
    bit-identity recorded in the derived field, the run's sampling
    provenance (``chunkflow.stats_provenance``) as the row's 7th
    element. Like the kernel row: record a violation, never raise."""
    from repro.core import chunkflow

    resolved = resolve_kernel_mode(kernel)
    big_m = 200_000 if smoke else 2_000_000
    big_cfg = queueing.SimConfig(n_servers=20, n_arrivals=big_m)
    scn = Scenario.paper_default(dists.exponential(), ks=(1, 2))
    rhos = jnp.asarray([0.3])
    kw = dict(n_seeds=1, chunk_size=CHUNK, kernel=resolved)

    t0 = time.perf_counter()
    off = queueing.run(key, scn, rhos, big_cfg, pipeline="off", **kw)
    jax.block_until_ready(off["mean"])
    off_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    on = queueing.run(key, scn, rhos, big_cfg, pipeline="on", **kw)
    jax.block_until_ready(on["mean"])
    on_s = time.perf_counter() - t0
    bit = all(bool(jnp.array_equal(off[f], on[f]))
              for f in ("mean", "p50", "p99"))
    return [("sweep_engine/pipeline_on_vs_off", on_s * 1e6,
             f"arrivals={big_m};chunk={CHUNK};off_s={off_s:.2f};"
             f"on_s={on_s:.2f};speedup={off_s / on_s:.2f}x;"
             f"bit_identical={bit}",
             None, scn_mod.provenance(scn), resolved,
             chunkflow.stats_provenance())]


def run(smoke: bool = False, mesh=None, kernel: str = "auto") -> list[Row]:
    rows: list[Row] = []
    key = jax.random.PRNGKey(1)
    cfg = (queueing.SimConfig(n_servers=20, n_arrivals=5_000) if smoke
           else CFG)
    resolved = resolve_kernel_mode(kernel)  # stamp rows with the real mode
    entries = _entries(smoke)

    # --- old path: one scan per (family, seed, k), dist static in jit ----
    old_us = []
    t0 = time.perf_counter()
    old_ths = []
    for fam, x, dist in entries:
        t1 = time.perf_counter()
        old_ths.append(_threshold_grid_reference(key, dist, cfg, n_seeds=2))
        old_us.append((time.perf_counter() - t1) * 1e6)
    old_total = time.perf_counter() - t0

    # --- fused path: every family in ONE engine call ---------------------
    t0 = time.perf_counter()
    new_ths = threshold.threshold_grid_batch(
        key, [dist for _, _, dist in entries], cfg, n_seeds=2,
        kernel=resolved)
    new_total = time.perf_counter() - t0
    new_us = new_total * 1e6 / len(entries)

    max_delta = 0.0
    for (fam, x, _), t_old, t_new, us in zip(entries, old_ths, new_ths,
                                             old_us):
        max_delta = max(max_delta, abs(t_old - t_new))
        rows.append((f"sweep_engine/{fam}/x={x:g}", us,
                     f"old={t_old:.3f};fused={t_new:.3f};"
                     f"speedup={us / new_us:.1f}x"))
    speedup = old_total / new_total
    rows.append(("sweep_engine/total", old_total * 1e6,
                 f"old_s={old_total:.2f};fused_s={new_total:.2f};"
                 f"speedup={speedup:.1f}x;max_threshold_delta={max_delta:.4f}"))

    # --- chunked vs pre-sampled: thresholds must agree within the load
    # grid's interpolation tolerance (grid step ~0.02) ---------------------
    rhos = jnp.linspace(0.05, 0.495, 24)
    grid_step = float(rhos[1] - rhos[0])
    chunk_delta = 0.0
    for dist in (dists.exponential(), dists.pareto(2.2)):
        t0 = time.perf_counter()
        th_un = threshold.threshold_grid(key, dist, cfg, rhos=rhos,
                                         n_seeds=2, kernel=resolved)
        un_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        th_ch = threshold.threshold_grid(key, dist, cfg, rhos=rhos,
                                         n_seeds=2, chunk_size=CHUNK,
                                         kernel=resolved)
        ch_s = time.perf_counter() - t0
        chunk_delta = max(chunk_delta, abs(th_un - th_ch))
        rows.append((f"sweep_engine/chunked/{dist.name}", ch_s * 1e6,
                     f"unchunked={th_un:.3f};chunked={th_ch:.3f};"
                     f"delta={abs(th_un - th_ch):.4f};"
                     f"tol={grid_step:.3f};"
                     f"match={abs(th_un - th_ch) <= grid_step};"
                     f"unchunked_s={un_s:.2f};chunked_s={ch_s:.2f}",
                     None, _paper_provenance(dist), resolved))

    # --- streamed large-n_arrivals sweep: peak input memory is set by
    # chunk_size, not n_arrivals --------------------------------------------
    big_m = 200_000 if smoke else 2_000_000
    big_cfg = queueing.SimConfig(n_servers=20, n_arrivals=big_m)
    scn_big = Scenario.paper_default(dists.exponential(), ks=(1, 2))
    t0 = time.perf_counter()
    out = queueing.run(key, scn_big, jnp.asarray([0.3]), big_cfg,
                       n_seeds=1, chunk_size=CHUNK, kernel=resolved)
    jax.block_until_ready(out["mean"])
    big_s = time.perf_counter() - t0
    rows.append((f"sweep_engine/chunked_{big_m // 1000}k", big_s * 1e6,
                 f"chunk={CHUNK};mean_k1={float(out['mean'][0, 0, 0]):.4f};"
                 f"p99_k2={float(out['p99'][0, 0, 1]):.3f};"
                 f"input_kb_chunked={_input_bytes(big_cfg, CHUNK) // 1024};"
                 f"input_kb_presampled="
                 f"{_input_bytes(big_cfg, big_m) // 1024};"
                 f"arrivals_per_s={big_m / big_s:.0f}",
                 None, _paper_provenance(dists.exponential()), resolved))
    rows.append(("sweep_engine/chunked_total", 0.0,
                 f"max_threshold_delta={chunk_delta:.4f};"
                 f"interp_tol={grid_step:.3f}"))

    # --- fused cell-update kernel on vs off: measured speedup ------------
    rows.extend(_kernel_rows(key, cfg, kernel, smoke))

    # --- sampling/compute pipeline on vs off: measured overlap speedup --
    rows.extend(_pipeline_rows(key, kernel, smoke))

    # --- sharded cell-plan execution: bit-identity + mesh provenance ----
    if mesh is not None:
        rows.extend(_sharded_rows(key, cfg, mesh, smoke))
    return rows
