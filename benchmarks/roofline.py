"""Roofline table of the fused cell-update kernel, measured on the chip.

The kernel (``repro.kernels.cell_update``): the analytic cost model
``cell_update_costs`` (FLOPs, HBM traffic, arithmetic intensity of one
engine call) against the timed wall clock of ``queueing.run`` with
``kernel="off"`` (scan body) and ``kernel="on"``. Reports achieved
GFLOP/s and HBM GB/s, their fractions of the device's peaks (``PEAKS``,
keyed by ``device_kind``), the ridge intensity peak FLOP/s over peak
bytes/s the kernel must beat to leave the memory-bound regime, and the
kernel-vs-scan speedup. Off the TPU nothing is timed: the rows carry the
cost model and say "not measured". A TPU missing from ``PEAKS`` is an
error, not a default. ``smoke=True`` shrinks the measured sweep.
"""
from __future__ import annotations

import time

from benchmarks.common import Row

# Published per-chip peaks, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}


def device_peaks(device) -> dict[str, float]:
    """Peaks of a TPU ``device`` from ``PEAKS``; raises for a kind the
    table does not hold."""
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device.device_kind!r}; "
            f"add it to benchmarks.roofline.PEAKS with its source"
        ) from None


def run(smoke: bool = False) -> list[Row]:
    """Measured roofline of the fused cell-update kernel vs the scan body.

    One row per path (scan / kernel): wall clock, analytic FLOPs and
    HBM bytes from ``cell_update_costs``, achieved GFLOP/s and GB/s
    with their peak fractions, plus a summary row with the measured
    speedup and the ridge intensity. Timings are steady-state (one
    warmup call compiles, the timed call reuses the jit cache). Off
    the TPU the rows hold the cost model only."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributions as dists, queueing
    from repro.core.scenario import Scenario
    from repro.kernels.cell_update import cell_update_costs

    n_arrivals = 5_000 if smoke else 20_000
    n_seeds, chunk = 2, 4_096
    cfg = queueing.SimConfig(n_servers=20, n_arrivals=n_arrivals)
    scn = Scenario.paper_default(dists.exponential(), ks=(1, 2))
    rhos = jnp.linspace(0.1, 0.4, 3)
    key = jax.random.PRNGKey(3)
    costs = cell_update_costs(
        n_cells=n_seeds * rhos.shape[0] * 2, n_servers=cfg.n_servers,
        k_max=2, n_arrivals=n_arrivals, n_bins=queueing.DEFAULT_BINS,
        n_seeds=n_seeds, chunk=chunk)
    model = (f"flops={costs['flops']:.3e};"
             f"hbm_bytes={costs['hbm_bytes']:.3e};"
             f"intensity={costs['intensity']:.1f}")

    device = jax.devices()[0]
    if device.platform != "tpu":
        return [(f"roofline/cell_update/{label}", 0.0,
                 f"{model};achieved=not measured (platform="
                 f"{device.platform})", None, None, mode)
                for label, mode in (("scan", "off"), ("kernel", "on"))]
    peaks = device_peaks(device)
    ridge = peaks["flops"] / peaks["hbm_bw"]
    rows: list[Row] = []
    secs = {}
    for label, mode in (("scan", "off"), ("kernel", "on")):
        def call():
            out = queueing.run(key, scn, rhos, cfg, n_seeds=n_seeds,
                               chunk_size=chunk, kernel=mode)
            jax.block_until_ready(out["mean"])
        call()  # warmup: compile outside the timed call
        t0 = time.perf_counter()
        call()
        s = time.perf_counter() - t0
        secs[label] = s
        gflops = costs["flops"] / s / 1e9
        gbs = costs["hbm_bytes"] / s / 1e9
        rows.append((f"roofline/cell_update/{label}", s * 1e6,
                     f"kernel={mode};device_kind={device.device_kind};"
                     f"{model};achieved_gflops={gflops:.2f};"
                     f"peak_frac={gflops * 1e9 / peaks['flops']:.2e};"
                     f"achieved_gbs={gbs:.2f};"
                     f"hbm_frac={gbs * 1e9 / peaks['hbm_bw']:.2e}",
                     None, None, mode))
    rows.append(("roofline/cell_update/summary", secs["kernel"] * 1e6,
                 f"kernel=on;intensity={costs['intensity']:.1f};"
                 f"ridge={ridge:.1f};"
                 f"compute_bound={costs['intensity'] > ridge};"
                 f"scan_s={secs['scan']:.2f};kernel_s={secs['kernel']:.2f};"
                 f"speedup={secs['scan'] / secs['kernel']:.2f}x",
                 None, None, "on"))
    return rows

