"""Roofline table: live cell-update kernel measurement + dry-run artifacts.

Part 1 — the fused cell-update kernel (``repro.kernels.cell_update``),
MEASURED ON THE CHIP: the analytic cost model ``cell_update_costs``
(FLOPs, HBM traffic, arithmetic intensity of one engine call) against
the timed wall clock of ``queueing.run`` with ``kernel="off"`` (scan
body) and ``kernel="on"``. Reports achieved GFLOP/s and HBM GB/s, their
fractions of the device's peaks (``PEAKS``, keyed by ``device_kind``),
the ridge intensity peak FLOP/s over peak bytes/s the kernel must beat
to leave the memory-bound regime, and the kernel-vs-scan speedup. Off
the TPU nothing is timed: the rows carry the cost model and say "not
measured". A TPU missing from ``PEAKS`` is an error, not a default.
``smoke=True`` shrinks the measured sweep.

Part 2 — dry-run artifacts (EXPERIMENTS.md §Roofline), when present.
Per (arch x shape x mesh), with the v5e peaks:
    compute term    = HLO_FLOPs / (chips x peak bf16 FLOP/s)
    memory term     = HLO_bytes / (chips x peak HBM B/s)
    collective term = collective_bytes / (chips x 50e9 B/s ICI link)
(the dry-run JSON stores PER-DEVICE flops/bytes — chips divide out).
Also reports MODEL_FLOPS = 6*N(_active)*D and the usefulness ratio.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

from benchmarks.common import Row

# Published per-chip peaks, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}
V5E = PEAKS["TPU v5 lite"]
LINK_BW = 50e9

_ROOT = Path(__file__).resolve().parent.parent
# prefer the optimized sweep; fall back to the baseline
DRYRUN_DIR = (_ROOT / "experiments/dryrun_opt"
              if (_ROOT / "experiments/dryrun_opt").exists()
              else _ROOT / "experiments/dryrun")


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


def analyze_record(rec: dict) -> dict | None:
    if not rec.get("ok") or "scaled_flops" not in rec:
        return None
    from repro.configs import base as cfgbase
    cfg = cfgbase.get_config(rec["arch"])
    shape = cfgbase.SHAPES[rec["shape"]]
    devices = rec["devices"]
    # per-device terms (JSON values are per-device already)
    t_compute = rec["scaled_flops"] / V5E["flops"]
    t_memory = rec["scaled_io_bytes"] / V5E["hbm_bw"]
    coll = sum(rec.get("collective_bytes", {}).values())
    t_coll = coll / LINK_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda x: x[1])[0]
    # model flops for this step kind
    n_params = (cfg.active_param_count if cfg.moe else cfg.param_count)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_params * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_params * tokens
    else:  # decode: one token per sequence
        model_flops = 2 * n_params * shape.global_batch
    model_flops_dev = model_flops / devices
    useful = model_flops_dev / max(rec["scaled_flops"], 1.0)
    return {
        "t_compute": t_compute, "t_memory": t_memory, "t_coll": t_coll,
        "dominant": dominant, "useful_ratio": useful,
        "model_flops_per_dev": model_flops_dev,
        "hbm_bytes_per_dev": rec.get("temp_size_in_bytes", 0),
    }


def _cell_update_rows(smoke: bool) -> list[Row]:
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


def run(smoke: bool = False) -> list[Row]:
    rows: list[Row] = _cell_update_rows(smoke)
    if not DRYRUN_DIR.exists():
        return rows + [("roofline/dryrun_missing", 0.0,
                        "run repro.launch.dryrun first")]
    for f in sorted(DRYRUN_DIR.glob("*.json")):
        rec = json.loads(f.read_text())
        a = analyze_record(rec)
        if a is None:
            rows.append((f"roofline/{f.stem}", 0.0,
                         f"SKIP({rec.get('error', 'no analysis')})"))
            continue
        rows.append((
            f"roofline/{f.stem}", rec.get("compile_s", 0) * 1e6,
            f"compute_s={a['t_compute']:.3e};memory_s={a['t_memory']:.3e};"
            f"collective_s={a['t_coll']:.3e};dominant={a['dominant']};"
            f"useful={a['useful_ratio']:.2f}"))
    return rows
