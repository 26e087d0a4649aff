"""Chip smoke test: the sweep engine and the hedged-serving path on a TPU.

Run from the repository root of a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # only the sharded cells-mesh phase

One chip. The paper's deployment (20 servers; exponential and
Pareto(2.1) service; k in {1, 2}; 16 loads in 0.05-0.45; 4 seeds, so
256 cells) goes through ``queueing.run`` with 2**20 arrivals streamed in
chunks of 2**16 (the sampling pipeline is on) and the default
percentiles, once with the compiled Pallas kernel (``kernel="on"``) and
once with the scan body (``kernel="off"``). The two must agree (means
to 1e-5 relative, percentiles to one log bin; whether they are
bit-identical is printed), and the exponential cells must match the
closed forms of ``core/analytic.py`` with the golden tests' tolerances.
Then a policy table is swept on the chip, loaded into an
``AdaptiveController`` that steers a ``BatchedHedgedService`` over four
``SimulatedEngine`` replicas, and a 500-request Poisson trace is
replayed open loop: every request must complete and none may fail.

Four chips (``--four-chips``): the same grid unsharded on chip 0 against
``queueing.run(mesh=make_sweep_mesh(4))`` with the kernel on under
``shard_map``, held to the same agreement; every device must hold cells.

Each phase prints one line: compile seconds (JAX's backend-compile
events) and how many programs were compiled, steady seconds (the
phase's wall time less its compile seconds — an estimate: a compile on
the sampling pipeline's producer thread overlaps device work),
copy-steps per second over the steady seconds (a copy-step is one copy
of one arrival in one cell) and the resolved kernel mode. The
last line is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any phase or check fails, the script exits
non-zero and prints no such line. It runs in one process and starts no
other.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_SERVERS = 20
KS = (1, 2)
PARETO_ALPHA = 2.1
RHO_RANGE = (0.05, 0.45)
N_LOADS = 16
N_SEEDS = 4
N_ARRIVALS = 1 << 20
CHUNK = 1 << 16
SEED = 0
# closed-form tolerances of tests/test_analytic_golden.py
MEAN_K1_REL, P99_K1_REL, MEAN_K2_REL, K2_MAX_RHO = 0.02, 0.05, 0.05, 0.25
# kernel-vs-scan agreement
MEAN_AGREE_REL = 1e-5
# serving phase: four replicas serving the two-point law of the
# adaptive-serving benchmark at a 10 ms mean
N_REPLICAS = 4
SERVICE_P = 0.9
MEAN_SERVICE_S = 0.01
TABLE_RHOS = (0.05, 0.15, 0.25, 0.35, 0.45, 0.55, 0.65, 0.8)
TABLE_DELAYS = (0.0, 0.5, 1.0, 2.0)
TABLE_ARRIVALS = 1 << 16
N_REQUESTS = 500
TRACE_RHO = 0.3


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


class CompileClock:
    """Sums the seconds of, and counts, the XLA backend compiles JAX
    reports through its monitoring events (one event per compiled
    program; tracing is not counted, since nested traces report inside
    their parent's)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.compiles += 1

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.compiles


def timed_phase(clock: CompileClock, fn):
    """Run ``fn`` (which must return host values); returns its result
    and (wall, compile, steady, n_compiles) seconds/counts."""
    c0, n0 = clock.mark()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    c1, n1 = clock.mark()
    return out, wall, c1 - c0, wall - (c1 - c0), n1 - n0


def paper_grid():
    import jax.numpy as jnp

    from repro.core import distributions as dists, queueing
    from repro.core.scenario import Scenario

    scn = Scenario.paper_default(
        (dists.exponential(), dists.pareto(PARETO_ALPHA)), ks=KS)
    rhos = jnp.linspace(*RHO_RANGE, N_LOADS)
    cfg = queueing.SimConfig(n_servers=N_SERVERS, n_arrivals=N_ARRIVALS)
    return scn, rhos, cfg


def engine_phase(name: str, clock: CompileClock, kernel: str, mesh=None):
    """One ``queueing.run`` of the paper grid; prints the phase line and
    returns the summaries as numpy arrays."""
    import jax
    import numpy as np

    from repro.core import chunkflow, queueing
    from repro.kernels.cell_update import resolve_kernel_mode

    scn, rhos, cfg = paper_grid()

    def call():
        out = queueing.run(jax.random.PRNGKey(SEED), scn, rhos, cfg,
                           n_seeds=N_SEEDS, chunk_size=CHUNK,
                           kernel=kernel, mesh=mesh)
        return {k: np.asarray(v) for k, v in out.items()}

    out, wall, comp, steady, n_comp = timed_phase(clock, call)
    stats = chunkflow.last_stats()
    n_rows = len(scn.dists) * N_SEEDS
    copy_steps = N_ARRIVALS * n_rows * N_LOADS * sum(KS)
    mode = resolve_kernel_mode(kernel, n_bins=queueing.DEFAULT_BINS)
    print(f"phase={name} kernel={mode} cells={n_rows * N_LOADS * len(KS)} "
          f"arrivals={N_ARRIVALS} chunk={CHUNK} "
          f"pipeline={'on' if stats and stats.enabled else 'off'} "
          f"devices={1 if mesh is None else mesh.devices.size} "
          f"wall_s={wall:.3f} compile_s={comp:.3f} compiles={n_comp} "
          f"steady_s={steady:.3f} "
          f"copy_steps_per_s={copy_steps / steady:.4e}",
          flush=True)
    return out


def check_agree(a: dict, b: dict, label: str) -> bool:
    """Means within MEAN_AGREE_REL, percentiles within one log bin;
    returns whether every summary is bit-identical."""
    import numpy as np

    from repro.core import queueing

    rel = np.max(np.abs(a["mean"] - b["mean"]) / np.abs(b["mean"]))
    if not rel <= MEAN_AGREE_REL:
        fail(f"{label}: means differ by {rel:.3e} relative "
             f"(limit {MEAN_AGREE_REL})")
    log_bin = (math.log(queueing.HIST_HI) - math.log(queueing.HIST_LO)) / (
        queueing.DEFAULT_BINS - 1)
    worst = 0.0
    keys = sorted(k for k in a if k.startswith("p"))
    for k in keys:
        worst = max(worst, float(np.max(np.abs(np.log(a[k] / b[k])))))
    if not worst <= log_bin * 1.001:
        fail(f"{label}: percentiles differ by {worst:.3e} in log "
             f"(one bin is {log_bin:.3e})")
    identical = all(np.array_equal(a[k], b[k]) for k in ["mean", *keys])
    print(f"check={label} mean_max_rel={rel:.3e} "
          f"pct_max_log={worst:.3e} log_bin={log_bin:.3e} "
          f"bit_identical={identical}", flush=True)
    return identical


def check_analytic(out: dict, label: str) -> None:
    """Exponential cells (dist 0) against core/analytic.py."""
    import numpy as np

    from repro.core import analytic

    _, rhos, _ = paper_grid()
    rhos = np.asarray(rhos)
    exp = {k: np.asarray(v)[0].mean(axis=0) for k, v in out.items()
           if k == "mean" or k == "p99"}            # (B, K) seed means
    k1_mean = np.asarray(analytic.mm1_mean(rhos))
    k1_p99 = np.log(100.0) / (1.0 - rhos)
    k2_mean = np.asarray(analytic.mm1_replicated_mean(rhos, 2))
    err_mean = np.abs(exp["mean"][:, 0] / k1_mean - 1.0)
    err_p99 = np.abs(exp["p99"][:, 0] / k1_p99 - 1.0)
    low = rhos <= K2_MAX_RHO
    err_k2 = np.abs(exp["mean"][low, 1] / k2_mean[low] - 1.0)
    for name, err, lim in (("k1_mean", err_mean, MEAN_K1_REL),
                           ("k1_p99", err_p99, P99_K1_REL),
                           ("k2_mean", err_k2, MEAN_K2_REL)):
        if not np.all(err <= lim):
            fail(f"{label}: exponential {name} off the closed form by "
                 f"{float(err.max()):.4f} relative (limit {lim})")
    print(f"check={label}_analytic k1_mean_max_rel={err_mean.max():.4f} "
          f"k1_p99_max_rel={err_p99.max():.4f} "
          f"k2_mean_max_rel={err_k2.max():.4f} "
          f"k2_loads={int(low.sum())}", flush=True)


def serving_phase(clock: CompileClock) -> None:
    """Policy table swept on the chip -> AdaptiveController ->
    BatchedHedgedService over SimulatedEngine replicas, open-loop
    Poisson replay."""
    import jax
    import numpy as np

    from repro.core import distributions as dists, queueing, threshold
    from repro.serving import replay
    from repro.serving.controller import AdaptiveController, PolicyTable
    from repro.serving.engine import SimulatedEngine
    from repro.serving.metrics import Telemetry
    from repro.serving.service import BatchedHedgedService

    cfg = queueing.SimConfig(n_servers=N_REPLICAS, n_arrivals=TABLE_ARRIVALS)
    sweep, wall, comp, steady, n_comp = timed_phase(
        clock, lambda: threshold.policy_table(
            jax.random.PRNGKey(SEED), dists.two_point(SERVICE_P), cfg,
            rhos=list(TABLE_RHOS), ks=KS, delays=TABLE_DELAYS,
            percentile=99.0, n_seeds=2, kernel="on"))
    table = PolicyTable.from_sweep(sweep)
    if not np.all(np.isfinite(table.tail)) or not np.all(table.tail > 0):
        fail("policy table holds non-finite or non-positive tails")
    n_cells = 2 * len(TABLE_RHOS) * table.n_variants
    print(f"phase=policy_table kernel=on cells={n_cells} "
          f"arrivals={TABLE_ARRIVALS} wall_s={wall:.3f} "
          f"compile_s={comp:.3f} compiles={n_comp} steady_s={steady:.3f} "
          f"best@0.15={table.entry(table.best(0.15))} "
          f"best@0.65={table.entry(table.best(0.65))}", flush=True)

    hi = (1.0 - 0.5 * SERVICE_P) / (1.0 - SERVICE_P)
    engines = []
    for i in range(N_REPLICAS):
        rng = np.random.default_rng(SEED + 1 + i)
        engines.append(SimulatedEngine(
            lambda rng=rng: MEAN_SERVICE_S * (
                0.5 if rng.random() < SERVICE_P else hi), name=f"s{i}"))
    ctl = AdaptiveController(table, N_REPLICAS,
                             mean_service_s=MEAN_SERVICE_S,
                             decision_stride=8, initial_rho=TRACE_RHO)
    svc = BatchedHedgedService(engines, batch_sizes=(1, 4), max_seq=8,
                               controller=ctl,
                               telemetry=Telemetry(window_s=0.5), seed=SEED)
    trace = replay.poisson_trace(N_REQUESTS, rho=TRACE_RHO,
                                 n_replicas=N_REPLICAS,
                                 mean_service_s=MEAN_SERVICE_S, seed=SEED)
    t0 = time.perf_counter()
    try:
        reqs = replay.replay_live(svc, trace, max_new_tokens=2)
    finally:
        svc.shutdown()
    wall = time.perf_counter() - t0
    done = sum(r.done_event.is_set() for r in reqs)
    failed = sum(r.failed for r in reqs)
    tel = svc.telemetry.provenance()
    if done != N_REQUESTS or failed or svc.stats["failed"]:
        fail(f"serving: {done}/{N_REQUESTS} completed, {failed} failed")
    ctl_p = ctl.provenance()
    print(f"phase=serving requests={N_REQUESTS} completed={done} "
          f"failed={failed} hedged={svc.stats['hedged']} "
          f"decisions={ctl_p['decisions']} k_range={ctl_p['k_min']}-"
          f"{ctl_p['k_max']} p50_ms={tel['p50'] * 1e3:.3f} "
          f"p99_ms={tel['p99'] * 1e3:.3f} wall_s={wall:.3f}", flush=True)


def cells_on_every_device(mesh) -> None:
    """The engine's own placement rules put real cells on each device."""
    import numpy as np

    from repro.core import cellplan

    scn, rhos, _ = paper_grid()
    plan = cellplan.make_cell_plan(len(scn.dists) * N_SEEDS, N_LOADS,
                                   len(KS), pad_to=mesh.devices.size)
    placed = plan.sharding_rule(mesh).put_cells(np.asarray(plan.valid))
    per_device = {s.device: int(np.asarray(s.data).sum())
                  for s in placed.addressable_shards}
    if len(per_device) != mesh.devices.size or min(per_device.values()) < 1:
        fail(f"cells per device: {per_device}")
    print("check=placement cells_per_device="
          + ",".join(str(v) for v in per_device.values()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-unsharded phase on a "
                         "four-chip mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform={dev.platform!r}")
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    clock = CompileClock()

    if args.four_chips:
        if len(devices) != 4:
            fail(f"--four-chips needs 4 devices, found {len(devices)}")
        from repro.launch.mesh import make_sweep_mesh

        mesh = make_sweep_mesh(4)
        cells_on_every_device(mesh)
        single = engine_phase("engine_chip0", clock, "on")
        sharded = engine_phase("engine_mesh4", clock, "on", mesh=mesh)
        check_agree(sharded, single, "mesh4_vs_chip0")
    else:
        on = engine_phase("engine_kernel_on", clock, "on")
        off = engine_phase("engine_kernel_off", clock, "off")
        check_agree(on, off, "kernel_on_vs_off")
        check_analytic(on, "kernel_on")
        check_analytic(off, "kernel_off")
        serving_phase(clock)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
