"""Cross-system crossover: disk-backed DB vs memcached vs DNS on ONE
mixed grid.

The paper's three measured systems answer the same question at three
points of the service-time spectrum: where is the load threshold below
which replication helps? Here each system is fitted once into a
unit-mean quantile-table ``EmpiricalDist`` (storage and memcached via
``storage_sim.empirical_service_dist``, DNS via the k=1 fit of
``dns.empirical_k_dists``) and all three ride ONE
``threshold.scenario_gain`` engine call as a heterogeneous mixed grid —
"which system" is the per-cell ``dist_id`` coordinate, so the three
help/hurt curves come out of a single compiled sweep, CRN-paired within
each system. ``threshold.crossing_load`` reads each system's crossover
off its gain column, and the summary row orders them: heavy-tailed disk
crosses latest, overhead-dominated memcached earliest.

A parity row re-runs the (smoke-sized) grid through the interpreted
Pallas cell-update kernel and records bit-identity with the scan body —
the mixed-grid analogue of ``sweep_engine/kernel_on_vs_off``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import Row, timed
from repro.core import dns, queueing, scenario as scn_mod, storage_sim, \
    threshold
from repro.core.scenario import Scenario
from repro.kernels.cell_update import kernel_path_mode, resolve_kernel_mode

SYSTEMS = ("disk", "memcached", "dns")


def _fits():
    """(dist, ms_scale, overhead) per system, fitted once."""
    disk = storage_sim.empirical_service_dist(storage_sim.StorageConfig())
    mem = storage_sim.empirical_service_dist(storage_sim.MEMCACHED)
    d = dns.empirical_k_dists(jax.random.PRNGKey(6), dns.DNSPopulation(),
                              ks=(1,))[0]
    # replicating a DNS query costs one extra ~0.5 KB packet, not a
    # client-side protocol handshake: no overhead term.
    return [disk, mem, (d, d.scale, 0.0)]


def run(smoke: bool = False, mesh=None, kernel: str = "auto") -> list[Row]:
    rows: list[Row] = []
    key = jax.random.PRNGKey(8)
    resolved = resolve_kernel_mode(kernel)
    mesh_shape = tuple(mesh.devices.shape) if mesh is not None else None
    fits = _fits()
    scns = tuple(Scenario(dists=dist, ks=(1, 2), client_overhead=ovh)
                 for dist, _, ovh in fits)
    cfg = queueing.SimConfig(n_servers=20,
                             n_arrivals=4_000 if smoke else 60_000)
    rhos = jnp.linspace(0.05, 0.495, 8 if smoke else 24)

    def work():
        # ONE engine call, three systems: gain matrix (B, 3)
        return threshold.scenario_gain(key, scns, rhos, cfg, n_seeds=2,
                                       mesh=mesh, kernel=resolved)

    g, us = timed(work)
    crossings = {}
    for i, name in enumerate(SYSTEMS):
        dist, ms_scale, ovh = fits[i]
        t = threshold.crossing_load(rhos, g[:, i])
        crossings[name] = t
        g_lo, g_hi = float(g[0, i]) * ms_scale, float(g[-1, i]) * ms_scale
        rows.append((f"fig_cross_system/{name}", us / len(SYSTEMS),
                     f"crossover_load={t:.3f};"
                     f"gain@{float(rhos[0]):.2f}={g_lo:.4f}ms;"
                     f"gain@{float(rhos[-1]):.2f}={g_hi:.4f}ms;"
                     f"mean_service_ms={ms_scale:.3f};"
                     f"overhead_frac={ovh:.3f}",
                     mesh_shape, scn_mod.provenance(scns[i]), resolved))
    order = sorted(crossings, key=crossings.get, reverse=True)
    rows.append(("fig_cross_system/crossover", us,
                 ";".join(f"{n}={crossings[n]:.3f}" for n in order)
                 + f";order={'>'.join(order)};"
                 f"rho_grid=[{float(rhos[0]):.2f},{float(rhos[-1]):.2f}]"
                 f"x{rhos.shape[0]}",
                 mesh_shape, scn_mod.provenance(scns), resolved))

    # scan-vs-kernel parity on the mixed grid (interpreted off-TPU so a
    # kernel-path measurement always exists); smoke-sized — parity is a
    # contract check, not a timing row.
    mode = resolved if resolved != "off" else kernel_path_mode()
    pcfg = queueing.SimConfig(n_servers=20, n_arrivals=2_000)
    prhos = jnp.asarray([0.1, 0.3])
    off = queueing.run(key, scns, prhos, pcfg, n_seeds=1, kernel="off")
    on, kus = timed(lambda: queueing.run(key, scns, prhos, pcfg,
                                         n_seeds=1, kernel=mode))
    bit = all(bool(jnp.array_equal(off[f], on[f]))
              for f in ("mean", "p50", "p99"))
    rows.append(("fig_cross_system/kernel_parity", kus,
                 f"kernel={mode};bit_identical={bit};"
                 f"cells={prhos.shape[0] * 2 * len(scns)};"
                 f"arrivals={pcfg.n_arrivals}",
                 None, scn_mod.provenance(scns), mode))
    return rows
