"""Sharded cell-plan executor: bit-identity with the unsharded engine.

The CRN contract (``queueing.py``) promises that for the same
``(key, chunk_size)`` the sharded and unsharded engines agree BIT FOR
BIT for any device count, because cell randomness derives from cell
coordinates, never device placement.

In-process tests run on a 1-device "cells" mesh — the full shard_map
machinery without real sharding, so they execute in every tier-1 run.
The subprocess test forces 8 host devices (the idiom of
``test_distributed_exec.py``: the XLA override must not leak into the
main test process) and checks cell counts both divisible and NOT
divisible by the device count (exercising the pad/mask path), the
dist-stacked driver, MIXED-policy scenario grids (policy/model codes
sharded as per-cell coordinates), HETEROGENEOUS mixed-dist grids (the
per-cell dist_id / svc_idx routing sharded the same way: scan ==
interpreted kernel == sharded), threshold bisection (bare dist
and Scenario forms), and the fused cell-update kernel (its per-cell
grid maps 1:1 onto the sharded axis, so kernel mode must preserve the
sharded==unsharded bit-identity too).
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import distributions as dists, queueing, threshold
from repro.core.scenario import (CANCEL_ON_COMPLETE, REPLICATE_TO_IDLE,
                                 SERVER_DEPENDENT, Scenario)
from repro.distributed import sweep_shard
from repro.launch.mesh import make_sweep_mesh

SRC = str(Path(__file__).resolve().parent.parent / "src")

CFG = queueing.SimConfig(n_servers=10, n_arrivals=6_000)
RHOS = jnp.asarray([0.1, 0.3])


def _assert_bit_identical(a, b, fields=("mean", "p50", "p99")):
    assert a["count"] == b["count"]
    for f in fields:
        assert jnp.array_equal(a[f], b[f]), f


class TestShardedSingleDeviceMesh:
    def test_chunked_bit_identical(self):
        key = jax.random.PRNGKey(0)
        kw = dict(ks=(1, 2), n_seeds=2, chunk_size=1_700)  # ragged chunks
        un = queueing.sweep(key, dists.exponential(), RHOS, CFG, **kw)
        sh = sweep_shard.sweep_sharded(key, dists.exponential(), RHOS, CFG,
                                       mesh=make_sweep_mesh(1), **kw)
        _assert_bit_identical(un, sh)

    def test_unchunked_bit_identical(self):
        key = jax.random.PRNGKey(1)
        kw = dict(ks=(1, 2), n_seeds=2)
        un = queueing.sweep(key, dists.pareto(2.5), RHOS, CFG, **kw)
        sh = sweep_shard.sweep_sharded(key, dists.pareto(2.5), RHOS, CFG,
                                       mesh=make_sweep_mesh(1), **kw)
        _assert_bit_identical(un, sh)

    def test_sweep_dists_bit_identical(self):
        key = jax.random.PRNGKey(2)
        ds = (dists.exponential(), dists.two_point(0.9))
        kw = dict(ks=(1, 2), n_seeds=2, percentiles=(), chunk_size=2_500)
        un = queueing.sweep_dists(key, ds, RHOS, CFG, **kw)
        sh = sweep_shard.sweep_dists_sharded(key, ds, RHOS, CFG,
                                             mesh=make_sweep_mesh(1), **kw)
        _assert_bit_identical(un, sh, fields=("mean",))
        assert sh["mean"].shape == (2, 2, 2, 2)

    def test_threshold_bisect_identical(self):
        key = jax.random.PRNGKey(3)
        kw = dict(iters=4, n_seeds=2, chunk_size=2_000)
        t_un = threshold.threshold_bisect(key, dists.exponential(), CFG,
                                          **kw)
        t_sh = threshold.threshold_bisect(key, dists.exponential(), CFG,
                                          mesh=make_sweep_mesh(1), **kw)
        assert t_un == t_sh

    def test_mixed_policy_grid_bit_identical(self):
        # a MIXED grid — paper cells, a cancellation cell, a
        # server-dependent cell — through run(mesh=...): the policy/model
        # codes shard with the plan, results bit-match the local engine.
        key = jax.random.PRNGKey(4)
        d = dists.exponential()
        scns = (Scenario.paper_default(d, ks=(1, 2)),
                Scenario(dists=d, policy=CANCEL_ON_COMPLETE, ks=(2,)),
                Scenario(dists=d, policy=REPLICATE_TO_IDLE, ks=(2,)),
                Scenario(dists=d, service_model=SERVER_DEPENDENT, mix=0.7,
                         ks=(2,)))
        kw = dict(n_seeds=2, chunk_size=1_700)
        un = queueing.run(key, scns, RHOS, CFG, **kw)
        sh = queueing.run(key, scns, RHOS, CFG, mesh=make_sweep_mesh(1),
                          **kw)
        _assert_bit_identical(un, sh)
        assert un["mean"].shape == (2, 2, 5)

    def test_mixed_dists_grid_bit_identical(self):
        # a HETEROGENEOUS grid — two systems via per-cell dist_id —
        # through run(mesh=...): svc_idx shards with the plan, results
        # bit-match the local engine (scan AND interpreted kernel).
        key = jax.random.PRNGKey(6)
        scns = (Scenario(dists=dists.exponential(), ks=(1, 2)),
                Scenario(dists=dists.pareto(2.5), ks=(1, 2),
                         client_overhead=0.05))
        kw = dict(n_seeds=2, chunk_size=1_700)
        un = queueing.run(key, scns, RHOS, CFG, **kw)
        sh = queueing.run(key, scns, RHOS, CFG, mesh=make_sweep_mesh(1),
                          **kw)
        _assert_bit_identical(un, sh)
        sh_kern = queueing.run(key, scns, RHOS, CFG, kernel="interpret",
                               mesh=make_sweep_mesh(1), **kw)
        _assert_bit_identical(un, sh_kern)
        assert un["mean"].shape == (2, 2, 4)

    def test_kernel_mode_bit_identical(self):
        # the fused cell-update kernel runs per shard on its local cells
        # (interpret mode on CPU): sharded kernel == unsharded kernel ==
        # unsharded scan, bit for bit
        key = jax.random.PRNGKey(5)
        scn = Scenario.paper_default(dists.exponential(), ks=(1, 2))
        kw = dict(n_seeds=2, chunk_size=1_700)
        un_scan = queueing.run(key, scn, RHOS, CFG, kernel="off", **kw)
        un_kern = queueing.run(key, scn, RHOS, CFG, kernel="interpret",
                               **kw)
        sh_kern = queueing.run(key, scn, RHOS, CFG, kernel="interpret",
                               mesh=make_sweep_mesh(1), **kw)
        _assert_bit_identical(un_scan, un_kern)
        _assert_bit_identical(un_kern, sh_kern)

    def test_rejects_wrong_mesh_axes(self):
        mesh = jax.make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="cells"):
            sweep_shard.sweep_sharded(jax.random.PRNGKey(0),
                                      dists.exponential(), RHOS, CFG,
                                      mesh=mesh)


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp

from repro.core import distributions as dists, queueing, threshold
from repro.distributed import sweep_shard
from repro.launch.mesh import make_sweep_mesh

assert jax.device_count() == 8
mesh = make_sweep_mesh(8)
cfg = queueing.SimConfig(n_servers=10, n_arrivals=5_000)
key = jax.random.PRNGKey(0)

def check(label, un, sh, fields=("mean", "p50", "p99")):
    assert un["count"] == sh["count"], label
    for f in fields:
        assert jnp.array_equal(un[f], sh[f]), (label, f)
    print(label, "bit-identical")

# divisible: 2 seeds x 2 loads x 2 ks = 8 cells on 8 devices
rhos = jnp.asarray([0.15, 0.35])
kw = dict(ks=(1, 2), n_seeds=2, chunk_size=2_000)
check("divisible",
      queueing.sweep(key, dists.exponential(), rhos, cfg, **kw),
      sweep_shard.sweep_sharded(key, dists.exponential(), rhos, cfg,
                                mesh=mesh, **kw))

# NOT divisible: 1 seed x 3 loads x 2 ks = 6 cells -> padded to 8
rhos3 = jnp.asarray([0.1, 0.25, 0.4])
kw = dict(ks=(1, 2), n_seeds=1, chunk_size=1_700)  # ragged final chunk
check("non-divisible",
      queueing.sweep(key, dists.pareto(2.5), rhos3, cfg, **kw),
      sweep_shard.sweep_sharded(key, dists.pareto(2.5), rhos3, cfg,
                                mesh=mesh, **kw))

# unchunked, non-divisible
kw = dict(ks=(1, 2), n_seeds=1)
check("unchunked",
      queueing.sweep(key, dists.two_point(0.9), rhos3, cfg, **kw),
      sweep_shard.sweep_sharded(key, dists.two_point(0.9), rhos3, cfg,
                                mesh=mesh, **kw))

# dist-stacked, non-divisible: 2 dists x 1 seed x 3 loads x 2 ks = 12 -> 16
ds = (dists.exponential(), dists.weibull(0.7))
kw = dict(ks=(1, 2), n_seeds=1, percentiles=(), chunk_size=2_000)
check("sweep_dists",
      queueing.sweep_dists(key, ds, rhos3, cfg, **kw),
      sweep_shard.sweep_dists_sharded(key, ds, rhos3, cfg, mesh=mesh,
                                      **kw),
      fields=("mean",))

# MIXED-policy grid, non-divisible: 1 seed x 3 loads x 5 variants = 15 -> 16
from repro.core.scenario import (CANCEL_ON_COMPLETE, REPLICATE_TO_IDLE,
                                 SERVER_DEPENDENT, Scenario)
d = dists.exponential()
scns = (Scenario.paper_default(d, ks=(1, 2)),
        Scenario(dists=d, policy=CANCEL_ON_COMPLETE, ks=(2,)),
        Scenario(dists=d, policy=REPLICATE_TO_IDLE, ks=(2,)),
        Scenario(dists=d, service_model=SERVER_DEPENDENT, mix=0.7,
                 ks=(2,)))
kw = dict(n_seeds=1, chunk_size=1_700)
check("mixed-policy",
      queueing.run(key, scns, rhos3, cfg, **kw),
      queueing.run(key, scns, rhos3, cfg, mesh=mesh, **kw))

# HETEROGENEOUS (mixed-dist) grid, non-divisible: two systems x
# 1 seed x 3 loads x 2 ks = 12 cells -> padded to 16. The per-cell
# svc_idx shards with the plan: scan == interpreted kernel == sharded.
het = (Scenario(dists=d, ks=(1, 2)),
       Scenario(dists=dists.pareto(2.5), ks=(1, 2), client_overhead=0.05))
het_scan = queueing.run(key, het, rhos3, cfg, **kw)
het_kern = queueing.run(key, het, rhos3, cfg, kernel="interpret", **kw)
het_sh = queueing.run(key, het, rhos3, cfg, mesh=mesh, **kw)
check("mixed-dists scan vs kernel", het_scan, het_kern)
check("mixed-dists scan vs sharded", het_scan, het_sh)

# fused cell-update kernel (interpret mode off-TPU), sharded at 8
# devices: each shard lays its own cells on the kernel's lanes, so
# sharded-kernel == unsharded-kernel == unsharded-scan bits
scn = queueing.Scenario.paper_default(dists.exponential(), ks=(1, 2))
un_scan = queueing.run(key, scn, rhos, cfg, kernel="off",
                       n_seeds=2, chunk_size=2_000)
un_kern = queueing.run(key, scn, rhos, cfg, kernel="interpret",
                       n_seeds=2, chunk_size=2_000)
sh_kern = queueing.run(key, scn, rhos, cfg, kernel="interpret",
                       mesh=mesh, n_seeds=2, chunk_size=2_000)
check("kernel unsharded-scan vs unsharded-kernel", un_scan, un_kern)
check("kernel unsharded-kernel vs sharded-kernel", un_kern, sh_kern)

# threshold bisection: every probe batch rides the sharded cell axis —
# under a Scenario too (cancellation: replication helps everywhere, so
# both paths must return the bracket's hi)
kw = dict(iters=4, n_seeds=2, chunk_size=2_000)
t_un = threshold.threshold_bisect(key, dists.exponential(), cfg, **kw)
t_sh = threshold.threshold_bisect(key, dists.exponential(), cfg,
                                  mesh=mesh, **kw)
assert t_un == t_sh, (t_un, t_sh)
scn = Scenario(dists=d, policy=CANCEL_ON_COMPLETE)
t_un = threshold.threshold_bisect(key, scn, cfg, **kw)
t_sh = threshold.threshold_bisect(key, scn, cfg, mesh=mesh, **kw)
assert t_un == t_sh, (t_un, t_sh)
print("threshold bit-identical")
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_matches_unsharded_8_devices():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-2500:])
    assert "SHARDED_OK" in out.stdout
