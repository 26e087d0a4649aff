"""The engine's Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: the TPU compiler that ships with libtpu compiles for a
DESCRIBED v5e chip (``topologies.get_topology_desc``), which refuses
what interpret mode accepts — blocks whose two minor dims are neither
(8, 128)-aligned nor the whole array, value-level dynamic slices, too
much VMEM. The widths are the one-chip smoke run's: 256 cells of 20
servers, k_max = 2, 8 seed rows (2 service laws x 4 seeds), 2048
sketch bins, 512-step blocks, a 65536-step chunk.

The topology is described inside a module fixture, never while the
module is imported: only one process at a time may load the TPU
library, and under pytest-xdist only the worker given this file should.
The persistent compilation cache is off around the compiles — an entry
compiled for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cell_update.kernel import cell_update_tc
from repro.kernels.hist_sketch.kernel import hist_accum_tc

C, N, K, ROWS, T, BINS, BLOCK = 256, 20, 2, 8, 65536, 2048, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _cell_shapes(sharding, *, n_svc: int, n_bins: int, n_svc_rows: int,
                 dists: bool):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    per_cell_f32 = [s((C,)) for _ in range(7)]   # rates .. delay
    shapes = [s((C, N)), s((C,)), s((C,)), s((C,)),
              s((C, n_bins)) if n_bins else s((0, 0)),
              s((ROWS, T)), s((T,)), s((T,)),
              s((ROWS, T, K), jnp.int32), s((n_svc_rows, T, n_svc)),
              *(s((C,), jnp.int32) for _ in range(4)),  # seed .. model
              *per_cell_f32]
    if dists:
        shapes.append(s((C,), jnp.int32))              # svc_idx
    return shapes


def test_hist_accum_compiles(one_chip):
    idx = jax.ShapeDtypeStruct((T, C), jnp.int32, sharding=one_chip)
    _compile(lambda i: hist_accum_tc(i, n_bins=BINS, block_t=BLOCK), idx)


@pytest.mark.parametrize("layout", [
    # (n_svc, sketch bins, service-table rows, has_dists, has_shared)
    pytest.param((K, BINS, ROWS, False, False), id="sketch_on"),
    pytest.param((K, 0, ROWS, False, False), id="sketch_off"),
    pytest.param((K, BINS, 2 * ROWS, True, False), id="has_dists"),
    # timed-policy fault grids: k_max degradation uniforms per copy
    pytest.param((2 * K, BINS, ROWS, False, False), id="timed_degraded"),
    pytest.param((2 * K + 1, BINS, ROWS, False, True), id="shared_degraded"),
])
def test_cell_update_compiles(one_chip, layout):
    n_svc, n_bins, n_svc_rows, has_dists, has_shared = layout
    shapes = _cell_shapes(one_chip, n_svc=n_svc, n_bins=n_bins,
                          n_svc_rows=n_svc_rows, dists=has_dists)
    _compile(lambda *a: cell_update_tc(
        *a, n_servers=N, n_bins=n_bins or BINS, block_t=BLOCK,
        has_shared=has_shared, has_dists=has_dists), *shapes)
