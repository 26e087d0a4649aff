"""The engine's Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: the TPU compiler that ships with libtpu compiles for a
DESCRIBED v5e chip (``topologies.get_topology_desc``), which refuses
what interpret mode accepts — blocks whose two minor dims are neither
(8, 128)-aligned nor the whole array, value-level dynamic slices, too
much VMEM. The widths are the one-chip smoke run's: 256 cells of 20
servers, k_max = 2, 8 seed rows (2 service laws x 4 seeds), 2048
sketch bins, 512-step blocks, a 65536-step chunk; the cell-update
kernel also at 288 and 1030 cells, its other lane-block shapes, and
with the fault-masking cell's copy budget of three.

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

from repro.kernels.cell_update.ops import cell_update
from repro.kernels.hist_sketch import ops as hist_sketch_ops
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


def _cell_shapes(sharding, *, n_cells: int, n_svc: int, n_bins: int,
                 n_svc_rows: int, dists: bool, k_max: int):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    c = n_cells
    shapes = [s((c, N)), s((c,)), s((c,)), s((c,)),
              s((c, n_bins)) if n_bins else s((0, 0)),
              s((ROWS, T)), s((T,)), s((T,)),
              s((ROWS, T, k_max), jnp.int32), s((n_svc_rows, T, n_svc)),
              s((c,), jnp.int32), s((c,)),               # seed, rates
              s((c, k_max), jnp.bool_), s((c,)),         # k_mask, ovh
              s((c,), jnp.int32), s((c,), jnp.int32),    # policy, model
              *(s((c,)) for _ in range(5))]              # mix .. delay
    if dists:
        shapes.append(s((c,), jnp.int32))              # svc_idx
    return shapes


def test_hist_accum_compiles(one_chip):
    idx = jax.ShapeDtypeStruct((T, C), jnp.int32, sharding=one_chip)
    _compile(lambda i: hist_accum_tc(i, n_bins=BINS, block_t=BLOCK), idx)


LAYOUTS = [
    # (n_svc, sketch bins, service-table rows, has_dists, has_shared,
    #  has_timed, k_max)
    pytest.param((K, BINS, ROWS, False, False, False, K), id="sketch_on"),
    pytest.param((K, 0, ROWS, False, False, False, K), id="sketch_off"),
    pytest.param((K, BINS, 2 * ROWS, True, False, False, K),
                 id="has_dists"),
    # timed-policy fault grids: k_max degradation uniforms per copy
    pytest.param((2 * K, BINS, ROWS, False, False, True, K),
                 id="timed_degraded"),
    pytest.param((2 * K + 1, BINS, ROWS, False, True, True, K),
                 id="shared_degraded"),
    # the fault-masking cell's: a three-attempt retry budget
    pytest.param((2 * 3, BINS, ROWS, False, False, True, 3),
                 id="timed_degraded_k3"),
]


def _compile_cell_update(sharding, layout, n_cells, monkeypatch):
    """The kernel path of the chunk body: the wrapper's gather onto the
    lanes, the kernel, and the histogram fold (whose dispatch asks
    ``on_tpu``, which sees this host's CPU, so it is steered here)."""
    monkeypatch.setattr(hist_sketch_ops, "on_tpu", lambda: True)
    (n_svc, n_bins, n_svc_rows, has_dists, has_shared, has_timed,
     k_max) = layout
    shapes = _cell_shapes(sharding, n_cells=n_cells, n_svc=n_svc,
                          n_bins=n_bins, n_svc_rows=n_svc_rows,
                          dists=has_dists, k_max=k_max)
    _compile(lambda *a: cell_update(
        *a, n_servers=N, n_bins=n_bins or BINS, block=BLOCK,
        has_shared=has_shared, has_timed=has_timed,
        has_dists=has_dists), *shapes)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_cell_update_compiles(one_chip, layout, monkeypatch):
    _compile_cell_update(one_chip, layout, C, monkeypatch)


# 288 cells: three sublane rows in one cell block; 1030: two cell
# blocks of eight rows, the last mostly padding
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("n_cells", [288, 1030])
def test_cell_update_compiles_lane_blocks(one_chip, layout, n_cells,
                                          monkeypatch):
    _compile_cell_update(one_chip, layout, n_cells, monkeypatch)
