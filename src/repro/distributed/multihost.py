"""Multi-process launch layer for the sweep engine.

``initialize`` wires one process of a multi-host run into the jax
distributed runtime (``jax.distributed.initialize`` with the gloo CPU
collectives backend), then installs the all-processes ``"cells"`` sweep
mesh as the ambient default (``repro.launch.mesh.set_default_sweep_mesh``)
— so a worker's plain ``queueing.run(...)`` call, with no ``mesh=``
anywhere, executes sharded across every host. On CPU each process gets
``local_device_count`` virtual devices via
``XLA_FLAGS=--xla_force_host_platform_device_count`` (set here if the
caller has not, BEFORE jax backends initialize), which is how CI
exercises the real multi-process code path on one machine: 2 spawned
subprocesses x 4 virtual devices against a single-process 8-device
reference, bit-identical (tests/test_multihost.py).

The other half of this module is the single cross-process gather of the
sweep: ``fetch_replicated`` jits an identity function with REPLICATED
output shardings, which makes XLA insert the all-gather that turns the
executor's cell-sharded summaries into arrays every process holds in
full — the one collective of the whole engine (see the design note in
``repro.distributed.sweep_shard``).
"""
from __future__ import annotations

import os
from functools import lru_cache

import numpy as np

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               local_device_count: int | None = None, *,
               set_default_mesh: bool = True) -> bool:
    """Join a multi-process jax runtime; returns True if one was joined.

    No-op (returns False) when ``num_processes`` is None or <= 1, so a
    launcher script can call this unconditionally and fall through to
    plain single-process execution. Must run before anything touches jax
    device state: ``local_device_count`` is applied through ``XLA_FLAGS``
    (ignored if the flag is already set — e.g. by the test harness) and
    the CPU collectives implementation is switched to gloo, both of
    which only take effect before backend initialization.

    With ``set_default_mesh`` (the default), the all-devices sweep mesh
    becomes the process-wide ambient default — every subsequent
    ``queueing.run`` resolves to it (``launch.mesh.resolve_mesh``) and
    executes sharded across all processes' devices.
    """
    if num_processes is None or int(num_processes) <= 1:
        return False
    if local_device_count is not None:
        if _FORCE_FLAG not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" {_FORCE_FLAG}={int(local_device_count)}").strip()
        # Virtual host devices only exist on the CPU backend. Pin the
        # platform too: with jax.distributed active, an installed
        # libtpu otherwise tries to initialize a TPU pod runtime (and
        # hangs >60s on TPU_WORKER_HOSTNAMES before aborting the
        # process) instead of quietly falling back to CPU.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    # CPU collectives: gloo (the only CPU backend with cross-host
    # all-gather support)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes), process_id=int(process_id))
    if set_default_mesh:
        from repro.launch import mesh as mesh_mod

        mesh_mod.set_default_sweep_mesh(mesh_mod.make_sweep_mesh())
    return True


def is_initialized() -> bool:
    import jax

    return jax.process_count() > 1


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def is_coordinator() -> bool:
    return process_index() == 0


def spans_processes(mesh) -> bool:
    """True when the mesh's devices live on more than one process —
    i.e. when finalization needs the cross-process gather and host-side
    ``np.asarray`` on a sharded array would fail (non-addressable
    shards)."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


@lru_cache(maxsize=None)
def _gather_fn(mesh, n: int):
    """Jitted identity with fully REPLICATED out_shardings: running it on
    cell-sharded arrays makes XLA emit the all-gather that assembles the
    global value on every process. Cached per (mesh, arity) — ONE
    compiled collective reused by every chunk-streamed sweep on the
    mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    return jax.jit(lambda *xs: tuple(xs), out_shardings=(rep,) * n)


def fetch_replicated(mesh, *xs) -> tuple[np.ndarray, ...]:
    """Gather cell-sharded arrays to full host copies on EVERY process
    (the sweep's single collective). Returns numpy arrays read from the
    first addressable shard — after replication, any shard is the whole
    value."""
    out = _gather_fn(mesh, len(xs))(*xs)
    return tuple(np.asarray(o.addressable_data(0)) for o in out)
