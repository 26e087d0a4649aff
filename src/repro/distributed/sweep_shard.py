"""Sharded cell-plan execution layer for the chunked sweep engine.

``run_sharded`` (the mesh path of ``repro.core.queueing.run``) and the
legacy shims ``sweep_sharded`` / ``sweep_dists_sharded`` are drop-in,
BIT-IDENTICAL replacements for the unsharded engine that run its
per-chunk scan body under ``shard_map`` over a 1-D ``"cells"`` device
mesh (``repro.launch.mesh.make_sweep_mesh``). The
(seed x load x variant) grid — dist-stacked along the seed axis, with
each variant's scenario policy/model codes riding the plan as per-cell
coordinates, so MIXED-policy grids shard like any other — is flattened
by ``repro.core.cellplan`` into one cell axis padded to a multiple of
the mesh size, and every device owns ``n_padded / n_devices`` cells end
to end:

  * Per-cell state is DEVICE-LOCAL for the whole stream: server
    free-time grids, Kahan mean state, and hist_sketch rows live in the
    local shard of the scan carry, and the Pallas histogram kernel runs
    per shard on its local (block, C/D) response blocks — the kernel's
    per-cell grid maps 1:1 onto the sharded axis. Nothing is
    communicated between chunks.
  * Cell randomness derives from cell COORDINATES, never device
    placement: chunk ``c``, seed ``s`` draws from
    ``split(fold_in(key, c), n_seeds)[s]`` through the exact unsharded
    samplers, executed on the host (chunk inputs are O(rows x
    chunk_size) — small by construction, that is the point of chunking).
  * The ONLY gather of results is at summary finalization, after the
    last chunk: pad cells are sliced away there, so they never reach a
    mean or a histogram summary.

Why host-side sampling and not per-cell sampling inside the shard: XLA's
codegen for the transcendental sampling transforms (log / pow) is only
approximately rounded, and the chosen expansion varies with tensor shape
and fusion context — a ``(C/D, T)``-shaped in-shard sampler produces
1-ULP-different draws for different device counts D, silently breaking
the CRN contract's sharding-invariance guarantee (observed on CPU at
~17% of draws for T=1700). Sampling once per seed on the host keeps the
op shapes — and therefore the bits — literally identical to the
unsharded engine. For the same reason the chunk BODY is its own XLA
program, mirroring the unsharded driver's sampler/body split, rather
than being fused with anything else.

Probe batches from ``threshold_bisect(mesh=...)`` ride the load axis of
the plan, so one sharded engine call still serves all brackets (and the
estimators no longer pass ``mesh=`` explicitly at all — ``queueing.run``
resolves the ambient mesh through ``repro.launch.mesh.resolve_mesh``).

Multi-host execution & sharding rules — design note
---------------------------------------------------

The same executor serves a SINGLE process with D devices and a
multi-process runtime (``repro.distributed.multihost.initialize``) where
the ``"cells"`` mesh spans every process's devices. Four pieces make the
multi-host path both correct and cheap:

**Sharding rules, declared once.** ``CellPlan.sharding_rule(mesh)``
returns the plan's ``repro.launch.mesh.SweepShardingRules``: everything
keyed by the cell axis (carry, per-cell plan parameters, per-device
input blocks) shards ``P("cells")``, chunk scalars replicate, and the
``put_*`` constructors build each global array from the blocks THIS
process owns (``jax.make_array_from_single_device_arrays``). Callers
never hand-build a ``NamedSharding``; the shard_map in_specs below and
the array constructors read the same rules object.

**Per-host sampling reduction.** Host-side sampling is per-seed
deterministic: row ``r`` of a chunk's input block is a pure function of
``split(fold_in(key, c), n_seeds)[r % n_seeds]`` (and, for service
tables, the row's distribution), NOT of which other rows are sampled
alongside it — so each process draws ONLY the sorted union of input
rows its local cells gather (``queueing.ChunkSampler.rows``) instead of
every process sampling the full O(all-rows x chunk) block. Locality
cannot change bits. ``cellplan.device_row_maps`` turns the plan's
global row indices into per-device row lists plus DEVICE-LOCAL gather
indices satisfying ``x[rows[d]][local[c]] == x[idx[c]]``; since the
chunk body reads inputs only through per-cell row gathers, remapping to
local positions is exact, and the shard_map input specs become
``P("cells")`` blocks (each device receives just its rows) rather than
full replicated blocks.

**Sampling/compute pipeline.** With ``pipeline="on"`` the chunk loop
runs through ``repro.core.chunkflow.iter_staged``: a producer thread
samples chunk ``c+1`` — eagerly, per row: the row-reduced sampler is
deliberately NOT jitted, because jit-fusing the stacked per-row draws
re-introduces exactly the shape-dependent ULP wobble described above
(observed flipping ~0.1% of one row's service draws when the requested
subset changed) — and stages its per-device blocks while the main
thread dispatches chunk ``c``'s shard_mapped body, double-buffered
with a bounded slot pool
(TransferBufferPool idiom) so peak staging memory is O(depth x chunk
inputs). The pipeline moves WHEN sampling happens, never what is
sampled: on/off are bit-identical.

**The single gather.** Per-cell state never crosses processes during
the stream. After the last chunk, finalization — and ONLY finalization
— gathers: on a mesh that spans processes, the cell-sharded ``ssum`` /
``cnt`` / ``hist`` buffers pass through a jitted identity with
replicated out_shardings (``multihost.fetch_replicated``), the one
collective of the whole engine, and every process computes the full
summary from its replica. Single-process meshes skip even that (eager
finalize reads the addressable shards directly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.core import cellplan, chunkflow, queueing
from repro.core import scenario as scenario_mod
from repro.core.distributions import ServiceDist
from repro.distributed import multihost
from repro.launch.mesh import make_sweep_mesh

Array = jax.Array


@functools.lru_cache(maxsize=None)
def _body_fn(mesh: jax.sharding.Mesh, n_servers: int, n_bins: int,
             block: int, use_kernel: str = "off",
             has_shared: bool = False, has_timed: bool = False,
             has_dists: bool = False):
    """Build (and cache) the jitted, shard_mapped chunk-body executor.

    The carry and the per-cell parameters — including the scenario
    policy/model codes, service-model mixes and the degradation /
    timed-policy parameters — are sharded over ``"cells"``, and so are
    the chunk INPUT blocks: each device receives only the input rows
    its own cells gather, with ``seed_idx`` / ``svc_idx`` already
    remapped to device-LOCAL row positions (``cellplan.device_row_maps``
    — an exact remap, see the module design note). Only the three chunk
    scalars replicate. Cached per mesh so repeated engine calls
    (threshold bisection!) reuse the wrapper and its jit cache.
    ``has_shared`` / ``has_timed`` are the static services-layout /
    timed-block flags of ``cell_update_ref`` (part of the cache key,
    like the kernel mode).

    ``use_kernel`` is a RESOLVED cell-update kernel mode (see
    ``queueing.run``): the Pallas kernel runs per shard on its local
    cells — its per-cell grid maps 1:1 onto the sharded axis, like the
    hist_sketch kernel — so every mode preserves the bit-identity
    contract.
    """
    def chunk_body(free, ssum, comp, cnt, hist, seed_idx, rates, k_mask,
                   ovh, policy_code, model_code, mix, p_slow, slow_factor,
                   p_fail, delay, svc_idx,
                   unit_gaps, servers, services, start, n_valid,
                   warmup_start):
        return queueing._sweep_chunk_cells(
            free, ssum, comp, cnt, hist, unit_gaps, servers, services,
            start, n_valid, warmup_start, seed_idx, rates, k_mask, ovh,
            policy_code, model_code, mix, p_slow, slow_factor, p_fail,
            delay, svc_idx if has_dists else None,
            n_servers=n_servers, n_bins=n_bins, block=block,
            use_kernel=use_kernel, has_shared=has_shared,
            has_timed=has_timed, has_dists=has_dists)

    cells = P("cells")
    # replication checking off: pallas_call (the kernels) has no
    # replication rule, and every spec here is explicit
    return jax.jit(jax.shard_map(
        chunk_body, mesh=mesh,
        in_specs=(cells,) * 20 + (P(),) * 3,
        out_specs=(cells,) * 5, check_vma=False))


def _sweep_cells_sharded(sampler, n_seeds_total: int,
                         rhos: Array, cfg: queueing.SimConfig, *,
                         variants, warmup_frac: float,
                         percentiles: tuple[float, ...], n_bins: int,
                         chunk_size: int | None,
                         mesh: jax.sharding.Mesh | None,
                         use_kernel: str = "off",
                         pipeline: str = "off",
                         call: int = -1) -> dict[str, Array]:
    """Drive the shard_mapped chunk body over the whole arrival stream.

    ``sampler`` is the SAME ``queueing.ChunkSampler`` the unsharded
    ``_run_engine`` consumes — identical randomness by construction;
    here its ``rows`` entry point draws only this process's input rows
    (the per-host sampling reduction, see the module design note).
    ``variants`` are the scenario's per-variant coordinates; their
    policy/model codes shard over the mesh with the rest of the plan, so
    MIXED-policy grids ride the same device-local body. ``pipeline`` is
    resolved (``"on"``/``"off"``): ``"on"`` overlaps next-chunk sampling
    + staging with the current chunk's compute via
    ``chunkflow.iter_staged`` — bit-identical either way. ``call``
    tags the ``repro.*`` profiler spans, as in ``queueing._run_engine``;
    ``repro.stage`` marks the host copy of the sampled rows and their
    staging onto the devices.
    """
    mesh = make_sweep_mesh() if mesh is None else mesh
    if tuple(mesh.axis_names) != ("cells",):
        raise ValueError(f"expected a 1-D ('cells',) mesh "
                         f"(make_sweep_mesh), got axes {mesh.axis_names}")
    spec = getattr(sampler, "spec", None)
    if spec is None or not hasattr(sampler, "rows"):
        raise TypeError(
            "the sharded executor needs a queueing.ChunkSampler "
            "(its .spec/.rows drive the per-host sampling reduction); "
            "got a bare sampler callable")
    with TraceAnnotation("repro.plan", call=call):
        m = cfg.n_arrivals
        variants = tuple(variants)
        policies, models = scenario_mod.variant_codes(variants)
        plan = cellplan.make_cell_plan(
            n_seeds_total, rhos.shape[0], len(variants),
            pad_to=mesh.devices.size, policies=policies, models=models,
            dist_ids=scenario_mod.variant_dist_ids(variants))
        rules = plan.sharding_rule(mesh)
        (rates_c, k_mask_c, ovh_c, mix_c, pslow_c, sfac_c, pfail_c,
         delay_c) = queueing._plan_cell_params(plan, rhos, cfg, variants)
        has_shared = scenario_mod.any_server_dependent(variants)
        has_timed = scenario_mod.any_timed(variants)
        has_dists = scenario_mod.any_dist_ids(variants)

        # global input-row index per cell -> per-device row lists + local
        # gather indices (exact remap; svc rows == seed rows unless the grid
        # is heterogeneous, where services stack one table per union member)
        n_dev = rules.n_devices
        seed_g = np.asarray(plan.seed_idx)
        seed_rows, seed_local = cellplan.device_row_maps(seed_g, n_dev)
        if has_dists:
            svc_rows, svc_local = cellplan.device_row_maps(
                np.asarray(plan.dist_id) * n_seeds_total + seed_g, n_dev)
        else:
            svc_rows, svc_local = seed_rows, seed_local

        # THIS process's sampling set: the sorted union over its devices
        # (shared rows are drawn once per host, not once per device)
        local_pos = rules.local_positions()
        proc_seed = np.unique(seed_rows[local_pos])
        proc_svc = np.unique(svc_rows[local_pos])
        seed_take = {p: np.searchsorted(proc_seed, seed_rows[p])
                     for p in local_pos}
        svc_take = {p: np.searchsorted(proc_svc, svc_rows[p])
                    for p in local_pos}

        warmup_start = int(m * warmup_frac)
        need_hist = len(percentiles) > 0
        t_chunk, n_chunks, block, pad = queueing._chunk_layout(
            cfg, chunk_size, need_hist, kernel_on=use_kernel != "off")
        t_pad = t_chunk + pad
        r_seed, r_svc = seed_rows.shape[1], svc_rows.shape[1]

        # carry + per-cell plan params as cell-sharded GLOBAL arrays (this
        # process supplies only its local devices' blocks — required on a
        # multi-process mesh, a no-op-cost re-layout on one process)
        put = lambda x: rules.put_cells(np.asarray(x))  # noqa: E731
        free, ssum, comp, cnt, hist = (
            put(x) for x in queueing._init_cell_state(plan, cfg, n_bins,
                                                      need_hist))
        (seed_local_g, svc_local_g, rates_g, k_mask_g, ovh_g, pol_g, mdl_g,
         mix_g, pslow_g, sfac_g, pfail_g, delay_g) = (
            put(x) for x in (seed_local, svc_local, rates_c, k_mask_c, ovh_c,
                             plan.policy_code, plan.model_code, mix_c,
                             pslow_c, sfac_c, pfail_c, delay_c))
        warm_g = rules.put_replicated(np.int32(warmup_start))
        run_chunk = _body_fn(mesh, cfg.n_servers, n_bins, block, use_kernel,
                             has_shared, has_timed, has_dists)

    def produce(c: int):
        """Sample THIS host's input rows for chunk ``c`` (one fused
        dispatch) and stage them as per-device cell-sharded blocks."""
        with TraceAnnotation("repro.sample", call=call, chunk=c):
            g, sv, svc = queueing._pad_chunk_inputs(
                *sampler.rows(c, t_chunk, proc_seed, proc_svc), pad)
        with TraceAnnotation("repro.stage", call=call, chunk=c):
            g, sv, svc = np.asarray(g), np.asarray(sv), np.asarray(svc)
            return (
                rules.put_blocks([g[seed_take[p]] for p in local_pos],
                                 (n_dev * r_seed,) + g.shape[1:]),
                rules.put_blocks([sv[seed_take[p]] for p in local_pos],
                                 (n_dev * r_seed,) + sv.shape[1:]),
                rules.put_blocks([svc[svc_take[p]] for p in local_pos],
                                 (n_dev * r_svc,) + svc.shape[1:]))

    use_pipe = pipeline == "on" and n_chunks > 1
    waits = chunkflow.Waits()
    for c, (gaps_g, servers_g, services_g) in enumerate(
            chunkflow.iter_staged(produce, n_chunks, enabled=use_pipe,
                                  call=call, waits=waits)):
        start = c * t_chunk
        with TraceAnnotation("repro.chunk.dispatch", call=call, chunk=c):
            free, ssum, comp, cnt, hist = run_chunk(
                free, ssum, comp, cnt, hist, seed_local_g, rates_g,
                k_mask_g, ovh_g, pol_g, mdl_g, mix_g, pslow_g, sfac_g,
                pfail_g, delay_g, svc_local_g, gaps_g, servers_g,
                services_g, rules.put_replicated(np.int32(start)),
                rules.put_replicated(np.int32(min(t_chunk, m - start))),
                warm_g)

    with TraceAnnotation("repro.drain", call=call):
        jax.block_until_ready(ssum)  # drain the producer before stats
    queueing._record_pipeline_stats(
        sampler, enabled=use_pipe, n_chunks=n_chunks, t_pad=t_pad,
        seed_rows=int(proc_seed.size), svc_rows=int(proc_svc.size),
        waits=waits)

    with TraceAnnotation("repro.finalize", call=call):
        if multihost.spans_processes(mesh):
            # THE single cross-process gather of the sweep (design note)
            gathered = multihost.fetch_replicated(
                mesh, *((ssum, cnt, hist) if need_hist else (ssum, cnt)))
            ssum, cnt = jnp.asarray(gathered[0]), jnp.asarray(gathered[1])
            hist = (jnp.asarray(gathered[2]) if need_hist
                    else jnp.zeros((0, 0)))
        return queueing._finalize_summary(plan, ssum, cnt, hist,
                                          m - warmup_start, percentiles)


def run_sharded(key: Array, scenario, rhos: Array, cfg: queueing.SimConfig,
                *, n_seeds: int = 2,
                percentiles: tuple[float, ...]
                = queueing.DEFAULT_PERCENTILES,
                n_bins: int = queueing.DEFAULT_BINS,
                chunk_size: int | None = None,
                mesh: jax.sharding.Mesh | None = None,
                kernel: str = "auto") -> dict[str, Array]:
    """``queueing.run`` across a device mesh (``mesh=None`` uses every
    visible device): same scenario semantics — including mixed-policy /
    mixed-model grids — same summary shapes, bit-identical results for
    the same ``(key, chunk_size)`` no matter the device count (and no
    matter the ``kernel`` mode). Equivalent to
    ``queueing.run(..., mesh=mesh)``."""
    return queueing.run(key, scenario, rhos, cfg, n_seeds=n_seeds,
                        percentiles=percentiles, n_bins=n_bins,
                        chunk_size=chunk_size,
                        mesh=make_sweep_mesh() if mesh is None else mesh,
                        kernel=kernel)


def sweep_sharded(key: Array, dist: ServiceDist, rhos: Array,
                  cfg: queueing.SimConfig, *, ks: tuple[int, ...] = (1, 2),
                  n_seeds: int = 2,
                  percentiles: tuple[float, ...]
                  = queueing.DEFAULT_PERCENTILES,
                  n_bins: int = queueing.DEFAULT_BINS,
                  chunk_size: int | None = None,
                  mesh: jax.sharding.Mesh | None = None) -> dict[str, Array]:
    """``queueing.sweep`` across a device mesh: same signature plus
    ``mesh`` (default: all visible devices), same summary shapes
    ``(n_seeds, len(rhos), len(ks))``, and — per the CRN contract —
    bit-identical results for the same ``(key, chunk_size)`` no matter
    the device count.

    .. deprecated:: Thin shim over ``run_sharded`` (paper-default
       scenario); prefer ``queueing.run(..., mesh=...)``."""
    scn = queueing.Scenario.paper_default(
        dist, ks=tuple(int(k) for k in ks),
        client_overhead=cfg.client_overhead, warmup_frac=cfg.warmup_frac)
    return run_sharded(key, scn, rhos, cfg, n_seeds=n_seeds,
                       percentiles=percentiles, n_bins=n_bins,
                       chunk_size=chunk_size, mesh=mesh)


def sweep_dists_sharded(key: Array, dist_list, rhos: Array,
                        cfg: queueing.SimConfig, *,
                        ks: tuple[int, ...] = (1, 2), n_seeds: int = 2,
                        percentiles: tuple[float, ...]
                        = queueing.DEFAULT_PERCENTILES,
                        n_bins: int = queueing.DEFAULT_BINS,
                        chunk_size: int | None = None,
                        mesh: jax.sharding.Mesh | None = None
                        ) -> dict[str, Array]:
    """``queueing.sweep_dists`` across a device mesh: distributions stack
    along the plan's seed axis (every dist shares per-seed keys and the
    same arrival process — CRN across dists), summaries come back
    ``(len(dist_list), n_seeds, len(rhos), len(ks))``, bit-identical to
    the unsharded engine.

    .. deprecated:: Thin shim over ``run_sharded`` (multi-``dists``
       paper-default scenario); prefer ``queueing.run(..., mesh=...)``."""
    dist_list = tuple(dist_list)
    scn = queueing.Scenario.paper_default(
        dist_list, ks=tuple(int(k) for k in ks),
        client_overhead=cfg.client_overhead, warmup_frac=cfg.warmup_frac)
    out = run_sharded(key, scn, rhos, cfg, n_seeds=n_seeds,
                      percentiles=percentiles, n_bins=n_bins,
                      chunk_size=chunk_size, mesh=mesh)
    if len(dist_list) == 1:  # run() adds the dist axis only for d > 1
        out = {k: (v[None] if isinstance(v, jax.Array) else v)
               for k, v in out.items()}
    return out
