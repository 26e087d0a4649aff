"""Discrete-event simulation of the paper's replication queueing model (§2.1).

Model (exactly as in the paper): ``N`` independent identical FIFO servers,
Poisson arrivals at rate ``N * rho`` (so each server sees utilization ``rho``
without replication), each arriving request is copied to ``k`` distinct
servers chosen uniformly at random, every copy is served to completion
(no cancellation — this is what doubles utilization), and the request's
response time is the minimum over its copies' (queueing delay + service
time). An optional fixed ``client_overhead`` is added to every request when
k > 1 (paper Figure 4).

Common random numbers (CRN): the arrival process, the first copy's server
choice, and the first copy's service time are identical for every ``k``
under the same seed, which makes paired k=2 vs k=1 comparisons (and hence
threshold estimation) low-variance.

Fused sweep engine — design note
--------------------------------

Every paper figure sweeps the same simulator over a (seed, load, k) grid,
and the pre-refactor code ran one sequential ``lax.scan`` per grid cell
from Python (``replication_gain`` alone ran ``2 * n_seeds`` full passes).
``sweep`` replaces those loops with ONE ``lax.scan`` over arrivals whose
carry stacks the per-server next-free times for the whole grid:

    free:  (S, B, K, N)   S seeds x B loads x K replication factors
                          x N servers

The scan step ``vmap``s a single-cell update (gather k server-free times,
max with the arrival time, add service, scatter back, min-reduce) over the
three grid axes. Randomness is sampled ONCE per seed at ``k_max = max(ks)``
and every k-slice consumes a prefix of the same copy set / service draws,
so the CRN coupling of the sequential path is preserved exactly: the k=1
slice sees bit-identical inputs to the old ``simulate_grid(key, ..., k=1)``.

The engine never materializes an ``(S, B, K, M)`` response array. Instead
it folds each response into streaming statistics:

  * a Kahan-compensated post-warmup sum (=> exact-to-float32 means), and
  * a log-spaced histogram sketch of ``n_bins`` buckets spanning
    [HIST_LO, HIST_HI], from which percentiles are read as geometric bin
    midpoints (relative error <= half a bin width, ~0.5% at the default
    2048 bins over 8 decades). The per-arrival one-hot scatter of PR 2 is
    gone: responses are staged in blocks of ``_SKETCH_BLOCK`` scan steps
    and folded into the histogram by the Pallas ``hist_sketch`` kernel
    (``repro.kernels.hist_sketch``), which contracts skinny 0/1 indicator
    matrices on the MXU and keeps the accumulator in VMEM (interpret mode
    off-TPU).

Chunk streaming (``chunk_size``)
--------------------------------

With ``chunk_size=None`` all randomness is pre-sampled, so host memory
caps ``n_arrivals`` at O(S * M * k_max). Passing ``chunk_size=T`` streams
the sweep instead: arrivals are processed in fixed-size chunks whose
gaps / copy sets / service times are freshly sampled per chunk, and only
the (S,B,K,N) free-time grid plus the streaming summaries cross chunk
boundaries. Peak memory is O(S * T * k_max + S*B*K*(N + n_bins)),
independent of ``n_arrivals`` — 10M-arrival sweeps run on a laptop.

Key-splitting / CRN contract (chunked mode):

  * Chunk ``c`` (arrivals ``[c*T, min((c+1)*T, M))``) draws ALL of its
    randomness from ``jax.random.fold_in(key, c)`` through the same
    samplers the unchunked engine uses, at ``n_arrivals=T``. The stream
    is a pure function of ``(key, chunk_size)``: reruns are bit-identical
    and chunk ``c``'s draws do not depend on how many chunks follow.
  * Every CRN pairing of the unchunked engine holds within each chunk —
    the arrival process is shared across loads, copy sets are nested
    across k (k=2's extra server is one of k=3's), copy j's service draw
    is shared by every k >= j, and ``sweep_dists`` gives all
    distributions the same arrival process — so paired comparisons
    (replication gain, thresholds) stay low-variance under chunking.
  * Different ``chunk_size`` values consume the key differently: the
    resulting summaries are statistically identical (same process, same
    estimator) but not bit-identical. ``chunk_size=None`` keeps the PR 2
    contract: seed ``s``, k-slice ``j`` sees bit-identical inputs to
    ``simulate_grid(split(key, n_seeds)[s], dist, rhos, cfg, ks[j])``.
  * Sharding invariance: the sharded executor
    (``repro.distributed.sweep_shard``) derives every cell's randomness
    from its SEED COORDINATE in the cell plan — chunk ``c``, seed ``s``
    draws from ``split(fold_in(key, c), n_seeds)[s]`` (``split(key,
    n_seeds)[s]`` unchunked) no matter which device owns the cell — and
    pad cells are sliced away before any summary is read. For the same
    ``(key, chunk_size)``, sharded and unsharded sweeps (and the
    thresholds derived from them) are therefore bit-identical for ANY
    device count.

Scenario & policy codes
-----------------------

``run(key, scenario, rhos, cfg, ...)`` is the public entry point: a
``repro.core.scenario.Scenario`` (or a sequence of them — a *mixed
grid*) declares replication policy, service model, ``ks``, client
overhead and warmup, and the engine lowers it to per-cell policy/model
CODES stored in the cell plan next to (seed, load, k). ``sweep`` /
``sweep_dists`` / ``replication_gain`` remain as thin paper-default
shims over ``run``.

Key-consumption contract per policy: every policy and service model
consumes EXACTLY the same randomness. The samplers always draw the full
``k_max`` copy set and all ``k_max`` per-copy service times, no matter
which policy uses how much of them — ``CANCEL_ON_COMPLETE`` discards a
cancelled loser's draw, ``REPLICATE_TO_IDLE`` discards the draws of
copies it never dispatches — and the ``SERVER_DEPENDENT`` service model
adds ONE extra column (the shared request component, sampled from
``fold_in(k_svc, k_max)``) only when a grid contains such a variant;
columns ``0..k_max-1`` are bit-identical either way. Policies and
models therefore stay CRN-paired with each other cell-for-cell: a
mixed grid's REPLICATE_ALL/IID column is bit-identical to the same
cell in a pure paper-default sweep, and paired policy comparisons
(cancel-vs-keep, idle-vs-all, any mix) are low-variance.

Why mixed grids stay ONE compiled body: the per-cell step branches on
the policy/model codes with ``jnp.where`` selects (all variants'
updates are computed, the cell's code picks one), so the vmapped cell
update has a single trace — no per-policy recompile, no ragged control
flow, and device-local state in the sharded executor is untouched. The
REPLICATE_ALL/IID branch is the pre-redesign computation op-for-op,
which is what keeps ``Scenario.paper_default`` bit-identical to the
legacy engine.

Execution layers
----------------

The engine is split into plan construction (``repro.core.cellplan``
flattens the stacked (S, B, K) axes into one padded cell axis), the
per-chunk body (``_sweep_chunk_cells``, one flat cell axis), and
finalization (``_finalize_summary``). ``_run_engine`` below drives the
body on a single device; ``repro.distributed.sweep_shard`` drives the
SAME body under ``shard_map`` over a 1-D ``"cells"`` device mesh.

Each chunk also rebases times to its own start (the free-time carry is
kept relative to the last chunk boundary), so float32 arrival times stay
O(chunk duration) instead of growing to O(total sim time) — long streams
LOSE no precision to the cumsum, unlike the pre-sampled path.

``simulate`` / ``simulate_grid`` remain for callers that need raw
per-arrival response times (tests, exact percentiles); they are thin
wrappers over the same single-cell step function.

Cell-update kernel (``kernel=``)
--------------------------------

The per-chunk body has two interchangeable, BIT-IDENTICAL
implementations dispatched by ``_sweep_chunk_cells``'s static
``use_kernel`` flag: the ``lax.scan`` reference
(``repro.kernels.cell_update.ref``, the default off-TPU) and a fused
Pallas kernel (``repro.kernels.cell_update.kernel``) that keeps each
cell's free-time grid, Kahan state and histogram counts resident in
VMEM across the whole chunk, writing carry to HBM once per chunk
instead of once per arrival. ``run(..., kernel=...)`` takes
``"auto"`` (kernel on TPU, scan elsewhere), ``"on"`` (the compiled
kernel; raises without a TPU), ``"off"`` or ``"interpret"`` (the
kernel through the Pallas interpreter — how CPU CI bit-tests the
kernel path); the sharded executor threads the same
mode through ``shard_map``, preserving sharded==unsharded
bit-identity in every mode. Kernel mode pads every chunk to a
lane-aligned sketch-block multiple (scan mode only pads when the
sketch is on) —
legal because zero-weight steps are bitwise no-ops on all carry state
(see ``ref.kahan_fold``), so padded and unpadded layouts agree bit
for bit. The step physics lives ONCE in
``repro.kernels.cell_update.ref.step_cell`` (re-exported here as
``_step_cell``); the kernel package's docstrings carry the VMEM
layout / block-size / CRN-contract design note.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import cellplan
from repro.core import chunkflow
from repro.core import scenario as scenario_mod
from repro.core.distributions import ServiceDist
from repro.core.scenario import (Policy, Scenario, ServiceModel,  # noqa: F401
                                 Variant)
from repro.kernels.cell_update import ops as cell_ops
from repro.kernels.cell_update.ref import cell_update_ref, step_cell
from repro.kernels.hist_sketch import ops as hist_ops
from repro.kernels.hist_sketch.ops import (DEFAULT_BINS, HIST_HI,  # noqa: F401
                                           HIST_LO)
from repro.launch import mesh as launch_mesh

Array = jax.Array

DEFAULT_PERCENTILES = (50.0, 90.0, 99.0, 99.9)

# The engine-call id of ``run``'s profiler spans: one per call,
# process-wide, so the spans of one call can be told from the next.
_ENGINE_CALLS = itertools.count()

# Scan steps staged per hist_sketch kernel call; chunk lengths are padded
# up to a multiple of this with zero-weight no-op arrivals.
_SKETCH_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Machine shape of a simulation. ``warmup_frac``/``client_overhead``
    are legacy knobs consumed by the paper-default shims (``sweep``,
    ``simulate``, the threshold estimators); ``run`` reads them from the
    ``Scenario`` instead."""

    n_servers: int = 20
    n_arrivals: int = 100_000
    warmup_frac: float = 0.1
    client_overhead: float = 0.0  # latency penalty added to replicated requests


def _overhead_when_replicated(overhead: float, k: int) -> float:
    """The paper's Figure 4 rule, in ONE place for every entry point:
    client overhead is charged only when a request is replicated (k > 1)."""
    return float(overhead) if k > 1 else 0.0


def _arrival_part(key: Array, n: int, m: int, k_max: int):
    """Distribution-independent randomness: unit-rate exponential gaps
    (scaled by the actual rate at sim time so the same key yields a coupled
    arrival process across loads) and the per-request copy sets."""
    k_gap, k_srv0, k_srvx, _ = jax.random.split(key, 4)
    unit_gaps = jax.random.exponential(k_gap, (m,))
    first = jax.random.randint(k_srv0, (m,), 0, n)
    if k_max > 1:
        # distinct extra copies: choose k-1 distinct offsets in [1, n).
        # The same score tensor is used for every k, so copy sets are nested
        # (k=2's extra server is also one of k=3's) — CRN across k.
        scores = jax.random.uniform(k_srvx, (m, n - 1))
        _, offs = jax.lax.top_k(scores, k_max - 1)  # (m, k_max-1) in [0, n-1)
        extra = (first[:, None] + 1 + offs) % n
        servers = jnp.concatenate([first[:, None], extra], axis=1)
    else:
        servers = first[:, None]
    return unit_gaps, servers


# fold_in index of the SERVER_DEPENDENT shared request component: FIXED —
# never a function of k or k_max — so the same arrival draws the same
# shared component in every grid layout and in the raw simulate paths
# (CRN across k and across entry points). Any constant that can never
# collide with a copy index works.
_SHARED_SVC_FOLD = 0x5CA1AB1E

# DEDICATED fold_in index of the degradation model's per-copy uniforms
# (copy j draws from fold_in(fold_in(k_svc, _DEGRADE_FOLD), j)). The
# PR-7 CRN contract: failure/straggler draws live on their OWN branch
# of the key tree — the service columns, the shared component and the
# arrival stream are untouched — so a healthy grid samples exactly the
# pre-degradation randomness (healthy cells keep today's bits), and
# degraded vs healthy cells stay CRN-paired draw-for-draw.
_DEGRADE_FOLD = 0xFA11ED


def _service_part(key: Array, dist: ServiceDist, cfg: SimConfig,
                  n_copies: int, with_shared: bool = False,
                  with_degr: bool = False):
    """Per-copy fold_in keys so copy j's service times are identical for
    every k_max (CRN: k=1 and k=2 share the first copy's service draw).
    ``with_shared`` appends the SERVER_DEPENDENT shared request component
    as one extra column, drawn from the fixed
    ``fold_in(k_svc, _SHARED_SVC_FOLD)``: the copy columns are
    bit-identical either way, and the shared column is identical for
    every ``n_copies`` — so it is CRN-shared across k, across grid
    layouts, and across the sweep/simulate entry points. ``with_degr``
    appends ``n_copies`` more uniform(0,1) columns (the degradation
    draws, one per copy) from the dedicated ``_DEGRADE_FOLD`` branch;
    column layout is ``[copies][shared?][degradation?]`` and the
    consumers receive the shared flag statically (the count alone is
    ambiguous at ``n_copies == 1``)."""
    m = cfg.n_arrivals
    _, _, _, k_svc = jax.random.split(key, 4)
    cols = [dist.sample(jax.random.fold_in(k_svc, j), (m,))
            for j in range(n_copies)]
    if with_shared:
        cols.append(dist.sample(
            jax.random.fold_in(k_svc, _SHARED_SVC_FOLD), (m,)))
    if with_degr:
        k_deg = jax.random.fold_in(k_svc, _DEGRADE_FOLD)
        cols.extend(jax.random.uniform(jax.random.fold_in(k_deg, j), (m,))
                    for j in range(n_copies))
    return jnp.stack(cols, axis=1)


def _sample_inputs(key: Array, dist: ServiceDist, cfg: SimConfig, k_max: int,
                   with_shared: bool = False, with_degr: bool = False):
    """Draw all randomness up front. Column 0 of servers/services is shared
    by every k (CRN); services carry the extra shared-component column
    when ``with_shared`` (SERVER_DEPENDENT scenarios) and the per-copy
    degradation uniforms when ``with_degr`` (degraded scenarios)."""
    unit_gaps, servers = _arrival_part(key, cfg.n_servers, cfg.n_arrivals,
                                       k_max)
    services = _service_part(key, dist, cfg, k_max, with_shared, with_degr)
    return unit_gaps, servers, services


# The single-arrival physics moved to the cell_update kernel package so
# the scan body and the Pallas kernel share one source of truth; kept
# under the old private name for the raw-response paths and tests.
_step_cell = step_cell


def _scan_sim(arrivals: Array, servers: Array, services: Array, n_servers: int,
              variant: Variant) -> Array:
    """Run the FIFO replication DES for ONE scenario variant. arrivals
    (M,), servers (M,k), services (M,k) or (M,k+1) with the shared
    component last -> response times (M,). Shares ``_step_cell`` with the
    sweep engine, so raw-response callers exercise the same policy/model
    code path."""
    k = servers.shape[1]
    ovh = jnp.asarray(_overhead_when_replicated(variant.overhead, k),
                      jnp.float32)
    mask = jnp.ones((k,), bool)
    pol = jnp.asarray(int(variant.policy), jnp.int32)
    mdl = jnp.asarray(int(variant.service_model), jnp.int32)
    mix = jnp.asarray(variant.mix, jnp.float32)
    psl = jnp.asarray(variant.p_slow, jnp.float32)
    sfa = jnp.asarray(variant.slow_factor, jnp.float32)
    pfl = jnp.asarray(variant.p_fail, jnp.float32)
    dly = jnp.asarray(variant.delay, jnp.float32)
    n_base = k + (1 if variant.needs_shared_draw else 0)
    has_degr = variant.needs_degradation_draw

    has_timed = variant.policy in scenario_mod.TIMED_POLICIES

    def step(free: Array, inp):
        t, srv, svc = inp
        shared = svc[k] if variant.needs_shared_draw else svc[0]
        degr = svc[n_base:n_base + k] if has_degr else jnp.zeros((k,))
        return _step_cell(free, t, srv, svc[:k], shared, degr, mask, ovh,
                          pol, mdl, mix, psl, sfa, pfl, dly,
                          has_timed=has_timed)

    free0 = jnp.zeros((n_servers,))
    _, resp = jax.lax.scan(step, free0, (arrivals, servers, services))
    return resp


@partial(jax.jit, static_argnames=("dist", "cfg", "k", "scenario"))
def simulate(key: Array, dist: ServiceDist, rho: Array, cfg: SimConfig,
             k: int = 1, *, scenario: Scenario | None = None) -> Array:
    """Response times (M,) for a single load ``rho`` and replication ``k``.

    Routed through the paper-default ``Scenario`` shim by default;
    ``scenario`` overrides policy / service model / mix / overhead for
    raw-response studies of the wider policy space (its ``dists``/``ks``
    are ignored here — ``dist``/``k`` stay authoritative).
    """
    scn = scenario or Scenario.paper_default(
        dist, client_overhead=cfg.client_overhead,
        warmup_frac=cfg.warmup_frac)
    variant = scn.variant_for(k)
    unit_gaps, servers, services = _sample_inputs(
        key, dist, cfg, k, with_shared=variant.needs_shared_draw,
        with_degr=variant.needs_degradation_draw)
    rate = cfg.n_servers * rho
    arrivals = jnp.cumsum(unit_gaps / rate)
    return _scan_sim(arrivals, servers[:, :k], services,
                     cfg.n_servers, variant)


@partial(jax.jit, static_argnames=("dist", "cfg", "k", "scenario"))
def simulate_grid(key: Array, dist: ServiceDist, rhos: Array, cfg: SimConfig,
                  k: int = 1, *, scenario: Scenario | None = None) -> Array:
    """Response times (B, M) for a grid of loads, one coupled sample path.
    ``scenario`` as in ``simulate``."""
    scn = scenario or Scenario.paper_default(
        dist, client_overhead=cfg.client_overhead,
        warmup_frac=cfg.warmup_frac)
    variant = scn.variant_for(k)
    unit_gaps, servers, services = _sample_inputs(
        key, dist, cfg, k, with_shared=variant.needs_shared_draw,
        with_degr=variant.needs_degradation_draw)
    rates = cfg.n_servers * rhos  # (B,)
    arrivals = jnp.cumsum(unit_gaps)[None, :] / rates[:, None]  # (B, M)
    sim = jax.vmap(
        lambda a: _scan_sim(a, servers[:, :k], services,
                            cfg.n_servers, variant))
    return sim(arrivals)


def _warm(resp: Array, cfg: SimConfig) -> Array:
    start = int(cfg.n_arrivals * cfg.warmup_frac)
    return resp[..., start:]


def summarize(resp: Array, cfg: SimConfig,
              percentiles=DEFAULT_PERCENTILES) -> dict[str, Array]:
    """Post-warmup mean + percentiles along the last axis."""
    r = _warm(resp, cfg)
    out = {"mean": jnp.mean(r, axis=-1)}
    for p in percentiles:
        out[f"p{p:g}"] = jnp.percentile(r, p, axis=-1)
    return out


# ---------------------------------------------------------------------------
# Fused sweep engine
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_servers", "n_arrivals", "k_max",
                                   "n_seeds"))
def _sample_sweep_arrivals(key: Array, n_servers: int, n_arrivals: int,
                           k_max: int, n_seeds: int):
    """(S,M) unit gaps + (S,M,k_max) copy sets. Distribution-independent and
    keyed only on the shape-bearing config fields (NOT the whole SimConfig),
    so its (one, comparatively expensive) compile is shared by every family
    — and every client_overhead / warmup variant — a benchmark sweeps."""
    keys = jax.random.split(key, n_seeds)
    return jax.vmap(
        lambda kk: _arrival_part(kk, n_servers, n_arrivals, k_max))(keys)


def _sample_sweep_services(key: Array, dist: ServiceDist, cfg: SimConfig,
                           k_max: int, n_seeds: int,
                           with_shared: bool = False,
                           with_degr: bool = False):
    """(S,M,n_svc) service draws (``n_svc = k_max + with_shared +
    k_max * with_degr``, layout ``[copies][shared?][degradation?]``).
    Deliberately NOT jitted: eager sampling reuses jax's per-op caches
    across distributions, so sweeping 15 families costs 15 x ~20ms
    instead of 15 x ~1s of per-family jit compiles (the PRNG bits are
    identical either way)."""
    keys = jax.random.split(key, n_seeds)
    return jnp.stack([_service_part(keys[s], dist, cfg, k_max, with_shared,
                                    with_degr)
                      for s in range(n_seeds)], axis=0)


def _sample_sweep_inputs(key: Array, dist: ServiceDist, cfg: SimConfig,
                         k_max: int, n_seeds: int,
                         with_shared: bool = False,
                         with_degr: bool = False):
    """Per-seed randomness for the engine: (S,M) gaps, (S,M,k_max) servers,
    (S,M,n_svc) services (shared component column for SERVER_DEPENDENT
    grids, per-copy degradation uniforms for degraded grids — see
    ``_service_part``). Bit-identical to ``n_seeds`` sequential
    ``_sample_inputs`` calls on ``jax.random.split(key, n_seeds)``."""
    unit_gaps, servers = _sample_sweep_arrivals(
        key, cfg.n_servers, cfg.n_arrivals, k_max, n_seeds)
    services = _sample_sweep_services(key, dist, cfg, k_max, n_seeds,
                                      with_shared, with_degr)
    return unit_gaps, servers, services


@partial(jax.jit, static_argnames=("n_servers", "n_bins", "block",
                                   "use_kernel", "has_shared",
                                   "has_timed", "has_dists"))
def _sweep_chunk_cells(free: Array, ssum: Array, comp: Array, cnt: Array,
                       hist: Array,
                       unit_gaps: Array, servers: Array, services: Array,
                       start: Array, n_valid: Array, warmup_start: Array,
                       seed_idx: Array, rates: Array, k_mask: Array,
                       ovh: Array, policy_code: Array, model_code: Array,
                       mix: Array, p_slow: Array, slow_factor: Array,
                       p_fail: Array, delay: Array, svc_idx: Array = None,
                       *, n_servers: int,
                       n_bins: int, block: int, use_kernel: str = "off",
                       has_shared: bool = False, has_timed: bool = False,
                       has_dists: bool = False):
    """Scenario- and distribution-agnostic fused core over ONE chunk of
    arrivals, on a flat cell axis (see ``repro.core.cellplan``).

    Per-cell carry threaded across chunks: ``free`` (C,N) server-free
    times RELATIVE to the chunk-start arrival time, ``ssum``/``comp``
    (C,) Kahan mean state, ``cnt`` (C,) completed post-warmup response
    counts (== the static post-warmup count for cells that cannot lose
    requests; less for degraded cells with blackholed copies), ``hist``
    (C, n_bins) sketch counts (shape (0, 0) skips the sketch). Sampled
    inputs stay at SEED granularity — ``unit_gaps`` (S,T), ``servers``
    (S,T,k_max), ``services`` (S,T,n_svc) with column layout
    ``[k_max copies][shared if has_shared][k_max degradation uniforms
    if present]`` (``has_shared`` is static; the degradation columns
    are detected from the remainder) — and ``seed_idx`` (C,) maps each
    cell to its input row, so one sampled row is shared by all
    (load, k) cells of a seed: the gather happens per scan step on a
    (S,k_max) slice, and the (C,T,...) expansion is never
    materialized. The sharded driver runs this same body per shard with
    the inputs replicated and ``seed_idx`` restricted to the local
    cells (global seed indices, sharded over the mesh).

    HETEROGENEOUS grids (``has_dists=True``, per-cell ``dist_id``):
    ``services`` carries one (n_seeds, T, n_svc) table PER dist-union
    member stacked along axis 0, and ``svc_idx`` (C,) =
    ``dist_id * n_seeds + seed_idx`` routes each cell's SERVICE gather
    to its system's table — gaps/servers/rebase stay ``seed_idx``-keyed
    (arrivals and copy sets are CRN-shared across systems). With
    ``has_dists=False`` (the default) ``svc_idx`` is unused and the
    compiled program is exactly the pre-dist_id one.
    ``rates``/``ovh``/``mix``/``p_slow``/``slow_factor``/``p_fail``/
    ``delay`` (C,), ``k_mask`` (C,k_max) and the ``policy_code``/
    ``model_code`` (C,) scenario coordinates are per-cell parameters
    gathered from the plan; the vmapped ``_step_cell`` branches on the
    codes per lane, which is what lets a MIXED grid (cells disagreeing
    on policy/model/degradation) run in this one compiled body. Callers
    that pass SERVER_DEPENDENT or degraded codes must supply the extra
    services columns (healthy/IID layouts reuse column 0 / zeros as
    dummies that the selects discard).

    ``start`` is the global index of the chunk's first step; ``n_valid``
    the real (non-padding) steps. Steps past ``n_valid`` are masked to
    zero-gap / zero-service / zero-weight no-ops — they can only bump an
    idle server's free time up to the chunk-end arrival time, which no
    later arrival (all at times >= it) can observe.

    When the sketch is on, the scan is staged in ``block``-step
    sub-blocks whose responses are folded into ``hist`` by the Pallas
    hist_sketch kernel — no per-step scatter, no (C,T) materialization
    beyond one block. Returns the carry with ``free`` rebased to the
    chunk-end time.

    ``use_kernel`` picks the body implementation (see the module design
    note): ``"off"`` runs the ``lax.scan`` reference
    (``cell_update_ref``), ``"on"`` / ``"interpret"`` the fused Pallas
    kernel (compiled / interpreted) — bit-identical by contract, pinned
    by the kernel parity tests. Kernel modes require ``T`` padded to
    the ``block`` multiple even without the sketch (``_chunk_layout``
    arranges this).
    """
    S, T = unit_gaps.shape
    need_hist = hist.size > 0
    if need_hist:
        assert T % block == 0, (T, block)

    i = jnp.arange(T)
    valid = i < n_valid                                       # (T,)
    warm = (valid & (start + i >= warmup_start)).astype(jnp.float32)
    gaps = unit_gaps * valid
    services = services * valid[None, :, None]
    cum = jnp.cumsum(gaps, axis=1)      # (S, T) offsets from chunk start

    body = (cell_update_ref if use_kernel == "off"
            else partial(cell_ops.cell_update,
                         interpret=(use_kernel == "interpret")))
    free, ssum, comp, cnt, hist = body(
        free, ssum, comp, cnt, hist, cum, warm,
        valid.astype(jnp.float32), servers, services, seed_idx,
        rates, k_mask, ovh, policy_code, model_code, mix, p_slow,
        slow_factor, p_fail, delay, svc_idx,
        n_servers=n_servers, n_bins=n_bins, block=block,
        has_shared=has_shared, has_timed=has_timed, has_dists=has_dists)

    # rebase to the chunk-end arrival time so floats stay O(chunk duration)
    free = free - (cum[:, -1][seed_idx] / rates)[:, None]
    return free, ssum, comp, cnt, hist


# --- plan construction / finalization shared by both execution layers ----

def _plan_cell_params(plan: cellplan.CellPlan, rhos: Array, cfg: SimConfig,
                      variants):
    """Per-cell engine parameters gathered from the plan's coordinates:
    arrival rates (C,), copy masks (C,k_max), client overheads (C,),
    service-model mixes (C,), degradation probabilities / straggler
    factors / blackhole probabilities / policy delays (C,) each.
    ``variants`` may be a plain ``ks`` tuple (paper default per k,
    overhead from ``cfg``) or per-variant ``scenario.Variant``s."""
    variants = tuple(
        v if isinstance(v, Variant)
        else Variant(k=int(v), overhead=cfg.client_overhead)
        for v in variants)
    k_max = max(v.k for v in variants)
    rates = cfg.n_servers * jnp.asarray(rhos)
    k_mask = jnp.asarray([[j < v.k for j in range(k_max)] for v in variants])
    ovh = jnp.asarray([_overhead_when_replicated(v.overhead, v.k)
                       for v in variants], jnp.float32)
    mix = jnp.asarray([v.mix for v in variants], jnp.float32)
    p_slow = jnp.asarray([v.p_slow for v in variants], jnp.float32)
    s_fac = jnp.asarray([v.slow_factor for v in variants], jnp.float32)
    p_fail = jnp.asarray([v.p_fail for v in variants], jnp.float32)
    delay = jnp.asarray([v.delay for v in variants], jnp.float32)
    return (rates[plan.load_idx], k_mask[plan.k_idx], ovh[plan.k_idx],
            mix[plan.k_idx], p_slow[plan.k_idx], s_fac[plan.k_idx],
            p_fail[plan.k_idx], delay[plan.k_idx])


def _init_cell_state(plan: cellplan.CellPlan, cfg: SimConfig, n_bins: int,
                     need_hist: bool):
    """Zeroed per-cell carry: free times, Kahan state, completed-response
    counts, sketch counts."""
    free = jnp.zeros((plan.n_padded, cfg.n_servers))
    ssum = comp = cnt = jnp.zeros((plan.n_padded,))
    hist = (jnp.zeros((plan.n_padded, n_bins)) if need_hist
            else jnp.zeros((0, 0)))
    return free, ssum, comp, cnt, hist


def _chunk_layout(cfg: SimConfig, chunk_size: int | None, need_hist: bool,
                  kernel_on: bool = False):
    """(chunk length, #chunks, sketch block, pad-to-block) of a stream.

    Chunks are padded to a block multiple when the sketch needs staged
    sub-blocks OR the Pallas cell-update kernel is on (its time grid is
    blocked unconditionally, in lane-aligned blocks). Padding never
    changes bits: zero-weight steps are bitwise no-ops on the whole
    carry (``ref.kahan_fold``)."""
    m = cfg.n_arrivals
    t_chunk = m if chunk_size is None else min(int(chunk_size), m)
    n_chunks = math.ceil(m / t_chunk)
    block = min(_SKETCH_BLOCK, t_chunk)
    if kernel_on:
        block = -(-block // hist_ops.LANE) * hist_ops.LANE
    pad = (-t_chunk) % block if (need_hist or kernel_on) else 0
    return t_chunk, n_chunks, block, pad


def _pad_chunk_inputs(unit_gaps: Array, servers: Array, services: Array,
                      pad: int):
    """Zero-pad a chunk's sampled inputs up to the sketch-block multiple."""
    if pad:
        unit_gaps = jnp.pad(unit_gaps, ((0, 0), (0, pad)))
        servers = jnp.pad(servers, ((0, 0), (0, pad), (0, 0)))
        services = jnp.pad(services, ((0, 0), (0, pad), (0, 0)))
    return unit_gaps, servers, services


def _finalize_summary(plan: cellplan.CellPlan, ssum: Array, cnt: Array,
                      hist: Array, count: int,
                      percentiles: tuple[float, ...]) -> dict[str, Array]:
    """Per-cell streaming state -> stacked (S,B,K) summaries. This is the
    single point where the sharded executor's device-local buffers are
    gathered (``unflatten`` slices pad cells away first, so they cannot
    contribute to any summary). ``count`` is the static post-warmup
    OFFERED count; ``cnt`` the per-cell COMPLETED count (less when a
    degraded cell blackholes every copy of a request). The mean divides
    by ``cnt`` — bit-identical to the pre-degradation ``ssum / count``
    for cells that cannot lose requests, since their float count equals
    the int exactly (f32 is exact on integers below 2**24)."""
    completed = cellplan.unflatten(plan, cnt)
    out: dict[str, Array] = {
        "mean": cellplan.unflatten(plan, ssum) / completed,
        "count": count, "completed": completed}
    if len(percentiles) > 0:
        quant = hist_ops.sketch_quantiles(
            cellplan.unflatten(plan, hist),
            jnp.asarray(percentiles, jnp.float32))            # (Q,S,B,K)
        for qi, p in enumerate(percentiles):
            out[f"p{p:g}"] = quant[qi]
    return out


def _record_pipeline_stats(sampler, *, enabled: bool, n_chunks: int,
                           t_pad: int, seed_rows: int, svc_rows: int,
                           waits: chunkflow.Waits) -> None:
    """Publish this run's pipeline + sampling shape and its chunk loop's
    ``waits`` to ``chunkflow`` so the benchmark harness can attach them
    as JSON provenance. ``seed_rows`` / ``svc_rows`` are the rows THIS
    process sampled per chunk (the full block on one process; the
    per-host reduction on many)."""
    spec = getattr(sampler, "spec", None)
    if spec is None:
        return
    k_max, n_svc = spec.k_max, spec.n_svc_cols

    def nbytes(n_seed, n_svc_rows):
        # f32 gaps (rows, T) + i32 servers (rows, T, k_max)
        # + f32 services (rows, T, n_svc)
        return 4 * t_pad * (n_seed * (1 + k_max) + n_svc_rows * n_svc)

    chunkflow.record_stats(chunkflow.PipelineStats(
        enabled=enabled, depth=chunkflow.DEFAULT_DEPTH, n_chunks=n_chunks,
        seed_rows_sampled=seed_rows, seed_rows_total=spec.n_seed_rows,
        svc_rows_sampled=svc_rows, svc_rows_total=spec.n_svc_rows,
        bytes_sampled_per_chunk=nbytes(seed_rows, svc_rows),
        bytes_full_per_chunk=nbytes(spec.n_seed_rows, spec.n_svc_rows),
        process_count=jax.process_count(),
        process_index=jax.process_index(),
        input_wait_s=waits.input_s, slot_wait_s=waits.slot_s))


def _run_engine(sampler, n_seeds_total: int, rhos: Array, cfg: SimConfig, *,
                variants: tuple[Variant, ...], warmup_frac: float,
                percentiles: tuple[float, ...],
                n_bins: int, chunk_size: int | None,
                use_kernel: str = "off",
                pipeline: str = "off", call: int = -1) -> dict[str, Array]:
    """Drive ``_sweep_chunk_cells`` over the whole arrival stream on one
    device: unpadded cell plan (variant policy/model codes as per-cell
    coordinates), seed-level sampled inputs shared by each seed's
    (load, variant) cells.

    ``sampler(chunk_idx, chunk_len)`` returns that chunk's
    ``(unit_gaps (S,T), servers (S,T,k_max), services (S,T,n_svc))`` —
    one call over the full stream when ``chunk_size`` is None.
    ``use_kernel`` is a RESOLVED kernel mode (never ``"auto"``); so is
    ``pipeline`` (``"on"``/``"off"``, never ``"auto"``): ``"on"``
    prefetches chunk ``c+1``'s inputs on a producer thread — through the
    sampler's FUSED jit entry point, one dispatch per chunk — while the
    chunk body for ``c`` runs (``repro.core.chunkflow``); bit-identical
    to ``"off"`` because it changes when inputs are sampled, never what.
    ``call`` tags the ``repro.*`` profiler spans of the loop (the
    engine-call id ``run`` assigns).
    """
    m = cfg.n_arrivals
    with TraceAnnotation("repro.plan", call=call):
        policies, models = scenario_mod.variant_codes(variants)
        plan = cellplan.make_cell_plan(
            n_seeds_total, rhos.shape[0], len(variants),
            policies=policies, models=models,
            dist_ids=scenario_mod.variant_dist_ids(variants))
        (rates_c, k_mask_c, ovh_c, mix_c, pslow_c, sfac_c, pfail_c,
         delay_c) = _plan_cell_params(plan, rhos, cfg, variants)
        has_shared = scenario_mod.any_server_dependent(variants)
        has_timed = scenario_mod.any_timed(variants)
        has_dists = scenario_mod.any_dist_ids(variants)
        # heterogeneous grids: route each cell's service gather to its
        # system's table row (services stacks one table per union member
        # along the seed axis); None keeps the legacy jaxpr untouched
        svc_idx_c = (plan.dist_id * n_seeds_total + plan.seed_idx
                     if has_dists else None)
        warmup_start = int(m * warmup_frac)
        need_hist = len(percentiles) > 0
        t_chunk, n_chunks, block, pad = _chunk_layout(
            cfg, chunk_size, need_hist, kernel_on=use_kernel != "off")
        free, ssum, comp, cnt, hist = _init_cell_state(plan, cfg, n_bins,
                                                       need_hist)

    use_pipe = pipeline == "on" and n_chunks > 1
    fused = getattr(sampler, "fused", None)
    draw = fused if (use_pipe and fused is not None) else sampler

    def produce(c: int):
        with TraceAnnotation("repro.sample", call=call, chunk=c):
            return _pad_chunk_inputs(*draw(c, t_chunk), pad)

    waits = chunkflow.Waits()
    for c, (unit_gaps, servers, services) in enumerate(
            chunkflow.iter_staged(produce, n_chunks, enabled=use_pipe,
                                  call=call, waits=waits)):
        start = c * t_chunk
        with TraceAnnotation("repro.chunk.dispatch", call=call, chunk=c):
            free, ssum, comp, cnt, hist = _sweep_chunk_cells(
                free, ssum, comp, cnt, hist, unit_gaps, servers, services,
                jnp.asarray(start), jnp.asarray(min(t_chunk, m - start)),
                jnp.asarray(warmup_start), plan.seed_idx, rates_c,
                k_mask_c, ovh_c, plan.policy_code, plan.model_code, mix_c,
                pslow_c, sfac_c, pfail_c, delay_c, svc_idx_c,
                n_servers=cfg.n_servers, n_bins=n_bins, block=block,
                use_kernel=use_kernel, has_shared=has_shared,
                has_timed=has_timed, has_dists=has_dists)
    # block on the last chunk so the producer thread (if any) is drained
    # before stats are read, then record sampling provenance
    with TraceAnnotation("repro.drain", call=call):
        jax.block_until_ready(ssum)
    spec = getattr(sampler, "spec", None)
    _record_pipeline_stats(
        sampler, enabled=use_pipe, n_chunks=n_chunks, t_pad=t_chunk + pad,
        seed_rows=spec.n_seed_rows if spec is not None else 0,
        svc_rows=spec.n_svc_rows if spec is not None else 0, waits=waits)

    with TraceAnnotation("repro.finalize", call=call):
        return _finalize_summary(plan, ssum, cnt, hist, m - warmup_start,
                                 percentiles)


def _chunk_key(key: Array, chunk_idx: int, chunk_size: int | None) -> Array:
    """The key-splitting contract: chunk c draws from fold_in(key, c);
    the unchunked stream consumes ``key`` itself (PR 2 compatible)."""
    return key if chunk_size is None else jax.random.fold_in(key, chunk_idx)


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Hashable static descriptor of a sweep's per-chunk randomness.

    ``kind`` picks the input-block layout (matching the three legacy
    sampler closures):

      ``"single"``   one distribution; gaps/servers/services all have
                     ``n_seeds`` rows.
      ``"stacked"``  legacy multi-dist sweeps (``sweep_dists``): every
                     dist shares the arrival process (CRN), so gaps /
                     servers are sampled once and TILED ``d`` times;
                     seed-row and service-row spaces both have
                     ``d * n_seeds`` rows.
      ``"tables"``   heterogeneous per-cell ``dist_id`` grids: gaps /
                     servers keep ``n_seeds`` rows, services stack one
                     table per dist-union member (``d * n_seeds``
                     service rows reached via ``svc_idx``).

    Being a frozen dataclass of hashables (``ServiceDist`` is already a
    static jit argument elsewhere), a spec is a valid static jit key —
    the fused samplers below compile once per spec and are shared by
    every chunk of a run.
    """

    kind: str
    dists: tuple[ServiceDist, ...]
    cfg: SimConfig
    k_max: int
    n_seeds: int
    with_shared: bool = False
    with_degr: bool = False

    @property
    def n_dists(self) -> int:
        return len(self.dists)

    @property
    def n_seed_rows(self) -> int:
        """Rows of the gaps/servers block (the seed-row space)."""
        return self.n_seeds * (self.n_dists if self.kind == "stacked"
                               else 1)

    @property
    def n_svc_rows(self) -> int:
        """Rows of the services block (the service-row space)."""
        return self.n_seeds * (self.n_dists if self.kind != "single"
                               else 1)

    @property
    def n_svc_cols(self) -> int:
        return (self.k_max + int(self.with_shared)
                + self.k_max * int(self.with_degr))


def _sample_chunk(spec: SamplerSpec, ck: Array, t: int):
    """One chunk's full ``(gaps, servers, services)`` block for any
    sampler kind — op-for-op the legacy closure bodies, so eager
    execution reproduces their exact per-op sequence (and bits)."""
    ccfg = dataclasses.replace(spec.cfg, n_arrivals=t)
    gaps, servers = _sample_sweep_arrivals(
        ck, spec.cfg.n_servers, t, spec.k_max, spec.n_seeds)
    if spec.kind == "single":
        services = _sample_sweep_services(
            ck, spec.dists[0], ccfg, spec.k_max, spec.n_seeds,
            spec.with_shared, spec.with_degr)
    else:
        services = jnp.concatenate(
            [_sample_sweep_services(ck, dd, ccfg, spec.k_max,
                                    spec.n_seeds, spec.with_shared,
                                    spec.with_degr)
             for dd in spec.dists], axis=0)
    if spec.kind == "stacked":
        d = spec.n_dists
        gaps, servers = (jnp.tile(gaps, (d, 1)),
                         jnp.tile(servers, (d, 1, 1)))
    return gaps, servers, services


@partial(jax.jit, static_argnames=("spec", "t"))
def _sample_chunk_fused(spec: SamplerSpec, ck: Array, t: int):
    """The same block as ONE jitted program. Bit-identical to the eager
    path (pinned by tests/test_multihost.py): the PRNG transforms'
    op shapes are per seed row either way, so XLA's shape-dependent
    ULP wobble (see the sweep_shard design note) cannot bite. One
    dispatch per chunk is what lets the sampling/compute pipeline
    overlap host sampling with device compute."""
    return _sample_chunk(spec, ck, t)


def _sample_chunk_rows(spec: SamplerSpec, ck: Array, t: int,
                       seed_rows: tuple[int, ...],
                       svc_rows: tuple[int, ...]):
    """Row-reduced sampling: draw ONLY the requested global rows of the
    chunk's input block.

    Row ``r`` of the seed-row space always derives from per-seed key
    ``split(ck, n_seeds)[r % n_seeds]`` (the tiled "stacked" layout
    repeats seed keys every ``n_seeds`` rows), and service row ``r``
    from ``(dists[r // n_seeds], split(ck, n_seeds)[r % n_seeds])`` —
    per-seed determinism, so each returned row is bit-identical to the
    corresponding row of ``_sample_chunk``'s full block no matter which
    subset is requested (pinned by tests/test_multihost.py). This is
    the per-host sampling reduction: a multi-host executor passes just
    the rows its local cells gather instead of the full
    O(all-rows x chunk) block.

    Deliberately EAGER, never jitted: under jit XLA fuses the stacked
    per-row service draws into one program whose op shapes depend on
    WHICH rows were requested, and that shape-dependent fusion wobbles
    individual draws by 1 ULP (observed: requesting all rows of a
    4-seed block flipped ~0.1% of row 0's service values — see the
    sweep_shard design note). Eagerly, every row is the same
    per-op-cached ``_service_part`` call the full block makes, so
    bit-identity is by construction, not by XLA's grace.
    """
    ccfg = dataclasses.replace(spec.cfg, n_arrivals=t)
    keys = jax.random.split(ck, spec.n_seeds)
    seed_of = jnp.asarray([r % spec.n_seeds for r in seed_rows])
    gaps, servers = jax.vmap(
        lambda kk: _arrival_part(kk, spec.cfg.n_servers, t,
                                 spec.k_max))(keys[seed_of])
    services = jnp.stack(
        [_service_part(keys[r % spec.n_seeds],
                       spec.dists[r // spec.n_seeds], ccfg, spec.k_max,
                       spec.with_shared, spec.with_degr)
         for r in svc_rows], axis=0)
    return gaps, servers, services


class ChunkSampler:
    """The engine's per-chunk input sampler.

    Callable with ``(chunk_idx, chunk_len)`` — the legacy closure
    protocol, drawing the full block EAGERLY (the PR 3 path: per-op
    caches shared across dist families, no per-family jit compile).
    Two additional entry points serve the pipeline and the multi-host
    executor, both bit-identical to the eager call by construction:

      ``fused(c, t)``                    the full block as one jitted
                                         dispatch (compiled per spec).
      ``rows(c, t, seed_rows, svc_rows)`` only the requested global
                                         rows (per-host reduction);
                                         eager, so the requested subset
                                         cannot change the bits (see
                                         ``_sample_chunk_rows``).
    """

    def __init__(self, spec: SamplerSpec, key: Array,
                 chunk_size: int | None):
        self.spec = spec
        self.key = key
        self.chunk_size = chunk_size

    def chunk_key(self, c: int) -> Array:
        return _chunk_key(self.key, c, self.chunk_size)

    def __call__(self, c: int, t: int):
        return _sample_chunk(self.spec, self.chunk_key(c), t)

    def fused(self, c: int, t: int):
        return _sample_chunk_fused(self.spec, self.chunk_key(c), t)

    def rows(self, c: int, t: int, seed_rows, svc_rows):
        return _sample_chunk_rows(self.spec, self.chunk_key(c), t,
                                  tuple(int(r) for r in seed_rows),
                                  tuple(int(r) for r in svc_rows))


def _sweep_sampler(key: Array, dist: ServiceDist, cfg: SimConfig,
                   k_max: int, n_seeds: int, chunk_size: int | None,
                   with_shared: bool = False, with_degr: bool = False):
    """The per-chunk sampler behind ``run``/``sweep``. Shared — this
    exact object, not a copy — with the sharded executor, so the two
    paths cannot drift apart on the CRN-critical sampling code the
    bit-identity contract depends on."""
    return ChunkSampler(SamplerSpec("single", (dist,), cfg, k_max,
                                    n_seeds, with_shared, with_degr),
                        key, chunk_size)


def _sweep_dists_sampler(key: Array, dist_list, cfg: SimConfig,
                         k_max: int, n_seeds: int,
                         chunk_size: int | None,
                         with_shared: bool = False,
                         with_degr: bool = False):
    """The per-chunk sampler behind multi-distribution runs (shared with
    the sharded executor, like ``_sweep_sampler``). Every distribution
    sees the same key, hence the same arrival process and copy sets
    (CRN across dists): arrivals are sampled once and tiled."""
    return ChunkSampler(SamplerSpec("stacked", tuple(dist_list), cfg,
                                    k_max, n_seeds, with_shared,
                                    with_degr), key, chunk_size)


def _dist_table_sampler(key: Array, dist_list, cfg: SimConfig,
                        k_max: int, n_seeds: int,
                        chunk_size: int | None,
                        with_shared: bool = False,
                        with_degr: bool = False):
    """The per-chunk sampler behind HETEROGENEOUS grids (per-cell
    ``dist_id``). Unlike ``_sweep_dists_sampler`` it does NOT tile the
    arrivals: gaps/servers stay (n_seeds, T) and only ``services``
    stacks one (n_seeds, T, n_svc) table per dist-union member along
    axis 0 — cells reach their system's table through ``svc_idx =
    dist_id * n_seeds + seed_idx`` while sharing one arrival process and
    copy sets (CRN across systems; dist-0 rows are bit-identical to a
    pure single-dist run of the same key)."""
    return ChunkSampler(SamplerSpec("tables", tuple(dist_list), cfg,
                                    k_max, n_seeds, with_shared,
                                    with_degr), key, chunk_size)


def run(key: Array, scenario: scenario_mod.ScenarioLike, rhos: Array,
        cfg: SimConfig, *, n_seeds: int = 2,
        percentiles: tuple[float, ...] = DEFAULT_PERCENTILES,
        n_bins: int = DEFAULT_BINS,
        chunk_size: int | None = None,
        mesh: jax.sharding.Mesh | None = None,
        kernel: str = "auto",
        pipeline: str = "auto") -> dict[str, Array]:
    """Execute a ``Scenario`` (or a sequence — a MIXED grid) over a load
    grid. THE public entry point of the sweep engine; ``sweep`` /
    ``sweep_dists`` / ``replication_gain`` are thin shims over it.

    Returns post-warmup summaries, each of shape
    ``(n_seeds, len(rhos), n_variants)`` — for a single scenario the
    variant axis is its ``ks`` in order; a sequence concatenates each
    scenario's variants. Scenarios with multiple ``dists`` add a leading
    dist axis (``sweep_dists`` layout). A HETEROGENEOUS sequence —
    scenarios with DIFFERENT single dists — instead keeps the
    ``(n_seeds, B, n_variants)`` layout: each variant carries its
    ``dist_id`` into the deduped dist union (see ``scenario.combine``),
    the engine samples one service table per union member, and every
    cell's service gather routes to its system's table inside the same
    compiled mixed grid ("which system" is just one more variant
    coordinate):

      ``mean``          streaming mean response
      ``p<q>``          histogram-sketch percentile per entry of
                        ``percentiles`` (pass ``()`` to skip the sketch
                        entirely — e.g. threshold estimation needs means
                        only)
      ``count``         post-warmup arrivals per cell (scalar)
      ``completed``     per-cell count of post-warmup requests that
                        COMPLETED — equals ``count`` except in degraded
                        cells where every copy of a request was
                        blackholed (those requests are excluded from
                        ``mean`` and the percentiles)

    ``chunk_size=None`` pre-samples the whole stream; an int streams
    arrivals in chunks of that many steps so peak memory is independent
    of ``cfg.n_arrivals``. ``mesh`` routes execution through the sharded
    cell-plan executor (``repro.distributed.sweep_shard``) —
    bit-identical for any device count. ``mesh=None`` does NOT force the
    single-device engine: it resolves through
    ``repro.launch.mesh.resolve_mesh`` (innermost ``use_sweep_mesh``
    context, else the multi-process default that
    ``distributed.multihost.initialize`` installs, else truly no mesh) —
    the ONE mesh-resolution point every entry point built on ``run``
    (``threshold.*``, benchmarks, shims) rides. ``kernel`` picks the
    chunk-body implementation (``"auto"`` / ``"on"`` / ``"off"`` /
    ``"interpret"``, see the module design note and
    ``repro.kernels.cell_update.ops.resolve_kernel_mode``) — every mode
    is bit-identical, on or off a mesh. ``pipeline`` controls the
    sampling/compute overlap (``repro.core.chunkflow``): ``"on"``
    prefetches each next chunk's inputs on a producer thread through the
    fused jitted sampler, ``"off"`` samples serially per chunk,
    ``"auto"`` turns it on exactly when there is something to overlap
    (a chunked stream with more than one chunk). All three are
    bit-identical — the pipeline moves WHEN sampling happens, never
    what is sampled.

    Key-splitting / CRN contract: unchanged from the legacy ``sweep``
    (see the module design note) — ``Scenario.paper_default`` consumes
    the key identically to the pre-scenario engine, and every policy /
    service model consumes the SAME draws, so with ``chunk_size=None``,
    seed s, variant j sees bit-identical inputs to
    ``simulate_grid(split(key, n_seeds)[s], dist, rhos, cfg, ks[j])``.
    With ``chunk_size=T``, chunk c draws from ``fold_in(key, c)`` at
    ``n_arrivals=T`` through the same per-seed samplers.

    ``warmup_frac`` and ``client_overhead`` come from the Scenario, NOT
    from ``cfg`` (the legacy shims copy them over).

    Each call is one ``repro.run`` profiler span, tagged with a
    process-wide engine-call id (``call``) that the engine's inner
    ``repro.*`` spans repeat, and with the call's shape: ``cells``,
    ``chunks``, the resolved ``kernel`` and ``pipeline``, ``devices``.
    """
    dist_list, warmup_frac, variants = scenario_mod.combine(scenario)
    if pipeline not in ("auto", "on", "off"):
        raise ValueError(f"pipeline must be 'auto', 'on' or 'off', "
                         f"got {pipeline!r}")
    if pipeline == "auto":
        pipeline = ("on" if chunk_size is not None
                    and cfg.n_arrivals > int(chunk_size) else "off")
    mesh = launch_mesh.resolve_mesh(mesh)
    has_dists = scenario_mod.any_dist_ids(variants)
    d = len(dist_list)
    n_seeds_total = n_seeds if has_dists else d * n_seeds
    use_kernel = cell_ops.resolve_kernel_mode(
        kernel, n_bins=n_bins if percentiles else None)
    call = next(_ENGINE_CALLS)
    with TraceAnnotation(
            "repro.run", call=call,
            cells=n_seeds_total * len(rhos) * len(variants),
            chunks=_chunk_layout(cfg, chunk_size, need_hist=False)[1],
            kernel=use_kernel, pipeline=pipeline,
            devices=1 if mesh is None else mesh.devices.size):
        rhos = jnp.asarray(rhos)
        k_max = max(v.k for v in variants)
        with_shared = scenario_mod.any_server_dependent(variants)
        with_degr = scenario_mod.any_degraded(variants)
        if d == 1:
            sampler = _sweep_sampler(key, dist_list[0], cfg, k_max,
                                     n_seeds, chunk_size,
                                     with_shared=with_shared,
                                     with_degr=with_degr)
        elif has_dists:
            # heterogeneous grid: the dist union stacks service TABLES
            # only; the plan's seed axis stays n_seeds and each cell
            # routes to its system's table via its dist_id (no per-dist
            # output axis — the variant axis already carries "which
            # system")
            sampler = _dist_table_sampler(key, dist_list, cfg, k_max,
                                          n_seeds, chunk_size,
                                          with_shared=with_shared,
                                          with_degr=with_degr)
        else:
            sampler = _sweep_dists_sampler(key, dist_list, cfg, k_max,
                                           n_seeds, chunk_size,
                                           with_shared=with_shared,
                                           with_degr=with_degr)

        kwargs = dict(variants=variants, warmup_frac=warmup_frac,
                      percentiles=tuple(percentiles), n_bins=n_bins,
                      chunk_size=chunk_size, use_kernel=use_kernel,
                      pipeline=pipeline, call=call)
        if mesh is not None:
            from repro.distributed.sweep_shard import _sweep_cells_sharded
            out = _sweep_cells_sharded(sampler, n_seeds_total, rhos, cfg,
                                       mesh=mesh, **kwargs)
        else:
            out = _run_engine(sampler, n_seeds_total, rhos, cfg, **kwargs)
        if d > 1 and not has_dists:
            out = {k: (v.reshape((d, n_seeds) + v.shape[1:])
                       if isinstance(v, jax.Array) else v)
                   for k, v in out.items()}
        return out


def _warn_deprecated_shim(name: str) -> None:
    warnings.warn(
        f"queueing.{name} is a deprecated paper-default shim; use "
        f"queueing.run with a Scenario (bit-identical output)",
        DeprecationWarning, stacklevel=3)


def sweep(key: Array, dist: ServiceDist, rhos: Array, cfg: SimConfig, *,
          ks: tuple[int, ...] = (1, 2), n_seeds: int = 2,
          percentiles: tuple[float, ...] = DEFAULT_PERCENTILES,
          n_bins: int = DEFAULT_BINS,
          chunk_size: int | None = None,
          kernel: str = "auto") -> dict[str, Array]:
    """Fused multi-(k, seed, load) sweep of the PAPER's model.

    .. deprecated:: Thin shim over ``run(key, Scenario.paper_default(
       dist, ks=ks, ...), rhos, cfg, ...)`` — bit-identical output
       (emits ``DeprecationWarning``); prefer ``run`` (it also
       expresses cancellation / dispatch-to-idle policies,
       server-dependent service and mixed grids).

    Summary shapes, chunking and the CRN contract are exactly ``run``'s
    (single-dist layout): ``(n_seeds, len(rhos), len(ks))``.
    """
    _warn_deprecated_shim("sweep")
    scn = Scenario.paper_default(dist, ks=tuple(int(k) for k in ks),
                                 client_overhead=cfg.client_overhead,
                                 warmup_frac=cfg.warmup_frac)
    return run(key, scn, rhos, cfg, n_seeds=n_seeds,
               percentiles=percentiles, n_bins=n_bins,
               chunk_size=chunk_size, kernel=kernel)


def sweep_dists(key: Array, dist_list, rhos: Array, cfg: SimConfig, *,
                ks: tuple[int, ...] = (1, 2), n_seeds: int = 2,
                percentiles: tuple[float, ...] = DEFAULT_PERCENTILES,
                n_bins: int = DEFAULT_BINS,
                chunk_size: int | None = None,
                kernel: str = "auto") -> dict[str, Array]:
    """Sweep MANY service-time distributions in one engine call by stacking
    them along the seed axis; summaries gain a leading dist axis
    ``(len(dist_list), n_seeds, len(rhos), len(ks))``.

    .. deprecated:: Thin shim over ``run`` with a multi-``dists``
       ``Scenario.paper_default`` — bit-identical output (emits
       ``DeprecationWarning``); prefer ``run``.
    """
    _warn_deprecated_shim("sweep_dists")
    dist_list = tuple(dist_list)
    scn = Scenario.paper_default(dist_list, ks=tuple(int(k) for k in ks),
                                 client_overhead=cfg.client_overhead,
                                 warmup_frac=cfg.warmup_frac)
    out = run(key, scn, rhos, cfg, n_seeds=n_seeds,
              percentiles=percentiles, n_bins=n_bins,
              chunk_size=chunk_size, kernel=kernel)
    if len(dist_list) == 1:  # run() adds the dist axis only for d > 1
        out = {k: (v[None] if isinstance(v, jax.Array) else v)
               for k, v in out.items()}
    return out


def mean_response(key: Array, dist: ServiceDist, rhos: Array, cfg: SimConfig,
                  k: int, n_seeds: int = 1,
                  chunk_size: int | None = None,
                  kernel: str = "auto") -> Array:
    """Post-warmup mean response (B,) averaged over ``n_seeds`` seeds."""
    scn = Scenario.paper_default(dist, ks=(int(k),),
                                 client_overhead=cfg.client_overhead,
                                 warmup_frac=cfg.warmup_frac)
    out = run(key, scn, rhos, cfg, n_seeds=n_seeds,
              percentiles=(), chunk_size=chunk_size, kernel=kernel)
    return jnp.mean(out["mean"][:, :, 0], axis=0)


def replication_gain(key: Array, dist: ServiceDist, rhos: Array,
                     cfg: SimConfig, k: int = 2, n_seeds: int = 2,
                     chunk_size: int | None = None,
                     mesh: jax.sharding.Mesh | None = None,
                     kernel: str = "auto") -> Array:
    """mean_k1(rho) - mean_k(rho), CRN-paired per seed. Positive = k helps.

    .. deprecated:: Thin shim over ``run`` with a paper-default
       ``Scenario`` at ``ks=(1, k)`` (emits ``DeprecationWarning``);
       prefer ``run`` + a paired-gain reduction (or
       ``threshold.scenario_gain``).

    ``mesh`` routes the sweep through the sharded cell-plan executor
    (bit-identical to the local path; see the module CRN contract)."""
    _warn_deprecated_shim("replication_gain")
    scn = Scenario.paper_default(dist, ks=(1, int(k)),
                                 client_overhead=cfg.client_overhead,
                                 warmup_frac=cfg.warmup_frac)
    out = run(key, scn, rhos, cfg, n_seeds=n_seeds, percentiles=(),
              chunk_size=chunk_size, mesh=mesh, kernel=kernel)
    m = out["mean"]  # (S, B, 2)
    return jnp.mean(m[:, :, 0] - m[:, :, 1], axis=0)
