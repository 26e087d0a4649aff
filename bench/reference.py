"""Plain reference of the engine's semantics, independent of the program.

The program under test is ``repro.core.queueing.run`` (and
``repro.core.threshold.threshold_bisect`` on top of it). This module
imports nothing of it. It re-derives each cell's inputs from the query
key by the engine's documented key contract, simulates N independent
FIFO servers one arrival at a time in float64 numpy, and reads exact
order statistics where the engine reads its histogram sketch.

Semantics (``scenario.Policy``, ``scenario.Degradation``): each request
has a budget of k copies on k distinct servers chosen uniformly at
random, with i.i.d. service.

- ``replicate_all`` (the paper's model) dispatches every copy at the
  arrival ``t``; ``hedge_after_delay`` and ``timeout_retry`` dispatch
  copy j at ``t + delay * c_j``, with ``c_j = j`` for hedging and
  ``c_j = sum_{i<j} min(2**i, 8)`` for retry's capped backoff, and only
  if no earlier surviving copy has finished by then; ``delay <= 0``
  fires every copy.
- A copy draws one uniform ``u``: it is a blackhole when ``u < p_fail``
  and a straggler when ``u >= 1 - p_slow``. A straggler's service is
  multiplied by ``slow_factor``; a blackhole never occupies its server
  and never responds. Retry's last attempt within its budget ignores its
  blackhole draw.
- Every fired, surviving copy occupies its server from
  ``max(free, dispatch)`` to its finish; the response is the first such
  finish, less ``t``, plus the client overhead when k > 1. A request with
  no surviving copy has no response: summaries are over the completed
  post-warm-up requests, with their count.

Key contract (``queueing`` module notes): chunk ``c`` of a chunked
stream draws from ``fold_in(key, c)`` at the chunk's full length (an
unchunked stream from ``key`` itself); seed ``s`` of a chunk from
``split(chunk_key, n_seeds)[s]``, split again in four: unit-rate
exponential gaps, the first copy's server, the uniform scores whose
top ``k_max - 1`` pick the other copies' offsets, and the service key,
whose ``fold_in(., j)`` draws copy ``j``'s service time and whose
``fold_in(fold_in(., 0xFA11ED), j)`` draws copy ``j``'s degradation
uniform. Every law and load of a seed shares its arrivals, copy sets
and degradation draws.

``cancel_on_complete``, ``replicate_to_idle`` and the
``server_dependent`` service model are not implemented; a grid that
asks for them raises.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import numpy as np

# The engine's documented histogram geometry (``hist_sketch.ops``): the
# comparison measures percentile gaps in its bins.
HIST_LO, HIST_HI, N_BINS = 1e-3, 1e5, 2048
LOG_BIN = math.log(HIST_HI / HIST_LO) / (N_BINS - 1)


TIMED = ("hedge_after_delay", "timeout_retry")
POLICIES = ("replicate_all",) + TIMED
DEGRADE_FOLD = 0xFA11ED  # fold_in index of the degradation uniforms
BACKOFF_CAP = 8          # retry waits at most 8 deadlines between attempts


class Column(NamedTuple):
    """One variant of a grid: a scenario at one replication ``k``."""

    k: int
    overhead: float
    law: int
    policy: str
    delay: float
    p_fail: float
    p_slow: float
    slow_factor: float

    @property
    def timed(self) -> bool:
        return self.policy in TIMED

    @property
    def degraded(self) -> bool:
        return self.p_fail > 0 or self.p_slow > 0

    def offsets(self) -> list[float]:
        """``c_j``: copy j's dispatch after the arrival, in delays."""
        if self.policy == "hedge_after_delay":
            return [float(j) for j in range(self.k)]
        if self.policy == "timeout_retry":
            return [float(sum(min(2 ** i, BACKOFF_CAP) for i in range(j)))
                    for j in range(self.k)]
        return [0.0] * self.k


@dataclasses.dataclass(frozen=True)
class Grid:
    """One engine call's cells, in the engine's output layout.

    ``laws`` are the law entries of the config (engine dist order);
    ``columns`` the variant axis; ``stacked`` whether the laws stack as
    a leading axis (one scenario with several laws) or each column names
    its own law (a mixed grid of single-law scenarios)."""

    laws: tuple
    columns: tuple[Column, ...]
    stacked: bool
    n_servers: int
    warmup_frac: float

    @property
    def k_max(self) -> int:
        return max(c.k for c in self.columns)

    @property
    def timed(self) -> bool:
        return any(c.timed for c in self.columns)

    @property
    def degraded(self) -> bool:
        return any(c.degraded for c in self.columns)


def grid_of(config: dict, laws: list[dict]) -> Grid:
    """The reference's view of a config's grid (``Scenario`` fields)."""
    scns = config["scenarios"]
    warm = {float(s.get("warmup_frac", 0.1)) for s in scns}
    assert len(warm) == 1, "a grid shares one warm-up fraction"
    stacked = all(s["dists"] == scns[0]["dists"] for s in scns)
    cols = []
    for s in scns:
        policy = s.get("policy", "replicate_all").lower()
        model = s.get("service_model", "iid").lower()
        if policy not in POLICIES or model != "iid":
            raise NotImplementedError(
                f"the plain reference implements {', '.join(POLICIES)} "
                f"over iid service; got policy={policy!r}, "
                f"service_model={model!r}")
        law = 0 if stacked else laws.index(s["dists"][0])
        degr = s.get("degradation", {})
        for k in s.get("ks", (1, 2)):
            ovh = float(s.get("client_overhead", 0.0)) if int(k) > 1 else 0.0
            cols.append(Column(
                int(k), ovh, law, policy,
                float(s.get("delay", 0.0)) if policy in TIMED else 0.0,
                float(degr.get("p_fail", 0.0)), float(degr.get("p_slow", 0.0)),
                float(degr.get("slow_factor", 1.0))))
    return Grid(laws=tuple(laws), columns=tuple(cols), stacked=stacked,
                n_servers=int(config["n_servers"]), warmup_frac=warm.pop())


# --- inputs ------------------------------------------------------------------

def _law_draws(law: dict, key, t: int) -> np.ndarray:
    """float64 service draws of one law from ``key``, by its definition."""
    import jax
    import jax.numpy as jnp

    if "module" in law:
        from bench.spec import law_module

        return np.asarray(law_module(law).reference_sample(law, key, (t,)),
                          np.float64)
    if "table" in law:
        q = np.asarray(law["table"], np.float64)
        n = len(q) - 1
        x = np.asarray(jax.random.uniform(key, (t,)), np.float64) * n
        idx = np.clip(np.floor(x).astype(np.int64), 0, n - 1)
        return q[idx] + (q[idx + 1] - q[idx]) * (x - idx)
    fam, args = law["family"], law.get("args", ())
    if fam == "exponential":
        return np.asarray(jax.random.exponential(key, (t,)), np.float64)
    if fam == "pareto":
        a = float(args[0])
        u = np.asarray(jax.random.uniform(
            key, (t,), minval=jnp.finfo(jnp.float32).tiny), np.float64)
        return (a - 1.0) / a * u ** (-1.0 / a)
    raise NotImplementedError(f"reference has no law {law}")


def draw_inputs(key, grid: Grid, n_seeds: int, n_arrivals: int,
                chunk: int | None):
    """The stream's inputs by the key contract: gaps (S, M) float64,
    servers (S, M, k_max) int, services (L, S, M, k_max) float64 and,
    for a degraded grid, the degradation uniforms (S, M, k_max) float64
    (the same for every law: they come from the seed's service key), or
    None."""
    import jax

    cpu = jax.devices("cpu")[0]
    n, k_max = grid.n_servers, grid.k_max
    t = n_arrivals if chunk is None else min(int(chunk), n_arrivals)
    n_chunks = -(-n_arrivals // t)
    gaps, servers, services, degr = [], [], [], []
    with jax.default_device(cpu):
        key = jax.device_put(key, cpu)
        for c in range(n_chunks):
            ck = key if chunk is None else jax.random.fold_in(key, c)
            keep = min(t, n_arrivals - c * t)
            g_c, s_c, v_c, u_c = [], [], [], []
            for sk in jax.random.split(ck, n_seeds):
                k_gap, k_first, k_extra, k_svc = jax.random.split(sk, 4)
                g_c.append(np.asarray(jax.random.exponential(k_gap, (t,)),
                                      np.float64)[:keep])
                first = np.asarray(jax.random.randint(k_first, (t,), 0, n))
                cols = [first]
                if k_max > 1:
                    scores = np.asarray(jax.random.uniform(k_extra,
                                                           (t, n - 1)))
                    order = np.argsort(-scores, axis=1, kind="stable")
                    cols += [(first + 1 + order[:, j]) % n
                             for j in range(k_max - 1)]
                s_c.append(np.stack(cols, axis=1)[:keep])
                v_c.append([np.stack([_law_draws(
                    law, jax.random.fold_in(k_svc, j), t)[:keep]
                    for j in range(k_max)], axis=1) for law in grid.laws])
                if grid.degraded:
                    k_deg = jax.random.fold_in(k_svc, DEGRADE_FOLD)
                    u_c.append(np.stack([np.asarray(jax.random.uniform(
                        jax.random.fold_in(k_deg, j), (t,)),
                        np.float64)[:keep] for j in range(k_max)], axis=1))
            gaps.append(np.stack(g_c))
            servers.append(np.stack(s_c))
            services.append(np.stack(v_c, axis=1))        # (L, S, keep, k)
            if grid.degraded:
                degr.append(np.stack(u_c))
    return (np.concatenate(gaps, axis=1), np.concatenate(servers, axis=1),
            np.concatenate(services, axis=2),
            np.concatenate(degr, axis=1) if degr else None)


# --- simulation ----------------------------------------------------------------

def simulate(cum, servers, services, cells, n_servers: int, dtype=np.float64,
             degr=None):
    """Per-cell response times (C, M), inf where a request has no
    response.

    ``cum`` (S, M) cumulative unit-rate arrival offsets, ``servers``
    (S, M, k_max), ``services`` (R, M, k_max), ``degr`` the degradation
    uniforms (S, M, k_max) or None for a healthy grid; ``cells`` a dict
    of (C,) arrays: ``row`` (seed row), ``svc_row``, ``rate``, ``k``;
    for a grid with a timed policy also ``delay``, ``retry`` and the
    dispatch offsets in delays ``coeff`` (C, k_max); for a degraded grid
    ``p_fail``, ``p_slow`` and ``slow_factor``. All arithmetic is in
    ``dtype``."""
    row, svc_row = cells["row"], cells["svc_row"]
    n_cells, k_max = len(row), servers.shape[-1]
    inv_rate = (1.0 / cells["rate"]).astype(dtype)
    copy = np.arange(k_max)[None, :]
    mask = copy < cells["k"][:, None]
    cum_t = np.ascontiguousarray(cum.T.astype(dtype))            # (M, S)
    srv_t = np.ascontiguousarray(np.moveaxis(servers, 1, 0))     # (M, S, k)
    svc_t = np.ascontiguousarray(np.moveaxis(services, 1, 0).astype(dtype))
    timed = "coeff" in cells
    if timed:
        delay = cells["delay"].astype(dtype)
        offset = delay[:, None] * cells["coeff"].astype(dtype)   # (C, k)
        fire_all = delay <= 0
        exempt = cells["retry"][:, None] & (copy == cells["k"][:, None] - 1)
    if degr is not None:
        u_t = np.ascontiguousarray(np.moveaxis(degr, 1, 0).astype(dtype))
        p_fail, p_slow, slow_factor = (cells[name].astype(dtype)[:, None]
                                       for name in ("p_fail", "p_slow",
                                                    "slow_factor"))
        slow_from = np.asarray(1, dtype) - p_slow
    free = np.zeros((n_cells, n_servers), dtype)
    out = np.empty((cum.shape[1], n_cells), dtype)
    lanes = np.arange(n_cells)[:, None]
    inf = np.asarray(np.inf, dtype)
    for i in range(cum.shape[1]):
        t = cum_t[i][row] * inv_rate
        srv = srv_t[i][row]
        cur = free[lanes, srv]
        svc = svc_t[i][svc_row]
        live = mask
        if degr is not None:
            u = u_t[i][row]
            svc = np.where(u >= slow_from, svc * slow_factor, svc)
            live = mask & (u >= p_fail)
        if timed:
            disp = t[:, None] + offset
            fin = np.maximum(cur, disp) + svc
            alive = live | exempt
            live = np.empty_like(mask)
            best = np.full(n_cells, inf)
            for j in range(k_max):
                fired = mask[:, j] if j == 0 else (
                    mask[:, j] & (fire_all | (best > disp[:, j])))
                live[:, j] = fired & alive[:, j]
                best = np.where(live[:, j], np.minimum(best, fin[:, j]),
                                best)
            out[i] = best - t
        else:
            fin = np.maximum(cur, t[:, None]) + svc
            out[i] = np.where(live, fin, inf).min(axis=1) - t
        free[lanes, srv] = np.where(live, fin, cur)
    return out.T


def order_stats(resp: np.ndarray, percentiles,
                completed: np.ndarray | None = None) -> dict[str, np.ndarray]:
    """For each percentile q, the value of rank ceil(q/100 * n) along the
    last axis: the response whose histogram bin the engine's sketch
    reads for q. ``completed`` gives each row's n where some responses
    are missing (inf, so they sort last); a row with none reads NaN.
    One partition serves every rank of complete rows."""
    n = resp.shape[-1]
    if completed is not None and (completed < n).any():
        ordered = np.sort(resp, axis=-1)
        out = {}
        for p in percentiles:
            rank = np.clip(np.ceil(float(p) / 100.0 * completed), 1, n) - 1
            got = np.take_along_axis(ordered, rank.astype(np.int64)[..., None],
                                     axis=-1)[..., 0]
            out[f"p{p:g}"] = np.where(completed > 0, got, np.nan)
        return out
    ranks = {p: min(max(int(math.ceil(float(p) / 100.0 * n)), 1), n) - 1
             for p in percentiles}
    if not ranks:
        return {}
    part = np.partition(np.ascontiguousarray(resp),
                        sorted(set(ranks.values())), axis=-1)
    return {f"p{p:g}": part[..., r] for p, r in ranks.items()}


def _column_arrays(grid: Grid) -> dict[str, np.ndarray]:
    """Per-column coordinates, indexed by variant, for ``simulate``."""
    cols = grid.columns
    out = {"k": np.asarray([c.k for c in cols]),
           "ovh": np.asarray([c.overhead for c in cols]),
           "law": np.asarray([c.law for c in cols])}
    if grid.timed:
        out["delay"] = np.asarray([c.delay for c in cols])
        out["retry"] = np.asarray([c.policy == "timeout_retry"
                                   for c in cols])
        out["coeff"] = np.asarray([c.offsets() + [0.0] * (grid.k_max - c.k)
                                   for c in cols])
    if grid.degraded:
        for name in ("p_fail", "p_slow", "slow_factor"):
            out[name] = np.asarray([getattr(c, name) for c in cols])
    return out


def run_grids(items, grid: Grid, n_seeds: int, n_arrivals: int,
              chunk: int | None, percentiles=(), dtype=np.float64) -> list:
    """Summaries of engine calls, one per ``(key, loads)`` item, in the
    engine's layout: ``mean``, ``p<q>`` and ``completed`` over the
    completed post-warm-up requests, each (S, B, V), or (L, S, B, V) for
    a stacked grid of several laws. All items run in one pass over the
    arrivals."""
    n_laws = len(grid.laws) if grid.stacked else 1
    squeeze = not (grid.stacked and len(grid.laws) > 1)
    gaps, servers, services, degr, parts = [], [], [], [], []
    per_col = _column_arrays(grid)
    for q, (key, loads) in enumerate(items):
        g, sv, svc, u = draw_inputs(key, grid, n_seeds, n_arrivals, chunk)
        gaps.append(g)
        servers.append(sv)
        services.append(svc)
        degr.append(u)
        loads = np.asarray(loads, np.float32)
        shape = (n_laws, n_seeds, len(loads), len(grid.columns))
        d, s, b, v = (x.ravel() for x in np.meshgrid(
            *(np.arange(x) for x in shape), indexing="ij"))
        cell = {name: a[v] for name, a in per_col.items()}
        if grid.stacked:
            cell["law"] = d
        cell.update(row=q * n_seeds + s, rate=(
            np.float32(grid.n_servers) * loads[b]).astype(np.float64))
        parts.append((shape, cell))
    n_rows = len(items) * n_seeds
    cells = {name: np.concatenate([p[1][name] for p in parts])
             for name in parts[0][1]}
    cells["svc_row"] = cells["law"] * n_rows + cells["row"]
    services = np.concatenate(services, axis=1)         # (L, Q*S, M, k)
    resp = simulate(np.cumsum(np.concatenate(gaps), axis=1),
                    np.concatenate(servers),
                    services.reshape((-1,) + services.shape[2:]),
                    cells, grid.n_servers, dtype,
                    np.concatenate(degr) if grid.degraded else None)
    resp = resp.astype(np.float64) + cells["ovh"][:, None]
    warm = resp[:, int(n_arrivals * grid.warmup_frac):]
    done = np.isfinite(warm)
    completed = done.sum(axis=1).astype(np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):  # none completed
        summary = {"mean": np.where(done, warm, 0.0).sum(axis=1) / completed,
                   "completed": completed}
    summary.update(order_stats(warm, percentiles, completed))
    out, at = [], 0
    for shape, _ in parts:
        n = int(np.prod(shape))
        out.append({name: a[at:at + n].reshape(shape[1:] if squeeze
                                               else shape)
                    for name, a in summary.items()})
        at += n
    return out


# --- threshold bisection ------------------------------------------------------

def paired_gain(mean: np.ndarray) -> np.ndarray:
    """(S, B, 2) means -> (B,) seed-averaged gain of k over k=1."""
    return (mean[:, :, 0] - mean[:, :, 1]).mean(axis=0)


def bisect(evaluate, lo: float, hi: float, iters: int) -> float:
    """Speculative bisection on the sign of the paired gain, as
    ``threshold_bisect`` documents it: one call brackets [lo, hi]; each
    further call evaluates the midpoint and both candidate next
    midpoints, resolving two levels; a quarter point, once chosen, is
    taken at the float32 load it was evaluated at (the loads an engine
    call takes are float32). ``evaluate(call, loads)`` returns
    the gains at ``loads`` of engine call ``call`` (the bracket is
    call ``iters``, the key index it draws from)."""
    g_lo, g_hi = evaluate(iters, [lo, hi])
    if g_hi > 0.0:
        return hi
    if g_lo < 0.0:
        return lo
    a, b = lo, hi
    level = call = 0
    while level < iters:
        mid = 0.5 * (a + b)
        if level + 1 < iters:
            probes = [0.5 * (a + mid), mid, 0.5 * (mid + b)]
            g_q_lo, g_mid, g_q_hi = evaluate(call, probes)
            if g_mid > 0.0:
                a, g_next, nxt = mid, g_q_hi, float(np.float32(probes[2]))
            else:
                b, g_next, nxt = mid, g_q_lo, float(np.float32(probes[0]))
            if g_next > 0.0:
                a = nxt
            else:
                b = nxt
            level += 2
        else:
            if evaluate(call, [mid])[0] > 0.0:
                a = mid
            else:
                b = mid
            level += 1
        call += 1
    return 0.5 * (a + b)
