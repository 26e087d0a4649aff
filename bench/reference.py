"""Plain reference of the engine's semantics, independent of the program.

The program under test is ``repro.core.queueing.run`` (and
``repro.core.threshold.threshold_bisect`` on top of it). This module
imports nothing of it. It re-derives each cell's inputs from the query
key by the engine's documented key contract, simulates the paper's
model (N independent FIFO servers; each request copied to k distinct
servers chosen uniformly at random; every copy served to completion;
the response is the first copy's finish, plus the client overhead when
k > 1) one arrival at a time in float64 numpy, and reads exact order
statistics where the engine reads its histogram sketch.

Key contract (``queueing`` module notes): chunk ``c`` of a chunked
stream draws from ``fold_in(key, c)`` at the chunk's full length (an
unchunked stream from ``key`` itself); seed ``s`` of a chunk from
``split(chunk_key, n_seeds)[s]``, split again in four: unit-rate
exponential gaps, the first copy's server, the uniform scores whose
top ``k_max - 1`` pick the other copies' offsets, and the service key,
whose ``fold_in(., j)`` draws copy ``j``'s service time. Every law and
load of a seed shares its arrivals and copy sets.

Only the paper's policy (replicate to all, i.i.d. service, no
degradation) is implemented; any other asks for an extension here and
raises.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# The engine's documented histogram geometry (``hist_sketch.ops``): the
# comparison measures percentile gaps in its bins.
HIST_LO, HIST_HI, N_BINS = 1e-3, 1e5, 2048
LOG_BIN = math.log(HIST_HI / HIST_LO) / (N_BINS - 1)


@dataclasses.dataclass(frozen=True)
class Grid:
    """One engine call's cells, in the engine's output layout.

    ``laws`` are the law entries of the config (engine dist order);
    ``columns`` the variant axis as ``(k, overhead, law index)``;
    ``stacked`` whether the laws stack as a leading axis (one scenario
    with several laws) or each column names its own law (a mixed grid
    of single-law scenarios)."""

    laws: tuple
    columns: tuple[tuple[int, float, int], ...]
    stacked: bool
    n_servers: int
    warmup_frac: float

    @property
    def k_max(self) -> int:
        return max(c[0] for c in self.columns)


def grid_of(config: dict, laws: list[dict]) -> Grid:
    """The reference's view of a config's grid (``Scenario`` fields)."""
    scns = config["scenarios"]
    for s in scns:
        if (s.get("policy", "replicate_all") != "replicate_all"
                or s.get("service_model", "iid") != "iid"
                or any(float(v) > 0 for k, v in
                       s.get("degradation", {}).items() if k != "slow_factor")):
            raise NotImplementedError(
                "the plain reference implements the paper's policy only "
                f"(replicate_all, iid, healthy); got {s}")
    warm = {float(s.get("warmup_frac", 0.1)) for s in scns}
    assert len(warm) == 1, "a grid shares one warm-up fraction"
    stacked = all(s["dists"] == scns[0]["dists"] for s in scns)
    cols = []
    for s in scns:
        law = 0 if stacked else laws.index(s["dists"][0])
        for k in s.get("ks", (1, 2)):
            ovh = float(s.get("client_overhead", 0.0)) if int(k) > 1 else 0.0
            cols.append((int(k), ovh, law))
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
    servers (S, M, k_max) int, services (L, S, M, k_max) float64."""
    import jax

    cpu = jax.devices("cpu")[0]
    n, k_max = grid.n_servers, grid.k_max
    t = n_arrivals if chunk is None else min(int(chunk), n_arrivals)
    n_chunks = -(-n_arrivals // t)
    gaps, servers = [], []
    services = []
    with jax.default_device(cpu):
        key = jax.device_put(key, cpu)
        for c in range(n_chunks):
            ck = key if chunk is None else jax.random.fold_in(key, c)
            keep = min(t, n_arrivals - c * t)
            g_c, s_c, v_c = [], [], []
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
            gaps.append(np.stack(g_c))
            servers.append(np.stack(s_c))
            services.append(np.stack(v_c, axis=1))        # (L, S, keep, k)
    return (np.concatenate(gaps, axis=1), np.concatenate(servers, axis=1),
            np.concatenate(services, axis=2))


# --- simulation ----------------------------------------------------------------

def simulate(cum, servers, services, cells, n_servers: int, dtype=np.float64):
    """Per-cell response times (C, M) of replicate-to-all FIFO servers.

    ``cum`` (S, M) cumulative unit-rate arrival offsets, ``servers``
    (S, M, k_max), ``services`` (R, M, k_max); ``cells`` a dict of (C,)
    arrays: ``row`` (seed row), ``svc_row``, ``rate``, ``k``. All
    arithmetic is in ``dtype``."""
    row, svc_row = cells["row"], cells["svc_row"]
    n_cells, k_max = len(row), servers.shape[-1]
    inv_rate = (1.0 / cells["rate"]).astype(dtype)
    mask = np.arange(k_max)[None, :] < cells["k"][:, None]
    cum_t = np.ascontiguousarray(cum.T.astype(dtype))            # (M, S)
    srv_t = np.ascontiguousarray(np.moveaxis(servers, 1, 0))     # (M, S, k)
    svc_t = np.ascontiguousarray(np.moveaxis(services, 1, 0).astype(dtype))
    free = np.zeros((n_cells, n_servers), dtype)
    out = np.empty((cum.shape[1], n_cells), dtype)
    lanes = np.arange(n_cells)[:, None]
    inf = np.asarray(np.inf, dtype)
    for i in range(cum.shape[1]):
        t = cum_t[i][row] * inv_rate
        srv = srv_t[i][row]
        cur = free[lanes, srv]
        fin = np.maximum(cur, t[:, None]) + svc_t[i][svc_row]
        free[lanes, srv] = np.where(mask, fin, cur)
        out[i] = np.where(mask, fin, inf).min(axis=1) - t
    return out.T


def order_stats(resp: np.ndarray, percentiles) -> dict[str, np.ndarray]:
    """For each percentile q, the value of rank ceil(q/100 * n) along the
    last axis: the response whose histogram bin the engine's sketch
    reads for q. One partition serves every rank."""
    n = resp.shape[-1]
    ranks = {p: min(max(int(math.ceil(float(p) / 100.0 * n)), 1), n) - 1
             for p in percentiles}
    if not ranks:
        return {}
    part = np.partition(np.ascontiguousarray(resp),
                        sorted(set(ranks.values())), axis=-1)
    return {f"p{p:g}": part[..., r] for p, r in ranks.items()}


def run_grids(items, grid: Grid, n_seeds: int, n_arrivals: int,
              chunk: int | None, percentiles=(), dtype=np.float64) -> list:
    """Summaries of engine calls, one per ``(key, loads)`` item, in the
    engine's layout: ``mean``, ``p<q>`` and ``completed``, each
    (S, B, V), or (L, S, B, V) for a stacked grid of several laws. All
    items run in one pass over the arrivals."""
    n_laws = len(grid.laws) if grid.stacked else 1
    squeeze = not (grid.stacked and len(grid.laws) > 1)
    gaps, servers, services, parts = [], [], [], []
    k_of = np.asarray([c[0] for c in grid.columns])
    ovh_of = np.asarray([c[1] for c in grid.columns])
    law_of = np.asarray([c[2] for c in grid.columns])
    for q, (key, loads) in enumerate(items):
        g, sv, svc = draw_inputs(key, grid, n_seeds, n_arrivals, chunk)
        gaps.append(g)
        servers.append(sv)
        services.append(svc)
        loads = np.asarray(loads, np.float32)
        shape = (n_laws, n_seeds, len(loads), len(grid.columns))
        d, s, b, v = (x.ravel() for x in np.meshgrid(
            *(np.arange(x) for x in shape), indexing="ij"))
        law = d if grid.stacked else law_of[v]
        parts.append((shape, {
            "row": q * n_seeds + s, "law": law, "k": k_of[v], "ovh": ovh_of[v],
            "rate": (np.float32(grid.n_servers) * loads[b]).astype(
                np.float64)}))
    n_rows = len(items) * n_seeds
    cells = {name: np.concatenate([p[1][name] for p in parts])
             for name in parts[0][1]}
    cells["svc_row"] = cells["law"] * n_rows + cells["row"]
    services = np.concatenate(services, axis=1)         # (L, Q*S, M, k)
    resp = simulate(np.cumsum(np.concatenate(gaps), axis=1),
                    np.concatenate(servers),
                    services.reshape((-1,) + services.shape[2:]),
                    cells, grid.n_servers, dtype)
    resp = resp.astype(np.float64) + cells["ovh"][:, None]
    warm = resp[:, int(n_arrivals * grid.warmup_frac):]
    summary = {"mean": warm.mean(axis=1),
               "completed": np.full(len(resp), float(warm.shape[1]))}
    summary.update(order_stats(warm, percentiles))
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
