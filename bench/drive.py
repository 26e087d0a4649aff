"""The queries a window sends: one client in a closed loop, each query a
call of the program's public entry point as users make it, with
``kernel`` and ``pipeline`` left at ``"auto"``.

Query ``q`` draws from ``fold_in(PRNGKey(seed), q)``, so no result can
be reused, and keeps the cell's shapes, so nothing compiles in the
window. Each query's outputs are kept for the correctness check.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, spec, work

WARMUP_QUERY = 0xFFFFFFF0  # fold_in index of the set-up query


def base_key(seed: int):
    """A PRNG key for any whole ``seed``: the 64 bits of ``seed`` mod
    2**64 as the key's two words (``PRNGKey(seed)`` for 0 <= seed < 2**32)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32)


def query_key(seed: int, q: int):
    return jax.random.fold_in(base_key(seed), q)


class CompileClock:
    """Sums the seconds of, and counts, the XLA backend compiles JAX
    reports through its monitoring events (one event per compiled
    program; tracing is not counted), and counts the programs loaded
    from the persistent compilation cache instead."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.hits


class GcClock:
    """Sums the seconds in which Python's garbage collector ran."""

    def __init__(self):
        self.seconds = 0.0
        self._start = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._start


class StreamQueries:
    """``queueing.run`` over the config's grid: every query streams
    ``arrivals`` arrivals through all cells."""

    def __init__(self, cell: spec.Cell, mesh=None):
        from repro.core import queueing

        tr, cfg = cell.traffic, cell.config
        self.queueing = queueing
        self.scenario = spec.build_scenario(cfg)
        self.sim = queueing.SimConfig(n_servers=int(cfg["n_servers"]),
                                      n_arrivals=int(tr["arrivals"]))
        self.loads = jnp.linspace(*tr["loads"][:2], int(tr["loads"][2]))
        self.kw = dict(n_seeds=int(tr["n_seeds"]),
                       chunk_size=tr.get("chunk"),
                       percentiles=tuple(float(p) for p in
                                         tr.get("percentiles", ())),
                       mesh=mesh)
        self.grid = reference.grid_of(cfg, spec.reference_laws(cfg))
        self.call = stream_call(self.grid, tr)

    def __call__(self, key) -> dict:
        out = self.queueing.run(key, self.scenario, self.loads, self.sim,
                                **self.kw)
        jax.block_until_ready(out)
        return {"out": out, "calls": (self.call,)}


def stream_call(grid: reference.Grid, tr: dict,
                n_loads: int | None = None) -> work.Call:
    """The work of one engine call over ``grid`` with traffic ``tr``."""
    n_seeds = int(tr["n_seeds"])
    n_loads = int(tr["loads"][2]) if n_loads is None else n_loads
    reps = (len(grid.laws) if grid.stacked else 1) * n_seeds * n_loads
    m = int(tr["arrivals"])
    return work.Call(
        ks=tuple(c.k for c in grid.columns) * reps,
        seed_rows=n_seeds, svc_rows=len(grid.laws) * n_seeds,
        k_max=grid.k_max, n_arrivals=m, chunk=tr.get("chunk"),
        warmup=int(m * grid.warmup_frac), n_servers=grid.n_servers,
        n_bins=reference.N_BINS if tr.get("percentiles") else 0,
        timed=tuple(c.timed for c in grid.columns) * reps,
        degraded=tuple(c.degraded for c in grid.columns) * reps)


class ThresholdQueries:
    """``threshold.threshold_bisect`` for one law of the config. The
    engine calls it makes are recorded (loads and means) under a host
    span of the benchmark's own, ``bench.engine_call``."""

    def __init__(self, cell: spec.Cell, mesh=None):
        from repro.core import queueing, threshold

        tr, cfg = cell.traffic, cell.config
        law_cfg = law_config(cfg, int(tr.get("law", 0)))
        self.threshold = threshold
        self.scenario = spec.build_scenario(law_cfg)
        self.sim = queueing.SimConfig(n_servers=int(cfg["n_servers"]),
                                      n_arrivals=int(tr["arrivals"]))
        self.kw = dict(k=int(tr["k"]), lo=float(tr["lo"]),
                       hi=float(tr["hi"]), iters=int(tr["iters"]),
                       n_seeds=int(tr["n_seeds"]), mesh=mesh)
        self.grid = reference.grid_of(
            dict(law_cfg, scenarios=[dict(law_cfg["scenarios"][0],
                                          ks=[1, int(tr["k"])])]),
            spec.reference_laws(law_cfg))
        self.tr = tr

    def __call__(self, key) -> dict:
        engine, made = self.threshold.run, []

        def recorded(key, scenario, rhos, cfg, **kw):
            with jax.profiler.TraceAnnotation("bench.engine_call"):
                out = engine(key, scenario, rhos, cfg, **kw)
            made.append((rhos, out["mean"]))
            return out

        self.threshold.run = recorded
        try:
            answer = self.threshold.threshold_bisect(key, self.scenario,
                                                     self.sim, **self.kw)
        finally:
            self.threshold.run = engine
        calls = tuple(stream_call(self.grid, self.tr, n_loads=len(r))
                      for r, _ in made)
        return {"answer": float(answer), "engine_calls": made,
                "calls": calls}


def law_config(config: dict, law: int) -> dict:
    """The config cut to its first scenario with only law ``law``."""
    first = dict(config["scenarios"][0])
    first["dists"] = [first["dists"][law]]
    return dict(config, scenarios=[first])


ENTRIES = {"run": StreamQueries, "threshold_bisect": ThresholdQueries}


def make_queries(cell: spec.Cell, mesh=None):
    return ENTRIES[cell.entry](cell, mesh)
