"""The comparison that decides ``correct``.

After the window closes, a sample of the completed queries, drawn from
the seed, is recomputed by the plain reference (``reference.py``) from
the same keys, and each number below is held to the limit the cell's
traffic file gives under ``check.limits``:

- ``mean_rel``: the widest relative gap of a cell's post-warm-up mean;
- ``pct_bins``: the widest gap of a percentile, in log-bins of the
  engine's histogram sketch, between the sketch and the exact order
  statistic whose bin it reads. The sketch clamps values outside
  [``HIST_LO``, ``HIST_HI``] to its edge bins, so the order statistic is
  clamped alike: a response of 6e17 reads the top bin, as documented;
- ``completed_gap``: the widest gap of a cell's count of completed
  requests (exact). A cell in which the reference completes no request
  reads ``mean_rel`` inf: it has nothing to compare, and fails;
- ``threshold_gap``: for a bisection query, the gap between its answer
  and the reference's bisection over its own gains.

A number that is not finite fails its limit.
"""
from __future__ import annotations

import numpy as np

from bench import drive, reference


def sample_queries(n_done: int, n_check: int, seed: int) -> list[int]:
    rng = np.random.default_rng(int(seed) % (1 << 64))
    n = min(n_check, n_done)
    return sorted(int(q) for q in rng.choice(n_done, size=n, replace=False))


def _widest(values) -> float:
    """The largest of ``values``; NaN where any is NaN."""
    vals = [float(v) for v in values]
    return float("nan") if any(np.isnan(vals)) else max(vals)


def compare_summaries(prog: dict, ref: dict) -> dict[str, float]:
    """Gaps of one engine call's outputs against the reference's."""
    out = {}
    mean = np.asarray(prog["mean"], np.float64)
    pcts = [k for k in ref if k.startswith("p")]
    with np.errstate(divide="ignore", invalid="ignore"):  # 0 reads as inf
        rel = np.abs(mean / ref["mean"] - 1.0)
        if "completed" in ref:  # a cell with no completed request fails
            rel = np.where(np.asarray(ref["completed"]) > 0, rel, np.inf)
        out["mean_rel"] = _widest(rel.ravel())
        if pcts:
            out["pct_bins"] = _widest(
                np.abs(np.log(np.asarray(prog[k], np.float64)
                              / np.clip(ref[k], reference.HIST_LO,
                                        reference.HIST_HI))).max()
                / reference.LOG_BIN for k in pcts)
    if "completed" in prog:
        out["completed_gap"] = _widest(np.abs(
            np.asarray(prog["completed"], np.float64)
            - ref["completed"]).ravel())
    return out


def _merge(gaps: list[dict]) -> dict[str, float]:
    merged: dict[str, float] = {}
    for g in gaps:
        for k, v in g.items():
            merged[k] = _widest([merged.get(k, 0.0), v])
    return merged


def stream_gaps(queries, records: dict, seed: int, picked) -> dict:
    tr = queries.kw
    items = [(drive.query_key(seed, q), np.asarray(queries.loads))
             for q in picked]
    refs = reference.run_grids(items, queries.grid, tr["n_seeds"],
                               queries.sim.n_arrivals, tr["chunk_size"],
                               tr["percentiles"])
    return _merge([compare_summaries(records[q]["out"], r)
                   for q, r in zip(picked, refs)])


def threshold_gaps(queries, records: dict, seed: int, picked) -> dict:
    import jax

    kw, grid = queries.kw, queries.grid
    m, n_seeds = queries.sim.n_arrivals, kw["n_seeds"]
    gaps = []
    for q in picked:
        rec = records[q]
        keys = jax.random.split(drive.query_key(seed, q), kw["iters"] + 1)
        # engine call j of the query: the bracket first (key index
        # iters), then the bisection calls 0, 1, ... in order
        index = [kw["iters"]] + list(range(len(rec["engine_calls"]) - 1))
        probes = [np.asarray(r, np.float32) for r, _ in rec["engine_calls"]]
        refs = reference.run_grids(
            [(keys[i], p) for i, p in zip(index, probes)], grid, n_seeds, m,
            None)
        known = {(i, tuple(p.tolist())): reference.paired_gain(r["mean"])
                 for i, p, r in zip(index, probes, refs)}

        def evaluate(call, loads):
            loads = np.asarray(loads, np.float32)
            got = known.get((call, tuple(loads.tolist())))
            if got is None:
                r = reference.run_grids([(keys[call], loads)], grid,
                                        n_seeds, m, None)[0]
                got = reference.paired_gain(r["mean"])
            return [float(g) for g in got]

        answer = reference.bisect(evaluate, kw["lo"], kw["hi"], kw["iters"])
        gaps.append(_merge([compare_summaries({"mean": mean}, r)
                            for (_, mean), r in zip(rec["engine_calls"],
                                                    refs)]
                           + [{"threshold_gap": abs(rec["answer"]
                                                     - answer)}]))
    return _merge(gaps)


def verify(queries, records: dict, seed: int, check: dict):
    """(correct, {name: (value, limit)}) over a seeded sample of the
    completed queries ``records`` (query index -> record)."""
    picked = sample_queries(len(records), int(check.get("queries", 1)),
                            seed)
    if not picked:
        return False, {}
    gaps = (threshold_gaps if isinstance(queries, drive.ThresholdQueries)
            else stream_gaps)(queries, records, seed, picked)
    limits = check["limits"]
    numbers = {name: (gaps[name], float(limits[name])) for name in limits
               if name in gaps}
    missing = set(limits) - set(gaps)
    correct = not missing and all(
        np.isfinite(v) and v <= lim for v, lim in numbers.values())
    return correct, numbers
