"""The control of the correctness check: the plain reference put in the
program's place, computed in the nearest precision below the program's
float32, bfloat16. It has to come out as not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--queries 1]

For each seed it draws the check's sample of queries as a run would
(queries ``0 .. queries-1`` stand for the window's), computes the
reference in float64 and again in bfloat16, compares the second as the
program's outputs by the cell's own check, and prints each number
beside its limit and whether the control was caught. Needs no chip; it
runs at the cell's own size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, drive, reference, spec  # noqa: E402

CONTROL_DTYPE = ml_dtypes.bfloat16


def stream_numbers(cell: spec.Cell, seed: int, n_queries: int,
                   dtype=CONTROL_DTYPE) -> dict[str, float]:
    tr = cell.traffic
    grid = reference.grid_of(cell.config, spec.reference_laws(cell.config))
    loads = np.linspace(*tr["loads"][:2], int(tr["loads"][2]),
                        dtype=np.float32)
    picked = check.sample_queries(n_queries, int(tr["check"]["queries"]),
                                  seed)
    items = [(drive.query_key(seed, q), loads) for q in picked]
    args = (grid, int(tr["n_seeds"]), int(tr["arrivals"]), tr.get("chunk"),
            tuple(float(p) for p in tr.get("percentiles", ())))
    refs = reference.run_grids(items, *args)
    ctrl = reference.run_grids(items, *args, dtype=dtype)
    return check._merge([check.compare_summaries(c, r)
                         for c, r in zip(ctrl, refs)])


def threshold_numbers(cell: spec.Cell, seed: int, n_queries: int,
                      dtype=CONTROL_DTYPE) -> dict[str, float]:
    import jax

    tr = cell.traffic
    law_cfg = drive.law_config(cell.config, int(tr.get("law", 0)))
    law_cfg["scenarios"][0]["ks"] = [1, int(tr["k"])]
    grid = reference.grid_of(law_cfg, spec.reference_laws(law_cfg))
    gaps = []
    for q in check.sample_queries(n_queries, int(tr["check"]["queries"]),
                                  seed):
        keys = jax.random.split(drive.query_key(seed, q), int(tr["iters"]) + 1)
        runs = {}

        def evaluate(call, loads, dt):
            r = reference.run_grids(
                [(keys[call], np.asarray(loads, np.float32))], grid,
                int(tr["n_seeds"]), int(tr["arrivals"]), None, dtype=dt)[0]
            runs.setdefault(dt, []).append((call, tuple(loads), r))
            return [float(g) for g in reference.paired_gain(r["mean"])]

        bisect = (lambda dt: reference.bisect(
            lambda c, lo: evaluate(c, lo, dt), float(tr["lo"]),
            float(tr["hi"]), int(tr["iters"])))
        ctrl_answer = bisect(dtype)
        ref_answer = bisect(np.float64)
        # the control's calls, each against the reference at its loads
        pairs = [(r, reference.run_grids(
            [(keys[c], np.asarray(lo, np.float32))], grid,
            int(tr["n_seeds"]), int(tr["arrivals"]), None)[0])
            for c, lo, r in runs[dtype]]
        gaps.append(check._merge(
            [{"mean_rel": check.compare_summaries(
                {"mean": c["mean"]}, r)["mean_rel"]} for c, r in pairs]
            + [{"threshold_gap": abs(ctrl_answer - ref_answer)}]))
    return check._merge(gaps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=6,
                    help="queries the window is taken to have completed")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    spec.import_program()
    numbers = (threshold_numbers if cell.entry == "threshold_bisect"
               else stream_numbers)
    limits = cell.traffic["check"]["limits"]
    for seed in args.seeds:
        got = numbers(cell, seed, args.queries)
        caught = any(not (np.isfinite(v) and v <= limits[n])
                     for n, v in got.items() if n in limits)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": np.dtype(CONTROL_DTYPE).name,
                          "caught": caught,
                          "numbers": {n: {"value": v, "limit": limits.get(n)}
                                      for n, v in got.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
