"""A witness for the correctness check: the plain reference run on the
program's own sampled inputs.

    python3 bench/witness.py --workload <cell> --seeds 1 2 3 [--query 0]

For each seed it runs query ``--query`` of a stream cell as a window
would, records the inputs the program's samplers drew for each chunk
(gaps, copy servers, service times and, in a degraded grid, the
degradation uniforms) on the way into the chunk body, and compares the
program's outputs by the cell's own check with two float64 references:
the reference on its own inputs (drawn on the CPU from the key, as the
check draws them) and the reference on the program's inputs. Where the
second gap is much the smaller, the first comes from the rounding of
the inputs (the samplers' last bits on the device that ran them), not
from the program's simulation. Prints one JSON line per seed with both
gaps, overall and per variant, and how far the two sets of inputs lie
apart. Runs on whatever device JAX gives the program.
"""
from __future__ import annotations

import argparse
import json
import sys
import unittest.mock
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import check, drive, reference, spec  # noqa: E402


def program_inputs(queries, key):
    """Run one query; its outputs and the inputs its samplers drew, in
    ``reference.draw_inputs``' layout."""
    import jax

    from repro.core import queueing

    drawn = []
    pad_inputs = queueing._pad_chunk_inputs

    def recorded(unit_gaps, servers, services, pad):
        drawn.append(tuple(np.asarray(jax.device_get(x))
                           for x in (unit_gaps, servers, services)))
        return pad_inputs(unit_gaps, servers, services, pad)

    with unittest.mock.patch.object(queueing, "_pad_chunk_inputs", recorded):
        out = queries(key)["out"]
    grid, m = queries.grid, queries.sim.n_arrivals
    if len(grid.laws) != 1:
        raise NotImplementedError("the witness reads single-law grids")
    k = grid.k_max
    gaps = np.concatenate([g for g, _, _ in drawn], axis=1)[:, :m]
    servers = np.concatenate([s for _, s, _ in drawn], axis=1)[:, :m]
    cols = np.concatenate([v for _, _, v in drawn], axis=1)[:, :m]
    degr = cols[..., -k:].astype(np.float64) if grid.degraded else None
    return out, (gaps.astype(np.float64), servers,
                 cols[None, ..., :k].astype(np.float64), degr)


def input_gaps(prog, own) -> dict[str, dict[str, float]]:
    """How far the program's inputs lie from the reference's own: the
    share of values that differ, and the widest relative difference."""
    out = {}
    for name, a, b in zip(("gaps", "servers", "services", "degradation"),
                          prog, own):
        if a is None:
            continue
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.where(a == b, 0.0, np.abs(a / b - 1.0))
        out[name] = {"share_differing": float(np.mean(a != b)),
                     "max_rel": float(rel.max())}
    return out


def per_variant(prog: dict, ref: dict) -> list[dict[str, float]]:
    """The check's gaps of each variant (last axis) on its own."""
    n_var = np.asarray(ref["mean"]).shape[-1]
    return [check.compare_summaries(
        {k: np.asarray(v)[..., j] for k, v in prog.items()
         if np.ndim(v) == np.ndim(ref["mean"])},
        {k: np.asarray(v)[..., j] for k, v in ref.items()})
        for j in range(n_var)]


def witness(cell: spec.Cell, seed: int, query: int = 0) -> dict:
    import jax

    queries = drive.make_queries(cell)
    if not isinstance(queries, drive.StreamQueries):
        raise NotImplementedError("the witness reads stream cells")
    key = drive.query_key(seed, query)
    out, prog = program_inputs(queries, key)
    out = {k: np.asarray(v) for k, v in out.items()}
    kw = queries.kw
    args = ([(key, np.asarray(queries.loads))], queries.grid, kw["n_seeds"],
            queries.sim.n_arrivals, kw["chunk_size"], kw["percentiles"])
    own_inputs = reference.draw_inputs(key, queries.grid, kw["n_seeds"],
                                       queries.sim.n_arrivals,
                                       kw["chunk_size"])

    def reference_on(inputs):
        with unittest.mock.patch.object(reference, "draw_inputs",
                                        lambda *a, **k: inputs):
            return reference.run_grids(*args)[0]

    own, on_prog = reference_on(own_inputs), reference_on(prog)
    return {"workload": cell.name, "seed": seed, "query": query,
            "device": jax.devices()[0].device_kind,
            "own_inputs": check.compare_summaries(out, own),
            "program_inputs": check.compare_summaries(out, on_prog),
            "per_variant": {"own_inputs": per_variant(out, own),
                            "program_inputs": per_variant(out, on_prog)},
            "inputs": input_gaps(prog, own_inputs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--query", type=int, default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    spec.import_program()
    for seed in args.seeds:
        print(json.dumps(witness(cell, seed, args.query)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
