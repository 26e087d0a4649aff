"""One run of one cell: set-up, a measured window, the correctness check
and the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window) loads the
program, points JAX's persistent compilation cache at the fixed
``.bench_cache/jax`` of the checkout and warms the cell's shapes with
one query of its own key. The window then sends queries back to back,
one client in a closed loop, until ``--seconds`` have passed; the last
query runs to its end. ``copy_steps_per_s`` is the copy-steps of all
completed queries over the time from the window's start to the end of
the last of them, and ``query_s`` that time over the queries completed.
With ``--trace 1`` the window runs under the profiler and the line
carries the cell's per-layer metrics instead, read from the trace by the
files of ``bench/metrics``.

After the window the memory peak is read, then a seeded sample of the
queries is checked against the plain reference (``check.py``). Each
number compared is printed beside its limit as the last lines of
standard error and under ``checks``, the last key of the result line,
which is the last line of standard output. Without an accelerator, or
with fewer devices than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

from bench import spec

CACHE_DIR = Path(".bench_cache") / "jax"
TRACE_DIR = Path(".bench_cache") / "trace"


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax(root: Path) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Context:
    """What a per-layer reader sees."""

    def __init__(self, reduced, calls, device_kind: str):
        self.reduced = reduced          # trace.Reduced of the window
        self.calls = calls              # work.Call of each engine call
        self.device_kind = device_kind
        self.notes: list[str] = []      # lines the run prints


def read_metric(root: Path, name: str, ctx: Context):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


def say(*parts, err: bool = False) -> None:
    print(*parts, file=sys.stderr if err else sys.stdout, flush=True)


def main(argv, *, t_start: float, root: Path = spec.ROOT,
         require_accelerator: bool = True, compile_cache: bool = True) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    spec.import_program(root)
    if compile_cache:
        configure_jax(root)

    import jax

    from bench import check, drive, trace

    devices = jax.devices()
    platform = devices[0].platform
    if require_accelerator and (platform not in ("tpu", "gpu")
                                or len(devices) < cell.chips):
        say(f"bench: needs {cell.chips} accelerator(s); JAX found "
            f"{len(devices)} {platform} device(s)", err=True)
        return 3
    used = devices[:cell.chips]
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_sweep_mesh

        mesh = make_sweep_mesh(cell.chips)
    clock, gc_clock = drive.CompileClock(), drive.GcClock()
    queries = drive.make_queries(cell, mesh)
    queries(drive.query_key(args.seed, drive.WARMUP_QUERY))
    setup_compile = clock.mark()

    records: dict[int, dict] = {}
    ends: list[float] = []
    host: list = []  # (process CPU times, GC seconds) at start and end
    failed = 0

    def window():
        nonlocal failed
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            t0 = time.perf_counter()
            host.append((os.times(), gc_clock.seconds))
            while True:
                q = len(records) + failed
                try:
                    with jax.profiler.TraceAnnotation("bench.query"):
                        records[q] = queries(drive.query_key(args.seed, q))
                except Exception as exc:  # a query that fails counts
                    failed += 1
                    say(f"query {q} failed: {exc!r}", err=True)
                t_end = time.perf_counter()
                ends.append(t_end)
                if t_end - t0 >= args.seconds:
                    host.append((os.times(), gc_clock.seconds))
                    return t0, t_end

    if args.trace:
        (t0, t_end), xplane = trace.capture(str(root / TRACE_DIR), window)
    else:
        t0, t_end = window()
    setup_s = t0 - t_start
    window_compile = clock.mark()
    (cpu0, gc0), (cpu1, gc1) = host
    host_cpu_s = (cpu1.user + cpu1.system) - (cpu0.user + cpu0.system)
    gc_s = gc1 - gc0
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in used) if platform != "cpu" else 0

    elapsed = t_end - t0
    calls = [c for r in records.values() for c in r["calls"]]
    say(f"setup compiles={setup_compile[1]} cache_hits={setup_compile[2]} "
        f"compile_s={setup_compile[0]!r} setup_s={setup_s!r}")
    say(f"window compiles={window_compile[1] - setup_compile[1]} "
        f"cache_hits={window_compile[2] - setup_compile[2]} "
        f"compile_s={window_compile[0] - setup_compile[0]!r} "
        f"queries={len(records)} failed={failed} elapsed_s={elapsed!r} "
        f"engine_calls_per_query={len(calls) / max(len(records), 1)!r} "
        f"host_cpu_s={host_cpu_s!r} gc_s={gc_s!r} "
        f"query_s_each={[round(b - a, 4) for a, b in zip([t0] + ends, ends)]}")
    from repro.core import chunkflow

    stats = chunkflow.last_stats()
    if stats is not None:
        say("pipeline " + json.dumps(chunkflow.stats_provenance()))

    device = {"platform": platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics: dict[str, dict] = {}
    breakdown = None
    if args.trace:
        reduced = trace.reduce(xplane)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        ctx = Context(reduced, calls, used[0].device_kind)
        for m in cell.per_layer:
            value = read_metric(root, m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        for note in ctx.notes:
            say(note)
        top = sorted(reduced.program_ns.items(), key=lambda x: -x[1])[:10]
        breakdown = {"device_ops": [[n, ns * 1e-9] for n, ns in top],
                     "idle_gaps": [[n, ns * 1e-9]
                                   for n, ns in reduced.gaps[:10]]}
    else:
        values = {"setup_s": setup_s,
                  "copy_steps_per_s": sum(c.copy_steps for c in calls)
                  / elapsed,
                  "query_s": elapsed / max(len(records), 1)}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    correct, numbers = check.verify(queries, records, args.seed,
                                    cell.traffic["check"])
    correct = correct and failed == 0
    result = {"correct": correct, "attempted": len(records) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # a gap that is not finite (no number, or infinitely far) reads null
    result["checks"] = {n: {"value": v if math.isfinite(v) else None,
                            "limit": lim} for n, (v, lim) in numbers.items()}
    for n, (v, lim) in numbers.items():
        say(f"check {n} value={v!r} limit={lim!r}", err=True)
    say(json.dumps(result))
    return 0
