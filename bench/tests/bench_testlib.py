"""Helpers of the benchmark's CPU tests: a copy of the benchmark's files
with tiny traffic, and one harness run in this process."""
from __future__ import annotations

import io
import json
import shutil
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def tiny_copy(dst: Path, arrivals: int = 1024, chunk: int = 512) -> Path:
    """The benchmark's files under ``dst`` with every traffic mix cut to
    ``arrivals`` arrivals (chunks of ``chunk``), the program linked in."""
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(ROOT / "src")
    for f in (dst / "bench" / "traffic").glob("*.json"):
        shrink(f, arrivals, chunk)
    return dst


def shrink(path: Path, arrivals: int, chunk: int) -> None:
    t = json.loads(path.read_text())
    t["arrivals"] = arrivals
    if t.get("chunk"):
        t["chunk"] = chunk
    path.write_text(json.dumps(t))


def run_cell(root: Path, workload: str, seed: int = 7,
             seconds: float = 0.2) -> tuple[dict, str]:
    """One harness run of ``workload`` in this process, without the look
    for an accelerator; returns the result line and standard error."""
    from bench import harness

    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0"],
                          t_start=time.perf_counter(), root=root,
                          require_accelerator=False,
                          compile_cache=False)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
