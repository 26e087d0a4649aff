"""The four-chip cell ``paper-20srv.tail-sweep-4chip`` (``paper-20srv``
with 16 seeds on ``make_sweep_mesh(4)``, 256 cells per device), run
from a copy of the benchmark at a tiny size on four virtual CPU devices
in a child process (the device count is fixed when JAX starts): a
sound run is correct, and a run whose finalize sees only the first
device's cells (the exchange between chips left out) is not."""
import json
import os
import subprocess
import sys

import bench_testlib

CHILD = r"""
import json, sys, time
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import bench_testlib
from bench import harness
from repro.core import queueing

root = bench_testlib.tiny_copy(Path(sys.argv[2]))
finalize = queueing._finalize_summary

def first_device_only(plan, ssum, cnt, hist, *args, **kw):
    keep = ssum.shape[0] // 4
    cut = lambda x: x.at[keep:].set(0)
    return finalize(plan, cut(ssum), cut(cnt),
                    cut(hist) if hist.size else hist, *args, **kw)

for fault in (False, True):
    queueing._finalize_summary = first_device_only if fault else finalize
    result, _ = bench_testlib.run_cell(root, "paper-20srv.tail-sweep-4chip")
    print(json.dumps({"fault": fault, "correct": result["correct"]}))
"""


def test_four_device_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(bench_testlib.ROOT / "bench" /
                                          "tests"), str(tmp_path / "b")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    assert rows == [{"fault": False, "correct": True},
                    {"fault": True, "correct": False}], out.stdout
