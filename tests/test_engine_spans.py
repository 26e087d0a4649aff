"""The engine's profiler spans (``repro.*``) and the chunk pipeline's wait
counters, traced on the CPU backend.

Each engine call is one ``repro.run`` span; inside it the set-up of its
cell plan (``repro.plan``), then the chunk loop marks each chunk's input
wait (``repro.chunk.input``), its sampling
(``repro.sample``, on the producer thread when pipelined), its staging
onto a mesh (``repro.stage``, sharded path only), its body's dispatch
(``repro.chunk.dispatch``), then the drain and ``repro.finalize``. The
spans are read back with the benchmark's own extraction
(``bench/spans.py``), which is what the per-layer readers use on a chip
trace; a CPU trace has no device planes, so only the host side is
checked here. Tracing must not change a single bit of the summaries.
"""
import json
import os
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spans, trace  # noqa: E402
from repro.core import chunkflow, queueing  # noqa: E402
from repro.core import distributions as dists  # noqa: E402

CFG = queueing.SimConfig(n_servers=5, n_arrivals=2048)
RHOS = jnp.asarray([0.2, 0.4])
SCN = queueing.Scenario.paper_default(dists.exponential(), ks=(1, 2))
KW = dict(n_seeds=2, percentiles=(50.0, 99.0))


def traced_spans(log_dir, fn):
    """``fn()`` under the profiler; its result and the trace's spans."""
    from jax.profiler import ProfileData

    out, path = trace.capture(str(log_dir), fn)
    return out, spans.host_spans(ProfileData.from_file(path))


def check_call(sp, run, n_chunks: int, *, pipelined: bool, sharded: bool):
    """The spans of the engine call whose ``repro.run`` span is ``run``;
    returns its ``call`` id."""
    call = run.meta["call"]
    assert run.meta["chunks"] == n_chunks
    mine = [s for s in sp if s.meta.get("call") == call]
    assert all(run.start_ns <= s.start_ns <= s.end_ns <= run.end_ns
               for s in mine)
    count = Counter(s.name for s in mine)
    for name in ("repro.chunk.input", "repro.chunk.dispatch",
                 "repro.sample"):
        assert sorted(s.meta["chunk"] for s in mine if s.name == name) \
            == list(range(n_chunks)), name
    assert count["repro.stage"] == (n_chunks if sharded else 0)
    assert count["repro.chunk.slot"] == (n_chunks if pipelined else 0)
    (plan,) = [s for s in mine if s.name == "repro.plan"]
    (fin,) = [s for s in mine if s.name == "repro.finalize"]
    assert plan.line == fin.line == run.line
    assert count["repro.drain"] == 1
    first = {s.name: s for s in mine if s.meta.get("chunk") == 0}
    assert plan.end_ns <= first["repro.chunk.input"].start_ns
    # the wait for chunk 0 holds its sampling, on either thread
    assert first["repro.chunk.input"].start_ns \
        <= first["repro.sample"].start_ns
    for s in mine:
        if s.name in ("repro.chunk.input", "repro.chunk.dispatch"):
            assert s.line == run.line, s  # the consumer's thread
        if s.name in ("repro.sample", "repro.stage", "repro.chunk.slot"):
            assert (s.line != run.line) == pipelined, s
    return call


def assert_same_bits(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def test_chunked_and_unchunked_spans(tmp_path):
    key = jax.random.PRNGKey(3)

    def both():
        t0 = time.perf_counter()
        chunked = queueing.run(key, SCN, RHOS, CFG, chunk_size=512, **KW)
        jax.block_until_ready(chunked)
        wall = time.perf_counter() - t0
        stats = chunkflow.last_stats()
        whole = queueing.run(key, SCN, RHOS, CFG, **KW)
        return chunked, wall, stats, jax.block_until_ready(whole)

    (chunked, wall, stats, whole), sp = traced_spans(tmp_path, both)
    runs = sorted((s for s in sp if s.name == "repro.run"),
                  key=lambda s: s.start_ns)
    assert len(runs) == 2
    first = check_call(sp, runs[0], 4, pipelined=True, sharded=False)
    second = check_call(sp, runs[1], 1, pipelined=False, sharded=False)
    assert second > first
    assert runs[0].meta["pipeline"] == "on"
    assert runs[1].meta["pipeline"] == "off"
    assert runs[0].meta["cells"] == 2 * 2 * 2
    assert runs[0].meta["devices"] == 1
    # serial: the inline sampling sits inside the consumer's input wait
    (inp,) = [s for s in sp if s.name == "repro.chunk.input"
              and s.meta["call"] == second]
    (smp,) = [s for s in sp if s.name == "repro.sample"
              and s.meta["call"] == second]
    assert inp.start_ns <= smp.start_ns <= smp.end_ns <= inp.end_ns

    assert stats.enabled and stats.n_chunks == 4
    assert 0 <= stats.input_wait_s < wall
    assert 0 <= stats.slot_wait_s < wall
    assert_same_bits(chunked, queueing.run(key, SCN, RHOS, CFG,
                                           chunk_size=512, **KW))
    assert_same_bits(whole, queueing.run(key, SCN, RHOS, CFG, **KW))


def test_serial_wait_counts_inline_sampling():
    waits = chunkflow.Waits()

    def produce(c):
        time.sleep(0.01)
        return c

    assert list(chunkflow.iter_staged(produce, 3, enabled=False,
                                      waits=waits)) == [0, 1, 2]
    assert waits.input_s >= 0.03 and waits.slot_s == 0.0


def test_pipelined_waits_split_by_side():
    """A slow consumer leaves the producer waiting for slots; a slow
    producer leaves the consumer waiting for inputs."""
    fast, slow = chunkflow.Waits(), chunkflow.Waits()
    for c in chunkflow.iter_staged(lambda c: c, 6, depth=1, waits=fast):
        time.sleep(0.01)
    assert fast.slot_s > 0.02

    def produce(c):
        time.sleep(0.01)
        return c

    assert list(chunkflow.iter_staged(produce, 6, waits=slow)) == \
        list(range(6))
    assert slow.input_s > 0.03


SHARDED = r"""
import json, sys
from pathlib import Path
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, sys.argv[1] + "/src")
import jax, jax.numpy as jnp, numpy as np
from jax.profiler import ProfileData
from bench import spans, trace
from repro.core import chunkflow, distributions as dists, queueing
from repro.launch.mesh import make_sweep_mesh

assert jax.device_count() == 4
cfg = queueing.SimConfig(n_servers=5, n_arrivals=2048)
scn = queueing.Scenario.paper_default(dists.exponential(), ks=(1, 2))
kw = dict(n_seeds=2, percentiles=(50.0, 99.0), chunk_size=512,
          mesh=make_sweep_mesh(4))
key = jax.random.PRNGKey(5)
plain = queueing.run(key, scn, jnp.asarray([0.2, 0.4]), cfg, **kw)
out, path = trace.capture(sys.argv[2], lambda: jax.block_until_ready(
    queueing.run(key, scn, jnp.asarray([0.2, 0.4]), cfg, **kw)))
same = all(np.array_equal(np.asarray(plain[k]), np.asarray(out[k]))
           for k in plain)
stats = chunkflow.last_stats()
print(json.dumps({"same": same, "spans": [
    [s.name, s.start_ns, s.end_ns, list(s.line), s.meta]
    for s in spans.host_spans(ProfileData.from_file(path))],
    "waits": [stats.input_wait_s, stats.slot_wait_s]}))
"""


def test_sharded_spans_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    t0 = time.perf_counter()
    res = subprocess.run([sys.executable, "-c", SHARDED, str(ROOT),
                          str(tmp_path / "trace")], env=env,
                         capture_output=True, text=True, timeout=600)
    wall = time.perf_counter() - t0
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got["same"]
    sp = [spans.Span(n, s, e, tuple(line), meta)
          for n, s, e, line, meta in got["spans"]]
    (run,) = [s for s in sp if s.name == "repro.run"]
    check_call(sp, run, 4, pipelined=True, sharded=True)
    assert run.meta["devices"] == 4 and run.meta["cells"] == 8
    assert all(0 <= w < wall for w in got["waits"])
