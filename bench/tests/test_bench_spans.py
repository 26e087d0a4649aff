"""The program's spans in a trace (``bench/spans.py``) and the readers
that split the device's idle time by them.

Two traces recorded on one TPU v5e chip beside this file, both of
``paper-20srv.tail-sweep`` at 2048 arrivals in chunks of 512 with
``--trace 1``: ``tail_sweep_tiny.xplane.pb.gz`` of a program without
``repro.*`` spans, and ``tail_sweep_tiny_spans.xplane.pb.gz`` of one
with them. On the first, every reader reads what it read before the
spans existed and the span readers read None; on the second, the span
readers read shares of the window that sum to at most its idle share.
"""
import gzip
import importlib.util
import re
import unittest.mock
from pathlib import Path

import pytest

import bench_testlib
from bench import drive, harness, reference, spec, spans, trace

DATA = Path(__file__).parent / "data"
PLAIN = DATA / "tail_sweep_tiny.xplane.pb.gz"
WITH_SPANS = DATA / "tail_sweep_tiny_spans.xplane.pb.gz"
STREAM = ("idle_input_share.stream", "idle_finalize_share.stream")
QUERY = ("idle_input_share.query", "idle_outside_engine_share.query")


def placed(root: Path, recorded: Path) -> harness.Context:
    """The recorded trace where a run of the checkout at ``root`` writes
    its own, and the reader context of its window (three stream calls
    of the tiny cell)."""
    path = (root / spans.TRACE_DIR / "plugins" / "profile" / "recorded"
            / "tiny.xplane.pb")
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(gzip.decompress(recorded.read_bytes()))
    cell = spec.load_cell("paper-20srv.tail-sweep", root)
    grid = reference.grid_of(cell.config, spec.reference_laws(cell.config))
    calls = [drive.stream_call(grid, cell.traffic)] * 3
    return harness.Context(trace.reduce(str(path)), calls, "TPU v5 lite")


@pytest.fixture
def root(tmp_path):
    return bench_testlib.tiny_copy(tmp_path / "b", arrivals=2048, chunk=512)


def test_trace_without_spans_reads_as_before(root):
    ctx = placed(root, PLAIN)
    got = {n: harness.read_metric(root, n, ctx)
           for n in ("idle_share.stream", "chunk_body_ns_per_copy_step.stream",
                     "chunk_body_roofline.stream", "sampler_share.stream")}
    assert got == {"idle_share.stream": 0.2640658453536169,
                   "chunk_body_ns_per_copy_step.stream": 191.31925837198892,
                   "chunk_body_roofline.stream": 0.013947348746293794,
                   "sampler_share.stream": 0.0015222595635235798}
    assert all(harness.read_metric(root, n, ctx) is None
               for n in STREAM + QUERY)
    assert ctx.notes == ["chunk_body_roofline bound_by=bytes "
                         "floor_s=6.295536996336997e-05 body_s=0.451378761"]
    top = sorted(ctx.reduced.program_ns.items(), key=lambda x: -x[1])[:3]
    assert top == [("jit__sweep_chunk_cells", 451378761),
                   ("jit__sample_chunk_fused", 688550),
                   ("jit_cumsum", 343648)]
    win = spans.read(str(next((root / spans.TRACE_DIR).rglob("*.pb"))))
    assert not win.spans
    assert win.gaps() == ctx.reduced.gaps  # labels as ``trace`` gives them
    assert win.idle_share() == pytest.approx(
        1 - ctx.reduced.busy_s / ctx.reduced.window_s, abs=1e-12)


def test_trace_with_spans_splits_the_idle_share(root):
    ctx = placed(root, WITH_SPANS)
    idle = harness.read_metric(root, "idle_share.stream", ctx)
    got = {n: harness.read_metric(root, n, ctx) for n in STREAM + QUERY}
    assert all(v is not None and 0 <= v <= 1 for v in got.values()), got
    assert sum(got[n] for n in STREAM) <= idle + 1e-6
    assert sum(got[n] for n in QUERY) <= idle + 1e-6
    split = [n for n in ctx.notes if n.startswith("idle_split")]
    assert len(split) == 2  # one from each cell kind's last reader
    assert any(n.startswith("idle_gap") and "/repro." in n
               for n in ctx.notes), ctx.notes
    win = spans.window_of(ctx, root)
    engine = win.covered((spans.ENGINE,))
    for (label, ns), (s, e) in zip(win.gaps(), sorted(
            (iv for ivs in win.idle_ns for iv in ivs),
            key=lambda g: g[0] - g[1])):
        if any(a <= (s + e) // 2 <= b for a, b in engine):
            assert "/repro." in label, label


def test_window_of_refuses_another_window(root):
    ctx = placed(root, WITH_SPANS)
    ctx.reduced.window_ns = (0, 1)
    with pytest.raises(ValueError, match="window"):
        spans.window_of(ctx, root)


def _window(idle, spans_):
    return spans.Window(window_ns=(0, 100), spans=spans_, idle_ns=idle,
                        bench_spans=[("bench.window", 0, 100),
                                     ("bench.query", 0, 90)],
                        host_events=[("np.asarray", 10, 40)])


def test_idle_inside_and_outside_spans():
    run = spans.Span("repro.run", 5, 90, (1, 0), {"call": 0})
    inp = spans.Span("repro.chunk.input", 10, 30, (1, 0), {"call": 0})
    stage = spans.Span("repro.stage", 12, 28, (1, 1), {"call": 0})
    fin = spans.Span("repro.finalize", 70, 90, (1, 0), {"call": 0})
    # two devices: idle 10-40 and 75-95 on the first, 0-5 on the second
    win = _window([[(10, 40), (75, 95)], [(0, 5)]], [run, inp, stage, fin])
    assert win.idle_share() == pytest.approx((30 + 20 + 5) / 2 / 100)
    assert win.idle_share_in(("repro.chunk.input",)) == pytest.approx(
        20 / 2 / 100)
    assert win.idle_share_in(("repro.finalize",)) == pytest.approx(
        15 / 2 / 100)
    # outside 5-90: 90-95 on the first device, 0-5 on the second
    assert win.idle_share_outside(("repro.run",)) == pytest.approx(
        (5 + 5) / 2 / 100)
    assert win.label(10, 40) == \
        "bench.query/repro.chunk.input+repro.stage:np.asarray"
    assert win.label(91, 95) == "between"
    assert win.gaps(2) == [
        ("bench.query/repro.chunk.input+repro.stage:np.asarray", 30),
        ("bench.query/repro.finalize", 20)]


def _reader_programs() -> set[str]:
    names = set()
    for path in (spec.BENCH / "metrics").glob("*.py"):
        mod_spec = importlib.util.spec_from_file_location(
            "programs_of_" + path.stem.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        names.update(getattr(mod, "PROGRAMS", ()))
    return names


def test_program_names_the_readers_match():
    """Each program a reader times, lowered as the engine calls it, is
    named by some reader's ``PROGRAMS``: a rename fails here, not as a
    silent null on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributions as dists, queueing
    from repro.distributed import sweep_shard
    from repro.launch.mesh import make_sweep_mesh

    seen = {}

    def spy(name, fn):
        def first_call(*args, **kw):
            seen.setdefault(name, (fn, args, kw))
            return fn(*args, **kw)
        return first_call

    body_fn = sweep_shard._body_fn
    scn = queueing.Scenario.paper_default(dists.exponential(), ks=(1, 2))
    cfg = queueing.SimConfig(n_servers=5, n_arrivals=1024)
    key, rhos = jax.random.PRNGKey(0), jnp.asarray([0.2])
    with unittest.mock.patch.multiple(
            queueing,
            _sweep_chunk_cells=spy("_sweep_chunk_cells",
                                   queueing._sweep_chunk_cells),
            _sample_chunk_fused=spy("_sample_chunk_fused",
                                    queueing._sample_chunk_fused),
            _sample_sweep_arrivals=spy("_sample_sweep_arrivals",
                                       queueing._sample_sweep_arrivals)), \
            unittest.mock.patch.object(
                sweep_shard, "_body_fn",
                lambda *a, **kw: spy("chunk_body", body_fn(*a, **kw))):
        # unchunked first: its eager sampler calls the arrival sampler
        # with concrete arrays before the fused one traces it
        queueing.run(key, scn, rhos, cfg, percentiles=())
        queueing.run(key, scn, rhos, cfg, chunk_size=512)
        queueing.run(key, scn, rhos, cfg, chunk_size=512,
                     mesh=make_sweep_mesh(1))
    assert set(seen) == {"_sweep_chunk_cells", "_sample_chunk_fused",
                         "_sample_sweep_arrivals", "chunk_body"}
    programs = _reader_programs()
    for name, (fn, args, kw) in seen.items():
        module = re.search(r"module @(\S+)",
                           fn.lower(*args, **kw).as_text()).group(1)
        assert any(p in module for p in programs), (name, module)
