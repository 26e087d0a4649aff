"""The reduction from a profiler trace to the per-layer readers' inputs,
on a small trace recorded on one TPU v5e chip beside this file
(``data/tail_sweep_tiny.xplane.pb.gz``: ``paper-20srv.tail-sweep`` at
2048 arrivals in chunks of 512 with ``--trace 1``; its window held
three queries)."""
import gzip
from pathlib import Path

import pytest

import bench_testlib
from bench import trace

RECORDED = Path(__file__).parent / "data" / "tail_sweep_tiny.xplane.pb.gz"


def test_union_and_clip():
    ivs = trace._union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 21)])
    assert ivs == [(0, 3), (5, 12), (20, 21)]
    assert trace._clip(ivs, 2, 20) == [(2, 3), (5, 12)]


def test_gap_label_names_span_and_host_event():
    spans = [("bench.window", 0, 100), ("bench.query", 10, 50)]
    events = [("ExecuteProgram", 20, 25), ("ParseArgs", 21, 40)]
    assert trace._label(20, 40, spans, events) == "bench.query:ParseArgs"
    assert trace._label(60, 70, spans, events) == "between"


def test_recorded_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    reduced = trace.reduce(str(path))
    assert len(reduced.busy_ns) == 1
    assert 0 < reduced.busy_s <= reduced.window_s
    body = reduced.program_s(("_sweep_chunk_cells",))
    assert body is not None and 0 < body <= reduced.busy_s * 1.001
    assert reduced.program_s(("no such program",)) is None
    assert reduced.gaps and all(ns > 0 for _, ns in reduced.gaps)
    assert [ns for _, ns in reduced.gaps] == sorted(
        (ns for _, ns in reduced.gaps), reverse=True)


def test_trace_without_window_is_refused(tmp_path):
    bad = tmp_path / "empty.xplane.pb"
    bad.write_bytes(b"")
    with pytest.raises(Exception):
        trace.reduce(str(bad))


def test_readers_on_recorded_trace(tmp_path):
    """Each per-layer reader of a stream cell reads the recorded trace
    and stays in range; the idle readers that split by the program's
    spans find none in it."""
    import json

    from bench import drive, harness, reference, spec

    root = bench_testlib.tiny_copy(tmp_path / "b", arrivals=2048, chunk=512)
    # where a run of that checkout writes its trace: the span readers look
    path = (root / harness.TRACE_DIR / "plugins" / "profile" / "recorded"
            / "tiny.xplane.pb")
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    cell = spec.load_cell("paper-20srv.tail-sweep", root)
    grid = reference.grid_of(cell.config, spec.reference_laws(cell.config))
    calls = [drive.stream_call(grid, cell.traffic)] * 3
    ctx = harness.Context(trace.reduce(str(path)), calls, "TPU v5 lite")
    got = {m["name"]: harness.read_metric(root, m["name"], ctx)
           for m in cell.per_layer}
    assert set(got) == {"idle_share.stream", "sampler_share.stream",
                        "chunk_body_ns_per_copy_step.stream",
                        "chunk_body_roofline.stream",
                        "idle_input_share.stream",
                        "idle_finalize_share.stream"}, json.dumps(got)
    assert got["idle_input_share.stream"] is None
    assert got["idle_finalize_share.stream"] is None
    assert 0 < got["idle_share.stream"] < 1
    assert 0 < got["sampler_share.stream"] < 1
    assert 150 < got["chunk_body_ns_per_copy_step.stream"] < 250
    assert 0 < got["chunk_body_roofline.stream"] < 100
