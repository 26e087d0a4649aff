"""``copy_fill.stream``: the copy slots the chunk body computed that hold
a copy within its cell's budget, read from ``repro.run``'s ``lanes`` and
``k_max``. It reads None on the two traces recorded beside this file
(one with no program spans, one with spans but no ``k_max``) and the
expected ratio on a synthetic window."""
import gzip
from pathlib import Path

import pytest

import bench_testlib
from bench import drive, harness, reference, spec, spans, trace, work

DATA = Path(__file__).parent / "data"
PLAIN = DATA / "tail_sweep_tiny.xplane.pb.gz"
WITH_SPANS = DATA / "tail_sweep_tiny_spans.xplane.pb.gz"


@pytest.fixture
def root(tmp_path):
    return bench_testlib.tiny_copy(tmp_path / "b", arrivals=2048, chunk=512)


def _placed(root: Path, recorded: Path) -> harness.Context:
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


def _window(runs):
    return spans.Window(window_ns=(0, 100), spans=runs, idle_ns=[[(0, 5)]],
                        bench_spans=[("bench.window", 0, 100),
                                     ("bench.query", 0, 90)],
                        host_events=[])


def _call(ks):
    return work.Call(ks=ks, seed_rows=2, svc_rows=2, k_max=max(ks),
                     n_arrivals=1024, chunk=512, warmup=102, n_servers=10,
                     n_bins=0)


@pytest.mark.parametrize("recorded", [PLAIN, WITH_SPANS],
                         ids=["no_spans", "spans_without_k_max"])
def test_copy_fill_reads_none_without_the_copy_budget(root, recorded):
    ctx = _placed(root, recorded)
    assert harness.read_metric(root, "copy_fill.stream", ctx) is None
    assert not any(n.startswith("copy_fill") for n in ctx.notes)


def test_copy_fill_reads_none_without_engine_calls(root, monkeypatch):
    monkeypatch.setattr(spans, "window_of", lambda ctx, root: _window([]))
    ctx = harness.Context(None, [], "TPU v5 lite")
    assert harness.read_metric(root, "copy_fill.stream", ctx) is None


def test_copy_fill_on_a_synthetic_window(root, monkeypatch):
    """Two calls of a grid of ks (1, 2, 3) x 2 cells on 128 lanes at
    k_max 3: 12 copies within budget a call over 384 slots."""
    meta = {"call": 0, "cells": 6, "lanes": 128, "k_max": 3, "timed": 4,
            "degraded": 2}
    runs = [spans.Span("repro.run", a, a + 40, (1, 0), dict(meta, call=i))
            for i, a in enumerate((5, 50))]
    monkeypatch.setattr(spans, "window_of", lambda ctx, root: _window(runs))
    call = _call((1, 2, 3) * 2)
    ctx = harness.Context(None, [call, call], "TPU v5 lite")
    got = harness.read_metric(root, "copy_fill.stream", ctx)
    assert got == pytest.approx((2 * 12) / (2 * 128 * 3))
    assert ctx.notes == ["copy_fill within=24 slots=768 cells=12 timed=8 "
                         "degraded=4"]


@pytest.mark.parametrize("ks,lanes,expected", [
    ((1, 2) * 192, 384, 0.75),
    ((1, 2, 3) * 192, 640, 1152 / 1920),
], ids=["bare_k2_full_lanes", "k3_padded_lanes"])
def test_copy_fill_counts_padding_and_masked_copies(root, monkeypatch, ks,
                                                    lanes, expected):
    """The layouts of a bare k<=2 grid on full lanes and of a k<=3 grid
    padded to 640 lanes: the masked copies and the pad lanes are what
    the ratio leaves out."""
    meta = {"call": 0, "cells": len(ks), "lanes": lanes, "k_max": max(ks),
            "timed": 0, "degraded": 0}
    runs = [spans.Span("repro.run", 5, 45, (1, 0), meta)]
    monkeypatch.setattr(spans, "window_of", lambda ctx, root: _window(runs))
    ctx = harness.Context(None, [_call(ks)], "TPU v5 lite")
    got = harness.read_metric(root, "copy_fill.stream", ctx)
    assert got == pytest.approx(expected)
