"""A run with its timed path broken underneath must come out as not
correct, once for each fault a cell can have: a chunk step that returns
its state unchanged, half of each chunk's arrivals left out (the mean
taken over the rest), and an answer altered where it is produced. The
runs skip the harness's look for a chip and are otherwise whole."""
import pytest

import bench_testlib


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testlib.tiny_copy(tmp_path_factory.mktemp("bench"))


def _unchanged(queueing, monkeypatch):
    def step(free, ssum, comp, cnt, hist, *args, **kw):
        return free, ssum, comp, cnt, hist

    monkeypatch.setattr(queueing, "_sweep_chunk_cells", step)


def _half_left_out(queueing, monkeypatch):
    body = queueing._sweep_chunk_cells

    def step(*args, **kw):
        args = list(args)
        args[9] = args[9] // 2      # n_valid: the chunk's real arrivals
        return body(*args, **kw)

    monkeypatch.setattr(queueing, "_sweep_chunk_cells", step)


def _mean_altered(queueing, monkeypatch):
    finalize = queueing._finalize_summary

    def altered(*args, **kw):
        out = finalize(*args, **kw)
        out["mean"] = out["mean"].at[(0,) * out["mean"].ndim].multiply(1.1)
        return out

    monkeypatch.setattr(queueing, "_finalize_summary", altered)


def _answer_altered(threshold, monkeypatch):
    bisect = threshold.threshold_bisect
    monkeypatch.setattr(threshold, "threshold_bisect",
                        lambda *a, **kw: bisect(*a, **kw) + 0.01)


STREAM = ["paper-20srv.tail-sweep", "paper-systems.tail-sweep"]
QUERY = "paper-20srv.threshold-query"


@pytest.mark.parametrize("workload", STREAM + [QUERY])
def test_sound_run_is_correct(root, workload):
    result, _ = bench_testlib.run_cell(root, workload)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _mean_altered])
@pytest.mark.parametrize("workload", STREAM + [QUERY])
def test_engine_fault_is_caught(root, workload, fault, monkeypatch):
    from repro.core import queueing

    fault(queueing, monkeypatch)
    result, _ = bench_testlib.run_cell(root, workload)
    assert result["correct"] is False, result["checks"]


def test_altered_threshold_is_caught(root, monkeypatch):
    from repro.core import threshold

    _answer_altered(threshold, monkeypatch)
    result, _ = bench_testlib.run_cell(root, QUERY)
    assert result["correct"] is False, result["checks"]
