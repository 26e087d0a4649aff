"""The roofline's work is counted from the cell, not from the code: the
kernel and the scan bodies of one cell are charged the same operations
and bytes, and the counts are the documented formulas."""
import bench_testlib


def _records(root, mode, monkeypatch):
    import jax

    from bench import drive, spec
    from repro.core import queueing

    monkeypatch.setattr(queueing.cell_ops, "resolve_kernel_mode",
                        lambda kernel, n_bins=None: mode)
    queries = drive.make_queries(spec.load_cell("paper-20srv.tail-sweep",
                                                root))
    return queries(jax.random.PRNGKey(1))


def test_kernel_and_scan_are_charged_alike(tmp_path, monkeypatch):
    import numpy as np

    root = bench_testlib.tiny_copy(tmp_path)
    scan = _records(root, "off", monkeypatch)
    kern = _records(root, "interpret", monkeypatch)
    (a,), (b,) = scan["calls"], kern["calls"]
    assert (a.ops, a.bytes, a.copy_steps) == (b.ops, b.bytes, b.copy_steps)
    assert np.array_equal(np.asarray(scan["out"]["mean"]),
                          np.asarray(kern["out"]["mean"]))


def test_counts_follow_the_formulas():
    from bench import work

    call = work.Call(ks=(1, 2) * 3, seed_rows=3, svc_rows=6, k_max=2,
                     n_arrivals=1000, chunk=250, warmup=100, n_servers=20,
                     n_bins=2048)
    assert call.copy_steps == 1000 * 9
    assert call.ops == 3 * 9000 + 3 * 1000 * 6 + 2 * 900 * 6
    assert call.bytes == 4 * 1000 * (3 * 3 + 6 * 2) + 2 * 4 * 6 * 2070 * 4
    floor, bound = work.floor_seconds([call], "TPU v5 lite")
    assert bound == "bytes" and floor == call.bytes / 819e9


def test_timed_and_degraded_counts():
    """Timed cells add 3 operations a copy-step (dispatch time, gate),
    degraded cells 1 (the straggler select); a degraded grid reads one
    degradation uniform per copy per law and seed row. A copy-step is a
    copy of the budget, whether or not it fires."""
    from bench import work

    base = dict(seed_rows=2, svc_rows=2, k_max=2, n_arrivals=1000,
                chunk=500, warmup=100, n_servers=10, n_bins=0)
    plain = work.Call(ks=(1, 2, 2), **base)
    call = work.Call(ks=(1, 2, 2), timed=(False, True, True),
                     degraded=(True, True, False), **base)
    assert call.copy_steps == plain.copy_steps == 5000
    assert call.ops == plain.ops + 1000 * (3 * 4 + 3)
    assert call.bytes == plain.bytes + 4 * 1000 * 2 * 2
