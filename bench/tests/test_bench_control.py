"""The control of the correctness check, at a size a test run holds:
the plain reference computed in bfloat16, put in the program's place,
must fail each cell's check with the limits the cell's traffic states."""
import pytest

import bench_testlib


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testlib.tiny_copy(tmp_path_factory.mktemp("bench"),
                                   arrivals=2048, chunk=1024)


@pytest.mark.parametrize("workload", ["paper-20srv.tail-sweep",
                                      "paper-systems.tail-sweep",
                                      "paper-20srv.threshold-query"])
def test_bfloat16_control_is_caught(root, workload):
    import numpy as np

    from bench import control, spec

    cell = spec.load_cell(workload, root)
    numbers = (control.threshold_numbers if cell.entry == "threshold_bisect"
               else control.stream_numbers)(cell, seed=5, n_queries=2)
    limits = cell.traffic["check"]["limits"]
    assert any(not (np.isfinite(v) and v <= limits[n])
               for n, v in numbers.items() if n in limits), numbers
