"""The arithmetic behind every bulk end-to-end metric, where the driver's
tier-1 command collects it: the cases of `benchmark/tests/test_rate.py` against
`benchmark/drivers/common.py` `bulk_rate` (all bytes over all walls, the median
wall beside it), imported read-only from the benchmark's own file, so that the
two collections can never disagree. `encode_MBps` and `rebuild_MBps` are made
there and nowhere else."""

import importlib.util
import os
import sys

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")


def _benchmarks_cases():
    """`benchmark/tests/test_rate.py` under a name of its own: it imports
    `drivers` and `harness` from the benchmark's directory, which is on the
    path for as long as that takes."""
    sys.path.insert(0, BENCH_DIR)
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_tests_test_rate", os.path.join(BENCH_DIR, "tests", "test_rate.py")
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH_DIR)
    return module


_cases = _benchmarks_cases()
test_bulk_rate = _cases.test_bulk_rate
test_a_driver_makes_its_metrics_through_the_one_helper = (
    _cases.test_a_driver_makes_its_metrics_through_the_one_helper
)
