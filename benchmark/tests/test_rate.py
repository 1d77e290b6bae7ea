"""A bulk cell's end-to-end metrics (`drivers/common.py` `bulk_rate`): the rate
is all bytes over all walls, so a stalled command counts with all it took; the
median wall stands beside it under a name of its own.

The recorded lists are `timed_seconds` of chip runs whose result lines PR 32's
builder kept (TPU v5 lite x1; one `ec.encode` moves 1100.5 MB, one `ec.rebuild`
of four shards 440.4 MB): each holds one command that stalled inside a file call
of the machine's disk, and read 687.05, 810.76 and 317.74 MB/s."""

import os
import re
import statistics
import types

import pytest

from drivers import common
from harness.manifest import BENCH_DIR

ENCODE_MB, REBUILD_MB = 1100.5, 440.4
RECORDED = {
    "u_encode_p1": ([1.3653, 1.3666, 1.3722, 1.366, 2.5389], ENCODE_MB, 687.05, 1.3666, 805.3),
    "u_encode_vb1": ([1.1887, 1.1431, 1.1348, 1.1807, 2.301, 1.196], ENCODE_MB, 810.76, 1.18470, 928.9),
    "f_rebuild_p2": ([1.1275, 1.1319, 1.1008, 1.0944, 1.1025, 1.0942, 1.1029, 1.0903, 1.091, 1.092, 4.219],
                     REBUILD_MB, 317.74, 1.1008, 400.1),
}
# thirteen unstalled walls with the jitter the chip shows (a command is 1.09-1.13 s)
QUIET = [1.1008, 1.0951, 1.1041, 1.0913, 1.1293, 1.1126, 1.0988, 1.0946, 1.1012, 1.1215, 1.1102, 1.0977, 1.1064]


def _rate(walls, mb=REBUILD_MB):
    run = types.SimpleNamespace(timed=list(walls), metrics={}, facts={})
    common.bulk_rate(run, "rebuild", int(mb * 1e6))
    return run


def _recorded(name):
    walls, mb, rate, p50, median_rate = RECORDED[name]
    run = _rate(walls, mb)
    assert run.metrics["rebuild_MBps"] == pytest.approx(rate, rel=0.001)
    assert run.metrics["rebuild_cmd_p50_s"] == pytest.approx(p50, abs=1e-4)
    facts = run.facts["timed"]
    assert facts["median_rate_MBps"] == pytest.approx(median_rate, abs=0.5)
    assert facts["stalled_ops"] == 1 and facts["max_s"] == max(walls) and facts["ops"] == len(walls)


def _one_stall(n):
    """One stalled command among n: the rate loses all the stall took, to the
    second; the median wall is one of the unstalled commands' (odd n) or lies
    between two of them (even n), within 2% of the median without the stall."""
    quiet = QUIET[:n - 1]
    walls = quiet[:n // 2] + [quiet[0] + 7.5] + quiet[n // 2:]
    run, without = _rate(walls), _rate(quiet)
    assert run.metrics["rebuild_MBps"] == pytest.approx(n * REBUILD_MB / (sum(quiet) + quiet[0] + 7.5))
    assert run.metrics["rebuild_MBps"] < 0.9 * without.metrics["rebuild_MBps"]
    p50 = run.metrics["rebuild_cmd_p50_s"]
    assert min(quiet) <= p50 <= max(quiet)
    assert p50 == pytest.approx(without.metrics["rebuild_cmd_p50_s"], rel=0.02)
    if n % 2:
        assert p50 in quiet
    assert run.facts["timed"]["stalled_ops"] == 1 and without.facts["timed"]["stalled_ops"] == 0


def _a_slow_command_in_every_k_shows_in_the_rate(k):
    """A program that made every k-th command twice as slow (a deferred flush, a
    periodic fsync) loses 1/(k+1) of its rate, whatever its median does."""
    walls = [2.2 if i % k == k - 1 else 1.1 for i in range(12)]
    run = _rate(walls)
    assert run.metrics["rebuild_MBps"] == pytest.approx(REBUILD_MB / 1.1 * k / (k + 1))
    assert run.facts["timed"]["stalled_ops"] == (12 // k if k > 2 else 0)  # at k = 2 the median is 1.65: none is over 1.5 of it


def _all_walls_count(n):
    """The rate is n commands' bytes over the sum of n walls; the facts agree with the walls."""
    walls = QUIET[:n]
    run = _rate(walls)
    assert run.metrics["rebuild_MBps"] == pytest.approx(n * REBUILD_MB / sum(walls))
    assert run.metrics["rebuild_cmd_p50_s"] == statistics.median(walls)
    facts = run.facts["timed"]
    assert facts == {"ops": n, "median_s": statistics.median(walls), "max_s": max(walls), "stalled_ops": 0,
                     "median_rate_MBps": int(REBUILD_MB * 1e6) / 1e6 / statistics.median(walls)}
    # no stall: the two readings lie within 1.5% of each other
    assert facts["median_rate_MBps"] == pytest.approx(run.metrics["rebuild_MBps"], rel=0.015)


def _empty():
    run = _rate([])
    assert run.metrics == {}
    assert run.facts["timed"] == {"ops": 0, "median_s": None, "max_s": None, "stalled_ops": 0,
                                  "median_rate_MBps": None}


def _stalled_counts_over_one_and_a_half_medians_only():
    walls = [1.0, 1.0, 1.0, 1.0, 1.0, 1.5, 1.5000001, 1.49, 0.2]
    assert statistics.median(walls) == 1.0
    assert common.timed_facts(walls, 1)["stalled_ops"] == 1  # 1.5 is not over 1.5 x 1.0; a fast one never counts
    assert common.timed_facts([1.0, 1.0, 4.0, 5.0, 6.0], 1)["stalled_ops"] == 0  # median 4.0: nothing over 6.0
    assert common.timed_facts([2.0], 1)["stalled_ops"] == 0


def _a_slow_run_moves_both():
    """Every command of a run 100 ms longer (PERF.md section 6, PR 31: the run-wide
    slow mode) moves the rate and the median alike and is no `stalled_ops`."""
    slow, quiet = _rate([w + 0.1 for w in QUIET]), _rate(QUIET)
    assert slow.facts["timed"]["stalled_ops"] == 0
    assert slow.metrics["rebuild_MBps"] < 0.93 * quiet.metrics["rebuild_MBps"]
    assert slow.metrics["rebuild_cmd_p50_s"] == pytest.approx(quiet.metrics["rebuild_cmd_p50_s"] + 0.1)


def _metric_names_follow_the_operation():
    run = types.SimpleNamespace(timed=[1.0, 3.0], metrics={}, facts={})
    common.bulk_rate(run, "encode", 2_000_000)
    assert run.metrics == {"encode_MBps": 1.0, "encode_cmd_p50_s": 2.0}


CASES = (
    [(f"recorded-{name}", _recorded, name) for name in RECORDED]
    + [(f"one-stall-of-{n}", _one_stall, n) for n in (3, 4, 5, 6, 7, 13)]
    + [(f"every-{k}-slow", _a_slow_command_in_every_k_shows_in_the_rate, k) for k in (2, 3, 4, 6)]
    + [(f"all-walls-count-{n}", _all_walls_count, n) for n in (1, 2, 3, 4, 5, 6, 7, 13)]
    + [("empty", _empty, None), ("stalled-ops", _stalled_counts_over_one_and_a_half_medians_only, None),
       ("slow-run", _a_slow_run_moves_both, None), ("names", _metric_names_follow_the_operation, None)]
)


@pytest.mark.parametrize("case,arg", [(c, a) for _, c, a in CASES], ids=[i for i, _, _ in CASES])
def test_bulk_rate(case, arg):
    case() if arg is None else case(arg)


@pytest.mark.parametrize("driver", ["encode_cycle", "rebuild_cycle", "rebuild_serverlost"])
def test_a_driver_makes_its_metrics_through_the_one_helper(driver):
    with open(os.path.join(BENCH_DIR, "drivers", driver + ".py")) as f:
        src = f.read()
    assert len(re.findall(r"common\.bulk_rate\(", src)) == 1
    assert not re.search(r"sum\(\s*run\.timed\s*\)", src)
    assert not re.search(r"run\.metrics\[", src), "a driver sets no end-to-end metric itself"
