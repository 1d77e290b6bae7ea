"""The trace reduction, on plain lists and on a small trace recorded by a
traced chip run of `warm10p4.encode-cycle` (PR 24, TPU v5 lite x1, one timed
`ec.encode` of the 1 GiB volume): `data/encode_op/.../runsc.xplane.pb`."""

import os

import pytest

from harness import reducers, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "encode_op")


def test_union_of_overlapping_and_nested_intervals():
    assert trace_reduce.union_seconds([]) == 0.0
    assert trace_reduce.union_seconds([(0, 1), (0.5, 2), (3, 4), (3.2, 3.4)]) == pytest.approx(3.0)


def test_gaps_cover_what_the_intervals_leave_of_the_window():
    got = trace_reduce.gaps([(1, 2), (1.5, 3), (5, 6)], (0, 10))
    assert got == [(0, 1), (3, 2), (6, 4)]
    assert trace_reduce.gaps([], (2, 5)) == [(2, 3)]


def test_summary_is_relative_to_the_window_and_clips_to_it():
    s = trace_reduce.summarize_events(
        modules=[("jit_f(1)", 10.0, 1.0), ("jit_f(2)", 12.0, 2.0)],
        ops=[("a", 10.0, 0.5), ("b", 10.5, 0.5), ("a", 12.0, 2.0), ("late", 99.0, 1.0)],
        window=(9.0, 15.0),
    )
    assert s["window_s"] == 6.0 and s["busy_s"] == pytest.approx(3.0)
    assert s["modules"] == [["jit_f", 1.0, 1.0], ["jit_f", 3.0, 2.0]]
    assert s["top_ops"][0] == ["a", 2.5]
    assert [round(d, 6) for _, d in s["gaps"]] == [1.0, 1.0, 1.0]


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce_trace(DATA)


def test_recorded_trace_busy_union_and_window(recorded):
    assert recorded["window_found"]
    (chip,) = recorded["chips"]
    assert chip["window_s"] == pytest.approx(3.26755461, abs=1e-6)
    assert chip["busy_s"] == pytest.approx(0.087573567, abs=1e-6)
    assert sum(d for _, d in chip["gaps"]) + chip["busy_s"] == pytest.approx(chip["window_s"], abs=1e-6)


def test_recorded_trace_program_time_sum_and_gap_list(recorded):
    (chip,) = recorded["chips"]
    encodes = [(s, d) for n, s, d in chip["modules"] if n == "jit__gf_apply_impl"]
    assert len(encodes) == 17  # 16 batches of (10, 6553600) and the (10, 5242880) tail
    assert sum(d for _, d in encodes) == pytest.approx(0.087404919, abs=1e-6)
    # the longest idle stretch is the one before the first dispatch: the shell child
    first = max(chip["gaps"], key=lambda g: g[1])
    assert first[0] == 0.0 and first[1] == pytest.approx(1.327181226, abs=1e-6)


def test_readers_on_the_recorded_trace(recorded):
    facts = {"traces": [recorded], "traced": [{"width": 105 << 20}], "device_kind": "TPU v5 lite",
             "setup": {}, "samples": {}}
    assert reducers.idle_pct(facts) == pytest.approx(100 * (1 - 0.087573567 / 3.26755461), abs=1e-4)
    roof = reducers.program_roofline_pct(facts, "^jit__gf_apply_impl$", 10, 4)
    assert roof == pytest.approx(100 * (14 * (105 << 20) / 819e9) / 0.087404919, abs=1e-4)
    assert 0 < roof < 100
    assert 50 < reducers.program_gap_median_ms(facts, "^jit__gf_apply_impl$") < 150
    assert reducers.program_gap_median_ms(facts, "^no_such_program$") is None
    assert reducers.class_p50_ms(facts, "degraded") is None
