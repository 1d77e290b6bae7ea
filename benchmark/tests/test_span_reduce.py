"""The span reduction, on hand-made lists and on a small recorded trace that
holds host spans: `data/rehearsal_spans/.../vm.xplane.pb`, the first traced
stretch of `run.py --workload warm10p4.encode-cycle --seed 3000000001 --seconds 3
--trace 1 --rehearse --keep-trace` (PR 25; CPU, the tiny rehearsal volume: one
timed `ec.encode` of one batch through the shell, the chip-owning server's
mirror and the profiler, so it has no device plane and its times are no
device's)."""

import copy
import json
import os

import pytest

from harness import manifest, span_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "rehearsal_spans")

# one thread of a server: an RPC whose run has two batches; a second thread
# with an RPC that overlaps nothing of the pipeline
RPC = [
    ("rpc.server", 1.0, 9.0, {"method": "VolumeEcShardsGenerate"}),
    ("encode.run", 1.5, 8.0, {}),
    ("encode.stage", 2.0, 2.0, {}),
    ("encode.read", 2.0, 1.0, {}),
    ("encode.write", 3.0, 0.5, {}),
    ("encode.dispatch", 4.0, 0.5, {}),
    ("encode.drain", 5.0, 3.0, {}),
    ("encode.sync", 5.0, 2.0, {}),
    ("encode.write", 7.0, 0.5, {}),
]
OTHER = [("rpc.server", 0.2, 0.3, {"method": "LeaseAdminToken"})]


def test_self_time_is_duration_less_what_direct_children_cover():
    rows = {(n, s): (depth, self_s) for n, s, _, depth, self_s in span_reduce.nest(RPC)}
    assert rows[("rpc.server", 1.0)] == (0, pytest.approx(1.0))  # 9 - the run's 8
    assert rows[("encode.run", 1.5)] == (1, pytest.approx(8.0 - 2.0 - 0.5 - 3.0))
    assert rows[("encode.stage", 2.0)] == (2, pytest.approx(0.5))  # 2 - read 1 - write 0.5
    assert rows[("encode.drain", 5.0)] == (2, pytest.approx(0.5))
    assert rows[("encode.sync", 5.0)] == (3, pytest.approx(2.0))
    # self seconds of one thread sum to its top-level spans' durations
    assert sum(r[4] for r in span_reduce.nest(RPC)) == pytest.approx(9.0)


def test_by_name_counts_unions_and_sums_self_over_threads():
    names = span_reduce.by_name([RPC, OTHER])
    assert names["rpc.server"] == {"count": 2, "union_s": pytest.approx(9.3), "self_s": pytest.approx(1.3)}
    assert names["encode.write"]["count"] == 2 and names["encode.write"]["self_s"] == pytest.approx(1.0)
    assert span_reduce.by_name([]) == {}


def test_a_gap_goes_to_the_deepest_span_open_at_each_instant():
    got = span_reduce.attribute_gap((0.0, 2.5), [RPC, OTHER])
    # 0.2 before any span, 0.3 inside the other thread's RPC, 0.5 more before
    # this one's, 0.5 of rpc.server alone, 0.5 of encode.run, 0.5 of encode.read
    assert got["unattributed"] == pytest.approx(0.7)
    assert [n for n, _ in got["spans"]] == ["encode.read", "encode.run", "rpc.server"]  # deepest first
    assert dict(got["spans"]) == {"encode.read": pytest.approx(0.5), "encode.run": pytest.approx(0.5),
                                  "rpc.server": pytest.approx(0.8)}
    assert sum(s for _, s in got["spans"]) + got["unattributed"] == pytest.approx(2.5)
    inside = span_reduce.attribute_gap((5.5, 1.0), [RPC, OTHER])
    assert inside == {"spans": [["encode.sync", pytest.approx(1.0)]], "unattributed": 0.0}
    assert span_reduce.attribute_gap((20.0, 1.0), [RPC]) == {"spans": [], "unattributed": pytest.approx(1.0)}


def test_gap_labels_keep_the_position_and_name_the_largest_holder():
    runs = [(4.5, 4.6), (7.5, 7.6)]
    assert span_reduce.gap_position((0.0, 4.5), runs, 10.0) == span_reduce.GAP_POSITIONS[0]
    assert span_reduce.gap_position((4.6, 2.9), runs, 10.0) == span_reduce.GAP_POSITIONS[1]
    assert span_reduce.gap_position((7.6, 2.4), runs, 10.0) == span_reduce.GAP_POSITIONS[2]
    between = span_reduce.attribute_gap((4.6, 2.9), [RPC])
    assert span_reduce.gap_label("between device ops", between) == "between device ops: encode.sync"
    assert span_reduce.gap_label("x", {"spans": [], "unattributed": 1.0}) == "x: unattributed"


def _facts():
    chip = {"chip": 0, "window_s": 10.0, "busy_s": 0.2,
            "modules": [["jit__gf_apply_impl", 4.5, 0.1], ["jit__gf_apply_impl", 7.5, 0.1]],
            "top_ops": [], "gaps": [[0.0, 4.5], [4.6, 2.9], [7.6, 2.4]]}
    threads = [[list(e) for e in RPC], [list(e) for e in OTHER]]
    return {"traces": [{"chips": [chip]}], "spans": [{"window_found": True, "threads": threads}]}


def test_readers_on_hand_made_facts_and_none_where_nothing_was_recorded():
    facts = _facts()
    assert span_reduce.span_self_ms(facts, "encode.write") == pytest.approx(1000.0)
    assert span_reduce.span_self_ms(facts, "rebuild.read") is None
    assert span_reduce.first_span_start_ms(facts, "rpc.server") == pytest.approx(200.0)
    # idle 9.8 s; uncovered: 0.2 + 0.5 before the RPCs, 0.0 after (the RPC ends at the window's end)
    assert span_reduce.idle_attributed_pct(facts) == pytest.approx(100 * (9.8 - 0.7) / 9.8)
    # the parent commit's trace: a device summary and no spans
    bare = {"traces": facts["traces"], "spans": [{"window_found": True, "threads": []}]}
    assert all(reader(bare, **args) is None for reader, args in (
        (span_reduce.span_self_ms, {"span": "encode.read"}),
        (span_reduce.first_span_start_ms, {"span": "rpc.server"}),
        (span_reduce.idle_attributed_pct, {})))
    assert span_reduce.span_self_ms({"traces": [], "spans": []}, "encode.read") is None


def test_cross_check_compares_stages_with_the_device_phase_and_finds_the_enclosing_rpc():
    facts = _facts()
    got = span_reduce.cross_check(facts["traces"][0], facts["spans"][0])
    assert got["device_runs"] == 2 and got["pipeline_phase_s"] == pytest.approx(3.1)
    assert got["stages_self_s"] == pytest.approx(1.0 + 1.0 + 0.5 + 2.0)  # read, 2 writes, dispatch, sync
    # of the phase 4.5-7.6: sync 5-7 and 0.5 of the second write; the dispatch ended at 4.5
    assert got["stages_in_phase_s"] == pytest.approx(2.5) and got["stages_in_phase_over_phase"] == pytest.approx(2.5 / 3.1)
    assert got["rpc_server"] == {"method": "VolumeEcShardsGenerate", "runs_inside": 2, "encloses_all": True,
                                 "lead_s": pytest.approx(3.5), "tail_s": pytest.approx(2.4)}
    late = copy.deepcopy(facts)
    late["traces"][0]["chips"][0]["modules"].append(["jit__gf_apply_impl", 10.5, 0.1])
    assert not span_reduce.cross_check(late["traces"][0], late["spans"][0])["rpc_server"]["encloses_all"]
    assert span_reduce.cross_check({"chips": []}, facts["spans"][0]) == {"device_runs": 0}


def test_the_profilers_other_form_of_an_annotated_name():
    assert span_reduce.split_name("encode.read") == ("encode.read", {})
    assert span_reduce.split_name("rpc.server#method=VolumeEcShardsGenerate#") == (
        "rpc.server", {"method": "VolumeEcShardsGenerate"})
    assert span_reduce.split_name("encode.read#bytes=65536000,batch=3#") == (
        "encode.read", {"bytes": "65536000", "batch": "3"})


@pytest.fixture(scope="module")
def recorded():
    return span_reduce.reduce_spans(DATA)


def test_recorded_trace_holds_the_programs_spans_with_their_attributes(recorded):
    assert recorded["window_found"]
    names = span_reduce.by_name(recorded["threads"])
    # the rehearsal's volume is one batch: every stage once, a write and a CRC per shard
    assert {n: names[n]["count"] for n in names if n.startswith("encode.")} == {
        "encode.run": 1, "encode.stage": 1, "encode.read": 1, "encode.dispatch": 1, "encode.drain": 1,
        "encode.sync": 1, "encode.write": 14, "encode.crc": 14}
    assert names["encode.read"]["self_s"] == pytest.approx(0.615376901, abs=1e-6)
    events = [e for t in recorded["threads"] for e in t]
    generate = [e for e in events if e[0] == "rpc.server" and e[3].get("method") == "VolumeEcShardsGenerate"]
    (run,) = [e for e in events if e[0] == "encode.run"]
    assert len(generate) == 1 and generate[0][1] <= run[1] and run[1] + run[2] <= generate[0][1] + generate[0][2]
    assert run[3] == {}  # volume, bytes, batches are annotated after its start: the ring has them, the mirror not
    writes = [e for e in events if e[0] == "encode.write"]
    assert len({e[3]["bytes"] for e in writes}) == 1 and writes[0][3]["bytes"] > 0
    # the shell's first RPC reaches the server well after the window opened: the child's start
    assert span_reduce.first_span_start_ms({"spans": [recorded]}, "rpc.server") > 100


def test_recorded_trace_self_times_account_for_the_run(recorded):
    names = span_reduce.by_name(recorded["threads"])
    stages = sum(names[f"encode.{s}"]["self_s"] for s in span_reduce.STAGES)
    assert stages == pytest.approx(names["encode.run"]["union_s"], rel=0.02)
    # a CPU trace has no device plane: nothing to attribute, and the reader says so
    assert span_reduce.idle_attributed_pct({"traces": [{"chips": []}], "spans": [recorded]}) is None


def test_the_parked_span_metrics_need_only_their_entries_and_the_listed_edits(tmp_path):
    with open(os.path.join(manifest.BENCH_DIR, "parked", "span-metrics.json")) as f:
        parked = json.load(f)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"] = doc["per_layer"] + parked["per_layer"]
    for name, spec in parked["layer_metrics"].items():
        (tmp_path / "layer_metrics").mkdir(exist_ok=True)
        (tmp_path / "layer_metrics" / f"{name}.json").write_text(json.dumps(spec))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    man = manifest.Manifest(str(path), bench_dir=str(tmp_path))
    assert len(parked["per_layer"]) == 15 and set(parked["layer_metrics"]) == {m["name"] for m in parked["per_layer"]}
    for m in parked["per_layer"]:
        assert m["source"] == "program_span" and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["layer"] in {x["layer"] for x in man.doc["per_layer"][:7]}  # a layer BENCHMARK.json names
        spec = man.layer_metric_spec(m["name"])
        assert spec["reader"] in span_reduce.READERS
        (cell,) = m["workloads"]
        assert cell in man.end_to_end[m["moves"]]["workloads"]
    facts = _facts()
    got = span_reduce.parked_metrics(facts, "warm10p4.encode-cycle")
    assert got["encode_sync_ms"] == {"value": pytest.approx(2000.0), "unit": "ms"}
    assert set(got) == {"encode_read_ms", "encode_write_ms", "encode_dispatch_ms", "encode_sync_ms",
                        "encode_shell_start_ms", "encode_idle_attributed_pct"}  # no CRC span in the hand-made lists
    assert span_reduce.parked_metrics(facts, "warm10p4.rebuild-4lost").keys() == {
        "rebuild_shell_start_ms", "rebuild_idle_attributed_pct"}
