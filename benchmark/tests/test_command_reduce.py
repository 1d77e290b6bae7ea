"""The command reduction, on hand-made lists and on a small recorded trace that
holds a whole command: `data/rehearsal_command/.../vm.xplane.pb`, the second traced
stretch of `run.py --workload warm10p4.encode-cycle --seed 4200000001 --seconds 3
--trace 1 --rehearse --keep-trace` (PR 42; CPU, the tiny rehearsal volume: one timed
`shell -c "lock; ec.encode ...; unlock"`, whose child handed its `shell.script` trace
to the chip-owning server's master, which left it in the profiler as one `shell.trace`
annotation beside its own mirrored spans; no device plane, and its times are no
device's)."""

import json
import os

import pytest

from harness import command_reduce, manifest, span_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "rehearsal_command")

# The child's tree, as `obs/trace.py` `flatten` spells it: born at wall clock
# 1,000 s; start 0-300 ms, lock 300-310, ec.encode 310-990 (plan 320-370 with one
# RPC, the batch RPC 400-900, a mount 900-950 from a pool thread), unlock 990-999.
NAMES = ["shell.script", "shell.start", "shell.command", "rpc.client", "shell.command", "shell.plan",
         "rpc.client", "rpc.client", "rpc.client", "shell.command", "rpc.client"]
WHAT = ["", "", "lock", "LeaseAdminToken", "ec.encode", "", "VolumeList", "VolumeEcShardsGenerateBatch",
        "VolumeEcShardsMount", "unlock", "ReleaseAdminToken"]
T_MS = [0, 0, 300, 301, 310, 320, 321, 400, 900, 990, 991]
DUR_MS = [1000, 300, 10, 8, 680, 50, 40, 500, 50, 9, 7]
DEPTH = [0, 1, 1, 2, 1, 2, 3, 2, 2, 1, 2]
THREAD = [0, 0, 0, 0, 0, 0, 0, 0, 1, 0, 0]
BIRTH_NS = 1000 * 10**9


def _join(xs, scale=1):
    return ";".join(str(x * scale) for x in xs)


def receipt(at_s=3.1, **over):
    attrs = {"trace_id": "ab12", "birth_unix_ns": BIRTH_NS, "names": ";".join(NAMES), "what": ";".join(WHAT),
             "t_ns": _join(T_MS, 10**6), "dur_ns": _join(DUR_MS, 10**6), "depth": _join(DEPTH),
             "thread": _join(THREAD), "start_ms": "170.0;90.0;40.0"}
    attrs.update(over)
    return ("shell.trace", at_s, 0.00001, attrs)


def root(method, at_s, dur_s, wall_ms_after_birth, **attrs):
    """A mirrored `rpc.server` root: it says the wall clock at its start."""
    return ("rpc.server", at_s, dur_s, {"method": method, "trace_id": "ab12",
                                        "unix_ns": BIRTH_NS + int(wall_ms_after_birth * 1e6), **attrs})


# the server's half, on the stretch's clock: the child was born at 2.0 s after the
# window opened; the batch RPC 2.4005-2.8995 with its run, a dispatch and a sync
SERVER = [
    root("LeaseAdminToken", 2.3015, 0.007, 301.5),
    root("VolumeList", 2.3215, 0.039, 321.5),
    root("VolumeEcShardsGenerateBatch", 2.4005, 0.499, 400.5),
    ("encode.run", 2.401, 0.45, {}),
    ("encode.dispatch", 2.45, 0.01, {}),
    ("encode.dispatch", 2.60, 0.01, {}),
    ("encode.sync", 2.70, 0.10, {}),
    ("ec.ecx", 2.86, 0.03, {}),
    root("VolumeEcShardsMount", 2.9005, 0.049, 900.5),
    ("ec.mount", 2.901, 0.030, {}),
    ("vs.heartbeat", 2.932, 0.010, {}),
    root("ReleaseAdminToken", 2.9915, 0.006, 991.5),
]
STRETCH = {"window_found": True, "threads": [SERVER, [receipt()]]}


def test_a_receipt_maps_onto_the_stretchs_clock_through_any_mirrored_root():
    (script,) = command_reduce.commands(STRETCH)
    assert script["trace_id"] == "ab12" and script["birth_s"] == pytest.approx(2.0)
    assert script["start_ms"] == [170.0, 90.0, 40.0] and len(script["spans"]) == len(NAMES)
    assert script["spans"][7] == ("rpc.client", "VolumeEcShardsGenerateBatch", pytest.approx(2.4),
                                  pytest.approx(0.5), 2, 0)
    found = command_reduce.the_command(STRETCH, "ec.encode")
    assert found[1][:2] == ("shell.command", "ec.encode") and command_reduce.the_command(STRETCH, "ec.rebuild") is None
    # no mirrored root: no clock to take, nothing is said; a receipt cut short: the same
    assert command_reduce.commands({"threads": [[receipt()]]}) == []
    assert command_reduce.commands({"threads": [SERVER, [receipt(depth="0;1")]]}) == []
    assert command_reduce.commands({"threads": []}) == [] and command_reduce.commands({}) == []


def test_the_clock_check_finds_every_root_inside_its_client_and_says_by_how_much_not():
    (script,) = command_reduce.commands(STRETCH)
    assert command_reduce.clock_check(STRETCH, script) == {
        "roots": 5, "matched": 5, "largest_violation_us": 0.0, "no_client": []}
    # a server whose wall clock runs 2 ms behind the child's: its roots land 2 ms late on the
    # child's scale... here: the mount's root begins 0.7 ms before its client span
    early = [e if e[3].get("method") != "VolumeEcShardsMount" else root("VolumeEcShardsMount", 2.8993, 0.049, 899.3)
             for e in SERVER]
    shifted = {"threads": [early, [receipt()]]}
    (script2,) = command_reduce.commands(shifted)
    # the median offset is still the other four roots': the one that disagrees shows
    assert script2["birth_s"] == pytest.approx(2.0)
    got = command_reduce.clock_check(shifted, script2)
    assert got["roots"] == 5 and got["largest_violation_us"] == pytest.approx(700.0, abs=1e-3)
    # a root no span of the script sent (the rebuilder's own call to the master, under the script's id)
    other = {"threads": [SERVER + [root("LookupEcVolume", 2.45, 0.001, 450.0)], [receipt()]]}
    (script3,) = command_reduce.commands(other)
    got = command_reduce.clock_check(other, script3)
    assert (got["roots"], got["matched"], got["no_client"]) == (6, 5, ["LookupEcVolume"])


def _facts():
    chip = {"chip": 0, "window_s": 3.2, "busy_s": 0.02,
            "modules": [["jit__gf_apply_impl", 2.47, 0.01], ["jit__gf_apply_impl", 2.62, 0.01]],
            "top_ops": [], "gaps": [[0.0, 2.47], [2.48, 0.14], [2.63, 0.57]]}
    return {"traces": [{"chips": [chip]}], "spans": [STRETCH]}


def test_the_five_readers_on_hand_made_facts_and_none_where_no_command_was_traced():
    facts = _facts()
    assert command_reduce.command_span_ms(facts, "ec.encode", "shell.start") == pytest.approx(300.0)
    assert command_reduce.command_span_ms(facts, "ec.encode", "shell.plan") == pytest.approx(50.0)
    assert command_reduce.command_span_ms(facts, "lock", "shell.plan") is None
    assert command_reduce.command_head_ms(facts, "ec.encode", "encode.dispatch") == pytest.approx(450.0)
    assert command_reduce.command_head_ms(facts, "ec.encode", "rebuild.dispatch") is None
    # the last sync ends at 2.8; the script at 3.0
    assert command_reduce.command_tail_ms(facts, "ec.encode", "encode.sync") == pytest.approx(200.0)
    # the gap before the first device op, 0-2.47 s: 2.0 s before the child was born are nobody's;
    # from its birth on some span that is no root is open at every instant (the start, a command
    # and under it the plan or an RPC, the server's run)
    assert command_reduce.head_attributed_pct(facts, "ec.encode") == pytest.approx(100.0 * 0.47 / 2.47, abs=0.05)
    for reader, args in ((command_reduce.command_span_ms, {"command": "ec.rebuild", "span": "shell.start"}),
                         (command_reduce.command_head_ms, {"command": "ec.rebuild", "dispatch": "rebuild.dispatch"}),
                         (command_reduce.command_tail_ms, {"command": "ec.rebuild", "sync": "rebuild.sync"}),
                         (command_reduce.head_attributed_pct, {"command": "ec.rebuild"})):
        assert reader(facts, **args) is None
    # the parent commit's trace (spans, no receipt), and a run that traced nothing
    bare = {"traces": facts["traces"], "spans": [{"window_found": True, "threads": [SERVER]}]}
    assert command_reduce.command_head_ms(bare, "ec.encode", "encode.dispatch") is None
    assert command_reduce.head_attributed_pct({"traces": [], "spans": []}, "ec.encode") is None


def test_a_roots_own_time_names_nothing():
    """Where only roots are open (the script's, an RPC's) the gap is nobody's:
    a root says that a process was busy, not with what."""
    facts = _facts()
    facts["spans"] = [{"threads": [[e for e in SERVER if e[0] in ("rpc.server",)], [receipt()]]}]
    child_only = command_reduce.head_attributed_pct(facts, "ec.encode")
    # the child's own spans alone: 2.0-2.47 still named (start, commands, plan, rpc.client)
    assert child_only == pytest.approx(100.0 * 0.47 / 2.47, abs=0.05)
    just_roots = {"traces": facts["traces"], "spans": [{"threads": [
        [e for e in SERVER if e[0] == "rpc.server"],
        [receipt(names="shell.script;shell.command", what=";ec.encode", t_ns="0;2400000000",
                 dur_ns="3000000000;100000000", depth="0;1", thread="0;0", birth_unix_ns=BIRTH_NS - 2 * 10**9)]]}]}
    # born at the window's start: of 0-2.47 only 2.4-2.47 lies under a span that is no root
    assert command_reduce.head_attributed_pct(just_roots, "ec.encode") == pytest.approx(100.0 * 0.07 / 2.47, abs=0.05)


def test_rpc_self_times_are_what_no_span_under_the_root_names():
    rows = {r["method"]: r for r in command_reduce.rpc_self_times(STRETCH)}
    assert set(rows) == {"VolumeEcShardsGenerateBatch", "VolumeEcShardsMount"}  # the EC RPCs alone
    assert rows["VolumeEcShardsGenerateBatch"]["self_ms"] == pytest.approx(499 - 450 - 30)  # the run, the .ecx
    assert rows["VolumeEcShardsMount"]["self_ms"] == pytest.approx(49 - 30 - 10)
    assert rows["VolumeEcShardsMount"]["self_pct"] == pytest.approx(100 * 9 / 49)


@pytest.fixture(scope="module")
def recorded():
    return span_reduce.reduce_spans(DATA)


def test_recorded_trace_holds_one_command_whole_on_the_servers_clock(recorded):
    (script,) = command_reduce.commands(recorded)
    names = [s[0] for s in script["spans"]]
    assert names[:2] == ["shell.script", "shell.start"] and names.count("shell.command") == 3
    assert [s[1] for s in script["spans"] if s[0] == "shell.command"] == ["lock", "ec.encode", "unlock"]
    assert [s[1] for s in script["spans"] if s[0] == "rpc.client"] == [
        "LeaseAdminToken", "VolumeList", "VolumeList", "VolumeMarkReadonly", "VolumeEcShardsGenerateBatch",
        "VolumeEcShardsMount", "VolumeDelete", "ReleaseAdminToken"]
    interp, imports, connect = script["start_ms"]
    start = script["spans"][1]
    # grpc and the shell's own modules are most of a child's start; the env and its channel next to nothing
    assert interp > 0 and imports > 10 * connect > 0 and interp + imports + connect <= start[3] * 1e3 + 0.01
    # the driver opened the window, then spawned the child (the kernel's tick is 10 ms)
    assert -0.02 <= script["birth_s"] <= 0.1
    check = command_reduce.clock_check(recorded, script)
    assert check["roots"] == check["matched"] == 8 and check["largest_violation_us"] < 1000.0
    # every EC RPC of the command names what it does: the mount, the heartbeat, the .ecx, the removal
    rows = command_reduce.rpc_self_times(recorded)
    assert {r["method"] for r in rows} == {"VolumeEcShardsGenerateBatch", "VolumeEcShardsMount", "VolumeDelete"}
    assert all(r["self_ms"] < 20 and r["self_pct"] < 10 for r in rows), rows
    by_name = span_reduce.by_name(recorded["threads"])
    assert {n: by_name[n]["count"] for n in ("ec.ecx", "ec.mount", "vs.heartbeat", "volume.remove")} == {
        "ec.ecx": 1, "ec.mount": 1, "vs.heartbeat": 2, "volume.remove": 1}


def test_recorded_trace_gives_the_five_metrics_with_a_device_summary_beside_it(recorded):
    """A CPU trace has no device plane: the device's half is made by hand, one
    program run at each `encode.dispatch` the recorded server made."""
    dispatches = sorted(e[1] for t in recorded["threads"] for e in t if e[0] == "encode.dispatch")
    assert len(dispatches) == 1
    first = dispatches[0] + 0.002
    chip = {"chip": 0, "window_s": 0.6, "busy_s": 0.001, "modules": [["jit__gf_apply_impl", first, 0.001]],
            "top_ops": [], "gaps": [[0.0, first], [first + 0.001, 0.6 - first - 0.001]]}
    facts = {"traces": [{"chips": [chip]}], "spans": [recorded]}
    got = command_reduce.parked_metrics(facts, "warm10p4.encode-cycle")
    assert set(got) == {"encode_cmd_start_ms", "encode_cmd_plan_ms", "encode_cmd_head_ms", "encode_cmd_tail_ms",
                        "encode_head_attributed_pct"}
    assert got["encode_cmd_start_ms"] == {"value": pytest.approx(264.363, abs=1e-3), "unit": "ms"}
    assert got["encode_cmd_plan_ms"]["value"] == pytest.approx(4.081, abs=1e-3)
    assert got["encode_cmd_head_ms"]["value"] == pytest.approx(327.335, abs=1e-3)
    assert got["encode_cmd_tail_ms"]["value"] == pytest.approx(25.183, abs=1e-3)
    # from the child's birth on, every instant before the first device op is under a span that says what ran
    assert 97.0 <= got["encode_head_attributed_pct"]["value"] <= 100.0
    assert command_reduce.parked_metrics(facts, "warm10p4.rebuild-4lost") == {}  # no ec.rebuild was traced
    # a trace from before PR 42 (PR 25's recording): spans, no receipt, no metric, no error
    old = span_reduce.reduce_spans(os.path.join(os.path.dirname(DATA), "rehearsal_spans"))
    assert command_reduce.commands(old) == []
    assert command_reduce.parked_metrics({"traces": [{"chips": [chip]}], "spans": [old]}, "warm10p4.encode-cycle") == {}


def test_the_parked_command_metrics_need_only_their_entries_and_the_one_edit(tmp_path):
    with open(os.path.join(manifest.BENCH_DIR, "parked", "command-metrics.json")) as f:
        parked = json.load(f)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    assert parked["edits"] == ["benchmark/harness/reducers.py: READERS.update(command_reduce.READERS)"]
    doc["per_layer"] = doc["per_layer"] + parked["per_layer"]
    for name, spec in parked["layer_metrics"].items():
        (tmp_path / "layer_metrics").mkdir(exist_ok=True)
        (tmp_path / "layer_metrics" / f"{name}.json").write_text(json.dumps(spec))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    man = manifest.Manifest(str(path), bench_dir=str(tmp_path))
    assert len(parked["per_layer"]) == 10 and set(parked["layer_metrics"]) == {m["name"] for m in parked["per_layer"]}
    layers = {x["layer"] for x in man.doc["per_layer"][:17]}  # the layers BENCHMARK.json names
    for m in parked["per_layer"]:
        assert m["source"] == "program_span" and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["layer"] in layers and m["better"] == ("higher" if m["unit"] == "%" else "lower")
        spec = man.layer_metric_spec(m["name"])
        assert spec["reader"] in command_reduce.READERS
        side = m["name"].split("_")[0]
        assert m["moves"] == f"{side}_MBps" and spec["args"]["command"] == f"ec.{side}"
        assert all(cell in man.end_to_end[m["moves"]]["workloads"] for cell in m["workloads"])
    cells = {w["name"] for w in doc["workloads"]}
    assert {c for m in parked["per_layer"] for c in m["workloads"]} == cells  # all five cells, each side its own
    assert not set(parked["layer_metrics"]) & {m["name"] for m in doc["per_layer"][:17]}
    assert not set(command_reduce.READERS) & set(span_reduce.READERS)
