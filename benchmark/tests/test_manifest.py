import copy
import json
import os

import pytest

from harness import manifest

with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as _f:
    GOOD = json.load(_f)


def write(tmp_path, doc):
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(doc))
    return str(p)


def test_the_committed_manifest_loads_and_every_cell_has_its_files():
    man = manifest.Manifest()
    for name in man.workloads:
        cell = man.cell(name)
        assert cell["config"]["guarantees"] and cell["config"]["reduced"]
        assert man.metrics_of("end_to_end", name)
        for m in man.metrics_of("per_layer", name):
            assert man.layer_metric_spec(m["name"])["reader"]
            assert name in man.end_to_end[m["moves"]].get("workloads", [name])


@pytest.mark.parametrize("bad", ["get p95", "a,b", "a/b", "", "x" * 65, "µs"])
def test_a_name_outside_the_allowed_set_is_rejected(tmp_path, bad):
    doc = copy.deepcopy(GOOD)
    doc["end_to_end"][0]["name"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.Manifest(write(tmp_path, doc))


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "x" * 17])
def test_a_unit_outside_the_allowed_set_is_rejected(tmp_path, bad):
    doc = copy.deepcopy(GOOD)
    doc["end_to_end"][0]["unit"] = bad
    with pytest.raises(manifest.ManifestError):
        manifest.Manifest(write(tmp_path, doc))


def test_a_cell_whose_files_are_missing_is_rejected(tmp_path):
    doc = copy.deepcopy(GOOD)
    doc["workloads"].append({"name": "warm10p4.nothing", "config": "warm10p4",
                             "traffic": "no-such-mix", "chips": 1, "why": "x"})
    man = manifest.Manifest(write(tmp_path, doc))
    with pytest.raises(manifest.ManifestError, match="missing file"):
        man.cell("warm10p4.nothing")
    with pytest.raises(manifest.ManifestError, match="unknown workload"):
        man.cell("never.named")


def test_a_layer_metric_must_move_an_end_to_end_metric(tmp_path):
    doc = copy.deepcopy(GOOD)
    doc["per_layer"][0]["moves"] = "nothing_known"
    with pytest.raises(manifest.ManifestError):
        manifest.Manifest(write(tmp_path, doc))


def test_the_parked_cell_needs_only_its_entries(tmp_path):
    """`benchmark/parked/<cell>.json` holds the BENCHMARK.json entries of a cell
    whose files are in place but which is not run yet; adding them must be all
    that a later PR has to do."""
    with open(os.path.join(manifest.BENCH_DIR, "parked", "bench1k.get-4lost.json")) as f:
        parked = json.load(f)
    doc = copy.deepcopy(GOOD)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        doc[key] = doc[key] + parked[key]
    man = manifest.Manifest(write(tmp_path, doc))
    cell = man.cell("bench1k.get-4lost")
    assert cell["driver"] == "closed_loop_get" and cell["config"]["dataset"]["files"] == 262144
    assert {m["name"] for m in man.metrics_of("end_to_end", "bench1k.get-4lost")} == {
        "get_p95_ms", "get_ops_per_s", "setup_s"}
    for m in man.metrics_of("per_layer", "bench1k.get-4lost"):
        assert man.layer_metric_spec(m["name"])["reader"]
