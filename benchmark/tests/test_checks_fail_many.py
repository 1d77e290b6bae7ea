"""The new cell's check must be able to fail too (`test_checks_fail.py`'s
cases, for `many10p4.rebuild-1lost-each`): a whole run at the rehearsal's size
on the CPU, sound or with one fault, read from the result line. The facts a
driver adds to `"timed"` are there beside the five every driver prints; a fact
is null where the command that would have said it failed."""

import json
import os
import subprocess
import sys

import pytest

from harness.manifest import BENCH_DIR, ROOT

WORKLOAD = "many10p4.rebuild-1lost-each"
FACTS = {"volumes", "signature_groups", "rpcs_per_command", "programs_compiled_in_window"}


@pytest.mark.parametrize("fault,sound", [("", True), ("flip_shard_byte", False), ("broken_apply", False)])
def test_checks_come_out(fault, sound):
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", WORKLOAD,
           "--seed", str(2**31 + 12), "--seconds", "1", "--trace", "0", "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearse"] is True
    assert result["checks_ok"] is sound, p.stdout[-3000:]
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks" and result["checks"]["failed_ops"] == {"value": result["failed"], "limit": 0}
    assert (all(c["value"] == 0 for c in result["checks"].values())) is sound
    timed = result["timed"]
    assert set(timed) == {"ops", "median_s", "max_s", "stalled_ops", "median_rate_MBps"} | FACTS
    assert timed["volumes"] == 8
    if sound:
        assert timed["ops"] == result["attempted"] and 0 < timed["median_s"] <= timed["max_s"]
        assert result["metrics"]["rebuild_MBps"]["value"] > 0
        assert not [name for name in result["metrics"] if name.endswith("_cmd_p50_s")]
        # all 8 x 17 files, CRCs, rows and GETs of every volume were compared
        assert len(result["checks"]) == 3 + 8 * 5 + 1


def test_the_cell_is_in_the_manifest_with_its_metrics():
    from harness.manifest import Manifest

    man = Manifest()
    cell = man.cell(WORKLOAD)
    assert cell["driver"] == "rebuild_1lost_each" and cell["workload"]["chips"] == 1
    assert [m["name"] for m in man.metrics_of("end_to_end", WORKLOAD)] == ["rebuild_MBps", "setup_s"]
    per_layer = {m["name"] for m in man.metrics_of("per_layer", WORKLOAD)}
    assert per_layer == {"many_rebuild_rpc_ms", "many_dispatch_gap_ms", "many_roofline", "device_idle_pct.many"}
    from harness import reducers
    for name in per_layer:
        assert man.layer_metric_spec(name)["reader"] in reducers.READERS
    config = cell["config"]
    assert sorted(int(v) for v in config["lost_shard_of_volume"]) == cell["traffic"]["volume_ids"]
    assert set(config["reduced"]) == set(man.configs["many10p4"]["reduced"])
