"""The sweep cell's check must be able to fail too (`test_checks_fail.py`'s
cases, for `sweep10p4.encode-8x128m`): a whole run at the rehearsal's size on
the CPU, sound or with one fault, read from the result line. The facts the
driver adds to `"timed"` are there beside the five every driver prints."""

import json
import os
import subprocess
import sys

import pytest

from harness.manifest import BENCH_DIR, ROOT

WORKLOAD = "sweep10p4.encode-8x128m"
FACTS = {"volumes", "batches", "rpcs_per_command", "programs_compiled_in_window"}
CASES = [("", None), ("flip_shard_byte", "v1.crc_mismatches"), ("flip_first_encode", "encodes_differing"),
         ("broken_apply", "v1.parity_cells_differing")]


@pytest.mark.parametrize("fault,shows_in", CASES)
def test_checks_come_out(fault, shows_in):
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", WORKLOAD,
           "--seed", str(2**31 + 40 + len(fault)), "--seconds", "2", "--trace", "0", "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearse"] is True
    assert result["checks_ok"] is (not fault), p.stdout[-3000:]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks" and result["checks"]["failed_ops"] == {"value": 0, "limit": 0}
    # the cut-over's four, 8 x (files, CRC32s, data cells, parity cells, GETs, the decoded .dat), failed operations
    assert len(result["checks"]) == 4 + 8 * 6 + 1
    wrong = {name for name, c in result["checks"].items() if c["value"] != 0}
    timed = result["timed"]
    if fault == "flip_first_encode":
        # the first sweep's files are gone by the end: only the CRC32s kept of each sweep show
        # it (where the machine was so slow that the window's first sweep was its last, the files do)
        assert wrong == {"encodes_differing"} if timed["ops"] > 1 else wrong
    else:
        assert (shows_in in wrong) if fault else not wrong, wrong
    assert set(timed) == {"ops", "median_s", "max_s", "stalled_ops", "median_rate_MBps"} | FACTS
    assert timed["volumes"] == 8 and timed["programs_compiled_in_window"] == 0
    assert timed["ops"] == result["attempted"] and 0 < timed["median_s"] <= timed["max_s"]
    assert timed["batches"] >= 1 and timed["rpcs_per_command"]["VolumeEcShardsGenerateBatch"] == 1
    assert timed["rpcs_per_command"]["VolumeEcShardsGenerate"] == 0
    assert result["metrics"]["encode_MBps"]["value"] > 0
    assert not [name for name in result["metrics"] if name.endswith("_cmd_p50_s")]


def test_the_cell_is_in_the_manifest_with_its_metrics():
    from harness import reducers
    from harness.manifest import Manifest

    man = Manifest()
    cell = man.cell(WORKLOAD)
    assert cell["driver"] == "encode_sweep" and cell["workload"]["chips"] == 1
    assert [m["name"] for m in man.metrics_of("end_to_end", WORKLOAD)] == ["encode_MBps", "setup_s"]
    per_layer = {m["name"] for m in man.metrics_of("per_layer", WORKLOAD)}
    assert per_layer == {"shell_noop_ms", "encode_dispatch_gap_ms", "encode_roofline", "device_idle_pct.encode",
                         "sweep_encode_rpc_ms", "sweep_cutover_rpc_ms"}
    for name in per_layer:
        assert man.layer_metric_spec(name)["reader"] in reducers.READERS
    config = cell["config"]
    assert config["volumes"] == len(cell["traffic"]["volume_ids"]) == 8
    assert set(config["reduced"]) == set(man.configs["sweep10p4"]["reduced"])
    assert config["source"] == man.configs["sweep10p4"]["source"]
    # the accepted encode cell keeps its own metrics, and gains none of the sweep's
    assert {m["name"] for m in man.metrics_of("per_layer", "warm10p4.encode-cycle")} == per_layer - {
        "sweep_encode_rpc_ms", "sweep_cutover_rpc_ms"}
