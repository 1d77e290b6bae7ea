"""The cluster sweep cell's check must be able to fail too
(`test_checks_fail_sweep.py`'s cases, for
`sweepspread10p4.encode-4x128m-4srv-x12`): a whole run at the rehearsal's size
on the CPU, four servers, sound or with one fault, read from the result line.
Each new guarantee has its control: a shard byte flipped on a PEER after the
program wrote it, a fifth shard of one volume planted on one server, a byte of
the window's first sweep altered, the device's answer altered."""

import json
import os
import subprocess
import sys

import pytest

from harness.manifest import BENCH_DIR, ROOT

WORKLOAD = "sweepspread10p4.encode-4x128m-4srv-x12"
FACTS = {"volumes", "batches", "rpcs_per_command", "copied_bytes_per_command", "shards_per_server",
         "programs_compiled_in_window"}
PLACEMENT = {"shards_not_on_one_server", "servers_over_4_of_a_volume", "shards_not_listed"}
CASES = [("", set()), ("flip_peer_shard_byte", {"v1.crc_mismatches"}), ("plant_fifth_shard", PLACEMENT),
         ("flip_first_encode", {"encodes_differing"}), ("broken_apply", {"v1.parity_cells_differing"})]


def rehearse(fault: str, seed: int, env=None) -> tuple[dict, str]:
    """-> (the result line, everything the run printed)."""
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", WORKLOAD,
           "--seed", str(seed), "--seconds", "2", "--trace", "0", "--rehearse"]
    p = subprocess.run(cmd + (["--fault", fault] if fault else []), cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def see(result: dict, out: str, fault: str, shows_in: set) -> None:
    """What every case of the cell has to show, sound or broken."""
    assert result["correct"] is False and result["rehearse"] is True
    assert result["checks_ok"] is (not fault), out[-3000:]
    assert result["attempted"] >= 2 and result["failed"] == 0  # `min_commands` of the rehearsal
    assert list(result)[-1] == "checks" and result["checks"]["failed_ops"] == {"value": 0, "limit": 0}
    # the sweeps' two, the placement's three, what was left (2), 4 x (files, CRC32s, data cells, parity cells),
    # the GETs, 4 decoded .dat, failed operations
    assert len(result["checks"]) == 2 + 3 + 2 + 4 * 4 + 1 + 4 + 1
    wrong = {name for name, c in result["checks"].items() if c["value"] != 0}
    if fault == "flip_first_encode":
        assert wrong == shows_in  # the first sweep's files are gone by the end: only the kept CRC32s show it
    else:
        assert shows_in <= wrong and (fault or not wrong), wrong
    timed = result["timed"]
    assert set(timed) == {"ops", "median_s", "max_s", "stalled_ops", "median_rate_MBps"} | FACTS
    assert timed["volumes"] == 4 and timed["programs_compiled_in_window"] == 0 and timed["batches"] >= 1
    assert timed["ops"] == result["attempted"] and 0 < timed["median_s"] <= timed["max_s"]
    rpcs = timed["rpcs_per_command"]
    assert rpcs["VolumeEcShardsGenerateBatch"] == 1 and rpcs["VolumeEcShardsGenerate"] == 0
    assert rpcs["VolumeEcShardsCopy"] == 12 and rpcs["VolumeDelete"] == 4 and rpcs["VolumeMarkWritable"] == 0
    assert sum(timed["shards_per_server"]) == 56  # the window's last sweep, before any control's fault
    assert timed["copied_bytes_per_command"] > 0
    assert result["metrics"]["encode_MBps"]["value"] > 0
    assert not [name for name in result["metrics"] if name.endswith("_cmd_p50_s")]


@pytest.mark.parametrize("fault,shows_in", CASES)
def test_checks_come_out(fault, shows_in):
    result, out = rehearse(fault, 2**31 + 450 + len(fault))
    see(result, out, fault, shows_in)


def test_the_cell_is_in_the_manifest_with_its_metrics():
    from harness import reducers
    from harness.manifest import Manifest

    man = Manifest()
    cell = man.cell(WORKLOAD)
    assert cell["driver"] == "encode_sweep_spread" and cell["workload"]["chips"] == 1
    assert [m["name"] for m in man.metrics_of("end_to_end", WORKLOAD)] == ["encode_MBps", "setup_s"]
    per_layer = {m["name"] for m in man.metrics_of("per_layer", WORKLOAD)}
    assert per_layer == {"shell_noop_ms", "encode_dispatch_gap_ms", "encode_roofline", "device_idle_pct.encode",
                         "sweep_encode_rpc_ms", "sweep_cutover_rpc_ms", "sweepspread_copy_ms",
                         "sweepspread_handover_ms"}
    for name in per_layer:
        assert man.layer_metric_spec(name)["reader"] in reducers.READERS
    config, traffic = cell["config"], cell["traffic"]
    assert config["volumes"] == len(traffic["volume_ids"]) == 4
    assert config["servers"] == 1 + len(config["cluster"]["peer_racks"]) == 4
    assert traffic["period_s"] == 3.5 and traffic["min_commands"] >= 12
    assert set(config["reduced"]) == set(man.configs["sweepspread10p4"]["reduced"])
    assert config["source"] == man.configs["sweepspread10p4"]["source"] and len(config["source"]) <= 200
    # a source of its own: two deployments from one page need sources that differ
    assert len({c["source"] for c in man.configs.values()}) == len(man.configs)
    # the one-server sweep keeps its own metrics, this cell reports them all, and its two stay its own
    assert {m["name"] for m in man.metrics_of("per_layer", "sweep10p4.encode-8x128m")} == per_layer - {
        "sweepspread_copy_ms", "sweepspread_handover_ms"}
