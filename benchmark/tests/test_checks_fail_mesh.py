"""The four-chip cell's check must be able to fail too (`test_checks_fail_many.py`'s
cases, for `mesh10p4.rebuild-4lost-x4`): a whole run at the rehearsal's size on
the CPU, sound or with the control's fault, read from the result line.

A rehearsal runs the ONE-DEVICE backend: `harness/server.py` sets
`WEEDTPU_BACKEND=jax` for every platform but the TPU, over the driver's
`server_env`. So the mesh facts (what `/status` must report, the devices a batch
lay on) are asserted only off rehearsal, by the driver on the chips; here the
two checks that hold them read 0 without having looked, and the facts the mesh
counters feed read null. The mesh itself on the CPU is `tests/`' to hold (four
forced host devices, `tests/test_mesh_rebuild_cell.py`). `broken_apply` is not
among the cell's faults: the mesh programs never pass `rs_jax.apply_matrix`,
which it wraps; the same file of `tests/` breaks `rs_jax.gf_apply` under them."""

import json
import os
import subprocess
import sys

import pytest

from drivers import rebuild_4lost_x4 as driver
from harness import reducers
from harness.manifest import BENCH_DIR, ROOT, Manifest

WORKLOAD = "mesh10p4.rebuild-4lost-x4"
FACTS = {"volumes", "rpcs_per_command", "mesh_batches_per_command", "programs_compiled_in_window"}
CASES = [("", set()), ("flip_shard_byte", {"rebuilt_shards_differing", "v1.crc_mismatches"})]


def rehearse(fault: str, seed: int, env=None) -> tuple[dict, str]:
    """-> (the result line, everything the run printed)."""
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", WORKLOAD,
           "--seed", str(seed), "--seconds", "1", "--trace", "0", "--rehearse"]
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
    # shards, where decoded, on how many devices, /status, compiles, the master's list;
    # 2 x (files, CRC32s, data cells, parity cells, GETs); failed operations
    assert len(result["checks"]) == 6 + 2 * 5 + 1
    wrong = {name for name, c in result["checks"].items() if c["value"] != 0}
    assert shows_in <= wrong and (fault or not wrong), wrong
    timed = result["timed"]
    assert set(timed) == {"ops", "median_s", "max_s", "stalled_ops", "median_rate_MBps"} | FACTS
    assert timed["volumes"] == 2 and timed["programs_compiled_in_window"] == 0
    assert timed["mesh_batches_per_command"] is None  # a rehearsal's backend is not the mesh
    assert timed["ops"] == result["attempted"] and 0 < timed["median_s"] <= timed["max_s"]
    rpcs = timed["rpcs_per_command"]
    assert rpcs["VolumeEcShardsRebuildBatch"] == 1 and rpcs["VolumeEcShardsRebuild"] == 0
    assert rpcs["VolumeStatus"] == 0 and rpcs["VolumeEcShardsCopy"] == 0
    assert result["metrics"]["rebuild_MBps"]["value"] > 0
    assert '"output": ["ec.rebuild batch on ' in out and ": 2 volumes in 1 signature groups" in out


@pytest.mark.parametrize("fault,shows_in", CASES)
def test_checks_come_out(fault, shows_in):
    result, out = rehearse(fault, 2**31 + 480 + len(fault))
    see(result, out, fault, shows_in)


def test_the_cell_is_in_the_manifest_with_its_metrics():
    man = Manifest()
    cell = man.cell(WORKLOAD)
    assert cell["driver"] == "rebuild_4lost_x4" and cell["workload"]["chips"] == 4
    # the one cell of four chips: at most half of the cells may ask for four
    assert [w["name"] for w in man.workloads.values() if w["chips"] == 4] == [WORKLOAD]
    assert [m["name"] for m in man.metrics_of("end_to_end", WORKLOAD)] == ["rebuild_MBps", "setup_s"]
    per_layer = {m["name"] for m in man.metrics_of("per_layer", WORKLOAD)}
    assert per_layer == {"mesh_rebuild_rpc_ms", "mesh_put_ms", "mesh_restore_ms", "mesh_dispatch_gap_ms",
                         "mesh_roofline", "device_idle_pct.mesh"}
    for name in per_layer:
        assert man.layer_metric_spec(name)["reader"] in reducers.READERS
        assert man.per_layer[name]["moves"] == "rebuild_MBps" and man.per_layer[name]["workloads"] == [WORKLOAD]
    config, traffic = cell["config"], cell["traffic"]
    assert config["volumes"] == len(traffic["volume_ids"]) == 2 and config["chips"] == 4
    assert traffic["min_commands"] >= 6
    # the worst legal loss of warm10p4, of both volumes: one signature, every batch takes the ring
    with open(os.path.join(BENCH_DIR, "configs", "warm10p4.json")) as f:
        assert config["lost_shards"] == json.load(f)["lost_shards"] == [0, 3, 11, 13]
    # the operator's one seam, and what it has to come to on four devices
    assert config["server_env"] == {"WEEDTPU_BACKEND": "mesh"}
    assert config["status"] == {"backend": "mesh", "mesh_shape": "2x2", "mesh_rebuild": "ring", "mesh_devices": 4}
    assert set(config["reduced"]) == set(man.configs["mesh10p4"]["reduced"])
    assert config["source"] == man.configs["mesh10p4"]["source"] and len(config["source"]) <= 200
    assert len({c["source"] for c in man.configs.values()}) == len(man.configs)
    assert os.path.exists(os.path.join(ROOT, config["reference"].split(" ")[0]))


def test_a_program_without_the_mesh_counters_gives_no_sample_and_no_metric():
    """The parent of PR 48 has neither `weedtpu_ec_mesh_seconds_total` nor
    `weedtpu_ec_mesh_batches_total`: the driver then takes no `mesh_put` /
    `mesh_restore` sample, and the reader leaves both metrics out of the line."""
    class Run:
        pass

    run = Run()
    run.marks = ({"weedtpu_rpc_server_seconds_sum{method=\"VolumeEcShardsRebuildBatch\"}": 1.0},
                 {"weedtpu_rpc_server_seconds_sum{method=\"VolumeEcShardsRebuildBatch\"}": 1.5})
    assert driver._mesh_batches(run) == {}
    man = Manifest()
    facts = {"samples": {"rebuild_rpc": [0.5]}, "setup": {}, "traces": [], "traced": [], "device_kind": None}
    for name, want in (("mesh_rebuild_rpc_ms", 500.0), ("mesh_put_ms", None), ("mesh_restore_ms", None),
                       ("mesh_dispatch_gap_ms", None), ("mesh_roofline", None), ("device_idle_pct.mesh", None)):
        spec = man.layer_metric_spec(name)
        assert reducers.READERS[spec["reader"]](facts, **spec.get("args", {})) == want
    run.marks[1].update({'weedtpu_ec_mesh_batches_total{variant="ring",devices="4"}': 54.0,
                         'weedtpu_ec_mesh_batches_total{variant="cols",devices="4"}': 2.0,
                         'weedtpu_ec_mesh_batches_total{variant="ring",devices="1"}': 0.0})
    assert driver._mesh_batches(run) == {"ring/4": 54, "cols/4": 2}
