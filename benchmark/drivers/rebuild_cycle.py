"""Traffic `rebuild_cycle`: four of an EC volume's 14 shards lost and rebuilt
through the operator's entry point, again and again.

Set-up encodes the volume once and keeps the sha256 of the shards that will be
lost. Window: repeat {untimed `VolumeEcShardsDelete` of the lost shards, wait
until the master's topology has lost them; timed `shell -c "lock; ec.rebuild;
unlock"`; each rebuilt shard compared by sha256} until `--seconds` have passed;
an operation that has started is finished. Rate = bytes of lost shard restored
over the seconds of the timed commands alone, all of them (`common.bulk_rate`)."""

from __future__ import annotations

import os
import time

from drivers import common
from harness import checks
from harness.server import http_json

FAULTS = ("flip_shard_byte", "broken_apply")


def _lose(run) -> None:
    run.srv.delete_shards(run.vid, run.lost)
    left = [s for s in run.lost if os.path.exists(checks.shard_path(run.base, s))]
    common.require(not left, f"shards {left} survived VolumeEcShardsDelete")
    t0 = time.monotonic()
    while True:
        topo = http_json(f"http://{run.srv.master_http}/dir/status")["Topology"]
        have = topo.get("ec_volumes", {}).get(str(run.vid), {})
        if not any(str(s) in have and have[str(s)] for s in run.lost):
            common.settle_disk()
            return
        common.require(time.monotonic() - t0 < 60, "the master never noticed the lost shards")
        time.sleep(0.05)


def _rebuild(run) -> None:
    run.srv.shell(common.LOCK.format("ec.rebuild"))


def _differing(run) -> int:
    return sum(
        1 for s in run.lost
        if not os.path.exists(checks.shard_path(run.base, s))
        or checks.file_sha(checks.shard_path(run.base, s)) != run.shard_sha[s]
    )


def setup(run) -> None:
    run.lost = [int(s) for s in run.config["lost_shards"]]
    common.build_and_boot(run)
    with run.phase("encode"):
        run.srv.shell(common.LOCK.format(f"ec.encode -volumeId {run.vid} -force"))
    with run.phase("shard_sha"):
        run.shard_sha = {s: checks.file_sha(checks.shard_path(run.base, s)) for s in run.lost}
        run.shard_bytes = os.path.getsize(checks.shard_path(run.base, run.lost[0]))
    with run.phase("warm_rebuild"):
        _lose(run)
        try:
            _rebuild(run)
        except common.BenchError as e:  # counted below; the window then shows it too
            print(f"benchmark: the warm-up's ec.rebuild failed: {e}", flush=True)
        run.warm_shards_differ = _differing(run)


def window(run) -> None:
    run.timed = []
    run.shards_differ = 0
    t_end = time.monotonic() + run.seconds
    while True:
        _lose(run)
        run.attempted += 1
        try:
            wall = common.timed_op(run, _rebuild, {"width": run.shard_bytes})
        except common.BenchError as e:
            print(f"benchmark: timed ec.rebuild failed: {e}", flush=True)
            run.failed += 1
            break
        run.timed.append(wall)
        last = time.monotonic() >= t_end
        if last and run.fault == "flip_shard_byte":
            common.flip_byte(checks.shard_path(run.base, run.lost[0]), run.seed)
        differing = _differing(run)
        if differing:
            run.shards_differ += differing
            run.failed += 1
        if last:
            break
    common.bulk_rate(run, "rebuild", len(run.lost) * run.shard_bytes)


def verify(run) -> None:
    run.check("rebuilt_shards_differing", run.shards_differ + run.warm_shards_differ, 0)
    got = checks.check_shards(run.base, run.orig_dat, run.seed, int(run.traffic["parity_rows_checked"]))
    for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
        run.check(name, got[name], 0)
    picks = common.sample_needles(run, int(run.traffic["final_gets"]))
    run.check("final_gets_wrong", common.get_round(run, picks, expect="ec_intact"), 0)
