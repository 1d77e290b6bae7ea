"""Traffic `encode_cycle`: one sealed volume turned into 14 shards through the
operator's entry point, again and again.

Window: repeat {timed `shell -c "lock; ec.encode -volumeId V -force; unlock"`;
untimed `ec.decode -volumeId V`, the restored .dat compared by sha256, wait
until the master lists V as a normal volume again} until `--seconds` have
passed; an operation that has started is finished, and the window ends on an
encode, whose shards are then checked. Rate = bytes of sealed volume over the
seconds of the timed commands alone."""

from __future__ import annotations

import os
import time

from drivers import common
from harness import checks

FAULTS = ("flip_shard_byte", "broken_apply")


def _encode(run) -> None:
    run.srv.shell(common.LOCK.format(f"ec.encode -volumeId {run.vid} -force"))


def _decode(run) -> bool:
    """Untimed restore. -> whether the .dat came back byte for byte."""
    run.srv.shell(common.LOCK.format(f"ec.decode -volumeId {run.vid}"))
    same = os.path.exists(run.base + ".dat") and checks.file_sha(run.base + ".dat") == run.dat_sha
    run.srv.wait_volume(run.vid)
    common.settle_disk()
    return same


def setup(run) -> None:
    common.build_and_boot(run)
    if run.trace:
        common.shell_noop_ms(run)
    with run.phase("warm_encode"):
        _encode(run)
    with run.phase("warm_decode"):
        run.warm_dat_differs = 0 if _decode(run) else 1


def window(run) -> None:
    run.timed = []
    run.dat_differs = 0
    width = -(-run.ds.dat_bytes // (10 * (1 << 20))) * (1 << 20)
    t_end = time.monotonic() + run.seconds
    while True:
        run.attempted += 1
        try:
            wall = common.timed_op(run, _encode, {"width": width})
        except common.BenchError as e:
            print(f"benchmark: timed ec.encode failed: {e}", flush=True)
            run.failed += 1
            break
        run.timed.append(wall)
        if time.monotonic() >= t_end:
            break
        if not _decode(run):
            run.dat_differs += 1
            run.failed += 1
    seconds = sum(run.timed)
    if seconds > 0:
        run.metrics["encode_MBps"] = len(run.timed) * run.ds.dat_bytes / 1e6 / seconds
    common.say(timed_ops=len(run.timed), timed_seconds=[round(t, 4) for t in run.timed])


def verify(run) -> None:
    if run.fault == "flip_shard_byte":
        common.flip_byte(checks.shard_path(run.base, 11), run.seed)
    got = checks.check_shards(run.base, run.orig_dat, run.seed, int(run.traffic["parity_rows_checked"]))
    for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
        run.check(name, got[name], 0)
    run.check("restored_dat_differing", run.dat_differs + run.warm_dat_differs, 0)
    # the last encode's data shards decode back to the volume, too
    run.check("final_dat_differing", 0 if _decode(run) else 1, 0)
