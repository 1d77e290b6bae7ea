"""Traffic `encode_cycle`: one sealed volume turned into 14 shards through the
operator's entry point, again and again, at a pace the cell fixes.

Window: every `period_s` seconds (or as soon after as the last cycle is done) a
timed `shell -c "lock; ec.encode -volumeId V -force; unlock"`; then, untimed,
the CRC32 of each of the 14 shard files is kept and the volume is put back
WITHOUT writing it again: the shards deleted by `VolumeEcShardsDelete`, the
original .dat and .idx linked back under the volume's names, `VolumeMount`, wait
until the master lists V as a normal volume. An operation that has started is
finished, and the window ends on an encode, whose shards are then checked
against the reference; every earlier encode has to have written the same 14
CRC32s, and `ec.decode` has to give the .dat back from the last one's. The pace
and the restore keep what the window writes (1.5 GB a command) under what the
machine's disk takes for good (PERF.md section 6, PR 33). Rate = bytes of
sealed volume over the seconds of the timed commands alone, all of them; beside
it their median wall (`common.bulk_rate`)."""

from __future__ import annotations

import concurrent.futures
import os
import time
import zlib

from drivers import common
from harness import checks

FAULTS = ("flip_shard_byte", "flip_first_encode", "broken_apply")
SHARDS = checks.DATA + checks.PARITY


def _encode(run) -> None:
    run.srv.shell(common.LOCK.format(f"ec.encode -volumeId {run.vid} -force"))


def _decode(run) -> bool:
    """Untimed `ec.decode`. -> whether the .dat came back byte for byte."""
    run.srv.shell(common.LOCK.format(f"ec.decode -volumeId {run.vid}"))
    same = os.path.exists(run.base + ".dat") and checks.file_sha(run.base + ".dat") == run.dat_sha
    run.srv.wait_volume(run.vid)
    common.settle_disk()
    return same


def _file_crc(path: str) -> int | None:
    if not os.path.exists(path):
        return None
    crc = 0
    with open(path, "rb") as f:
        while chunk := f.read(8 << 20):
            crc = zlib.crc32(chunk, crc)
    return crc


def _shard_crcs(run) -> list:
    """CRC32 of each shard file as it lies on disk (zlib lets go of the GIL: a thread a file)."""
    with concurrent.futures.ThreadPoolExecutor(SHARDS) as pool:
        return list(pool.map(_file_crc, [checks.shard_path(run.base, s) for s in range(SHARDS)]))


def _restore(run) -> None:
    """Untimed: the volume as it was before the encode, and not a byte of it written."""
    run.srv.delete_shards(run.vid, list(range(SHARDS)))
    os.link(run.orig_dat, run.base + ".dat")
    os.link(run.orig_idx, run.base + ".idx")
    run.srv.mount_volume(run.vid)
    run.srv.wait_volume(run.vid)
    common.settle_disk()


def setup(run) -> None:
    common.build_and_boot(run)
    if run.trace:
        common.shell_noop_ms(run)
    with run.phase("warm_encode"):
        _encode(run)
    with run.phase("warm_restore"):
        _restore(run)


def window(run) -> None:
    run.timed = []
    run.cycle_crcs = []
    period = float(run.traffic["period_s"])
    width = -(-run.ds.dat_bytes // (10 * (1 << 20))) * (1 << 20)
    t0 = time.monotonic()
    while True:
        run.attempted += 1
        try:
            wall = common.timed_op(run, _encode, {"width": width})
        except common.BenchError as e:
            print(f"benchmark: timed ec.encode failed: {e}", flush=True)
            run.failed += 1
            break
        run.timed.append(wall)
        if run.fault == "flip_first_encode" and len(run.timed) == 1:
            common.flip_byte(checks.shard_path(run.base, 3), run.seed)
        run.cycle_crcs.append(_shard_crcs(run))
        if time.monotonic() >= t0 + run.seconds:
            break
        _restore(run)
        time.sleep(max(0.0, t0 + len(run.timed) * period - time.monotonic()))
    common.bulk_rate(run, "encode", run.ds.dat_bytes)


def verify(run) -> None:
    if run.fault == "flip_shard_byte":
        common.flip_byte(checks.shard_path(run.base, 11), run.seed)
    got = checks.check_shards(run.base, run.orig_dat, run.seed, int(run.traffic["parity_rows_checked"]))
    for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
        run.check(name, got[name], 0)
    # every encode of the window wrote the 14 files the last one wrote, which the lines above hold to the reference
    crcs = run.cycle_crcs or [[None]]
    run.check("encodes_differing", sum(1 for c in crcs if c != crcs[-1] or None in c), 0)
    # the last encode's data shards decode back to the volume, too
    run.check("final_dat_differing", 0 if _decode(run) else 1, 0)
