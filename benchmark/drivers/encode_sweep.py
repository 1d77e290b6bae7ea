"""Traffic `encode_sweep`: the maintenance script's pass over every volume
that filled up since the last one. `ec.encode` without `-volumeId` takes them
all in one command, again and again, at a pace the cell fixes.

Set-up builds the configuration's volumes from the seed (one data set per
volume id), keeps hard links to each `.dat` and `.idx` and each `.dat`'s
sha256, boots the chip-owning server over them, and runs one whole cycle to
warm every shape. Window: every `period_s` seconds (or as soon after as the
last cycle is done) ONE timed `shell -c "lock; ec.encode -force -checkpoint
<file>; unlock"`, which selects every volume of the default collection; then,
untimed, the CRC32 of every shard file of every volume is kept, the chip
server's counters are asked what the command did, and the volumes are put
back WITHOUT writing them again, as `encode_cycle` puts back one: the shards
deleted by `VolumeEcShardsDelete`, the original `.dat` and `.idx` linked back
under the volume's names, `VolumeMount`, wait until the master lists them as
normal volumes. An operation that has started is finished, and the window
ends on a sweep, whose shards are then checked against the reference; every
earlier sweep has to have written the same CRC32s, and `ec.decode` has to
give every `.dat` back from the last one's. Rate = bytes of sealed volume
(all of them) over the seconds of the timed commands alone, all of them
(`common.bulk_rate`).

`correct` holds the deployment's guarantees, never which RPC did the work: a
program that encodes volume by volume is as correct here as one that batches.
What the program's counters say of HOW it did it goes on the result line as
facts, inside `"timed"` (the one place of the line a driver fills): `volumes`,
`batches` (device dispatches, from the command's own output; null where it
does not say), `rpcs_per_command` (by method, on the chip server, the last
timed command's) and `programs_compiled_in_window`."""

from __future__ import annotations

import concurrent.futures
import functools
import multiprocessing
import os
import re
import time

from drivers import common
from drivers import encode_cycle as cycle
from drivers import rebuild_1lost_each as many
from harness import checks
from harness.peers import scrape
from harness.server import DEVICE_BACKENDS

FAULTS = ("flip_shard_byte", "flip_first_encode", "broken_apply")

SHARDS = checks.DATA + checks.PARITY
ENCODE_RPCS = ("VolumeEcShardsGenerate", "VolumeEcShardsGenerateBatch")
CUTOVER_RPCS = ("VolumeMarkReadonly", "VolumeMarkWritable", "VolumeEcShardsCopy", "VolumeEcShardsMount",
                "VolumeEcShardsDelete", "VolumeDelete")
RPCS = ENCODE_RPCS + CUTOVER_RPCS
ENCODED_BYTES = "weedtpu_ec_encode_bytes_total"
RUNS = "weedtpu_ec_encode_runs_total"
DISPATCHES = "weedtpu_ec_dispatch_total"
BATCHES_RE = re.compile(r"^ec\.encode batch on \S+: \d+ volumes in (\d+) batches", re.M)


def _base(run, vid: int) -> str:
    return os.path.join(run.data_dir, str(vid))


def _sweep(run) -> None:
    run.last_out = run.srv.shell(common.LOCK.format(f"ec.encode -force -checkpoint {run.checkpoint}"))


def _shard_crcs(run) -> list:
    """CRC32 of every shard file of every volume as it lies on disk, None for
    one that is not there (zlib lets go of the GIL: a thread a file)."""
    paths = [checks.shard_path(_base(run, vid), s) for vid in run.vids for s in range(SHARDS)]
    with concurrent.futures.ThreadPoolExecutor(SHARDS) as pool:
        return list(pool.map(cycle._file_crc, paths))


def _restore(run) -> None:
    """Untimed: every volume as it was before the sweep, and not a byte of it written."""
    for vid in run.vids:
        run.srv.delete_shards(vid, list(range(SHARDS)))
        os.link(run.orig_dat[vid], _base(run, vid) + ".dat")
        os.link(run.orig_idx[vid], _base(run, vid) + ".idx")
        run.srv.mount_volume(vid)
    for vid in run.vids:
        run.srv.wait_volume(vid)
    common.settle_disk()


def _look(run) -> None:
    """What the chip server's counters say of the last command: every volume's
    bytes were encoded THERE, by dispatches of its device backend and of no
    other; where the program counts encodes by backend, one a volume."""
    rose = functools.partial(many._rose, run)
    by_backend = {k: rose(k) for k in run.marks[1] if k.startswith((RUNS, DISPATCHES))}
    device = {f'{name}{{backend="{b}"}}' for name in (RUNS, DISPATCHES) for b in DEVICE_BACKENDS}
    runs = [n for k, n in by_backend.items() if k.startswith(RUNS) and k in device]
    if (rose(ENCODED_BYTES) != sum(run.ds[vid].dat_bytes for vid in run.vids)
            or any(n for k, n in by_backend.items() if k not in device)
            or not any(n for k, n in by_backend.items() if k.startswith(DISPATCHES) and k in device)
            or (runs and sum(runs) != len(run.vids))):
        run.off_chip += 1


def _timed_sweep(run, width: int) -> float | None:
    """-> the command's wall seconds, or None where it failed."""
    before = scrape(run.srv.vs_url)
    run.attempted += 1
    try:
        wall = common.timed_op(run, _sweep, {"width": width})
    except common.BenchError as e:
        print(f"benchmark: timed ec.encode failed: {e}", flush=True)
        run.failed += 1
        return None
    run.marks = (before, scrape(run.srv.vs_url))
    seconds = {m: round(many._rose(run, many.RPC_S.format(m)), 4) for m in RPCS}
    run.rpcs_per_command = {m: int(many._rose(run, many.RPC_N.format(m))) for m in RPCS}
    for name, methods in (("sweep_encode_rpc", ENCODE_RPCS), ("sweep_cutover_rpc", CUTOVER_RPCS)):
        run.facts["samples"].setdefault(name, []).append(
            sum(many._rose(run, many.RPC_S.format(m)) for m in methods))
    common.say(command=len(run.timed), wall=round(wall, 4), rpc_seconds=seconds, rpcs=run.rpcs_per_command,
               output=run.last_out.strip().splitlines()[1:-1])
    return wall


def setup(run) -> None:
    run.vids = [int(v) for v in run.traffic["volume_ids"]]
    common.require(len(run.vids) == int(run.config["volumes"]), "traffic and configuration disagree on the volumes")
    with run.phase("volume"):
        # one process per volume: each is seeded from --seed and its volume id
        jobs = [(run.data_dir, vid, run.seed * 1000 + vid, run.dataset) for vid in run.vids]
        with concurrent.futures.ProcessPoolExecutor(
                len(jobs), mp_context=multiprocessing.get_context("spawn")) as pool:
            run.ds = dict(zip(run.vids, pool.map(many._build, jobs)))
    run.orig_dat, run.orig_idx = {}, {}
    for vid in run.vids:
        run.orig_dat[vid] = os.path.join(run.work, f"orig{vid}.dat")
        run.orig_idx[vid] = os.path.join(run.work, f"orig{vid}.idx")
        os.link(_base(run, vid) + ".dat", run.orig_dat[vid])
        os.link(_base(run, vid) + ".idx", run.orig_idx[vid])
    with run.phase("dat_sha"), concurrent.futures.ThreadPoolExecutor(len(run.vids)) as pool:
        run.dat_sha = dict(zip(run.vids, pool.map(checks.file_sha, [run.orig_dat[v] for v in run.vids])))
    with run.phase("boot"):
        run.boot(run.vids[0])
        for vid in run.vids[1:]:
            run.srv.wait_volume(vid)
    common.settle_disk()
    if run.trace:
        common.shell_noop_ms(run)
    run.checkpoint = os.path.join(run.work, "ec_encode.checkpoint")
    run.off_chip = 0
    run.rpcs_per_command = None
    with run.phase("warm_sweep"):
        _sweep(run)
        common.say(phase="warm_command", output=run.last_out.strip().splitlines())
    with run.phase("warm_restore"):
        _shard_crcs(run)
        _restore(run)
        # a mount warms its small-read shapes on a thread of its own, which may
        # still be compiling when the warm command has answered: let it finish
        seen, t0 = None, time.monotonic()
        while (now := scrape(run.srv.vs_url).get(many.COMPILED)) != seen and time.monotonic() - t0 < 60:
            seen = now
            time.sleep(0.5)


def window(run) -> None:
    run.timed = []
    run.cycle_crcs = []
    period = float(run.traffic["period_s"])
    block = int(run.config["code"]["small_block_bytes"])
    width = sum(-(-run.ds[vid].dat_bytes // (checks.DATA * block)) * block for vid in run.vids)
    compiled_before = scrape(run.srv.vs_url).get(many.COMPILED)
    t0 = time.monotonic()
    while True:
        wall = _timed_sweep(run, width)
        if wall is None:
            break
        run.timed.append(wall)
        if run.fault == "flip_first_encode" and len(run.timed) == 1:
            common.flip_byte(checks.shard_path(_base(run, run.vids[-1]), 3), run.seed)
        run.cycle_crcs.append(_shard_crcs(run))
        _look(run)
        if time.monotonic() >= t0 + run.seconds:
            break
        _restore(run)
        time.sleep(max(0.0, t0 + len(run.timed) * period - time.monotonic()))
    common.bulk_rate(run, "encode", sum(run.ds[vid].dat_bytes for vid in run.vids))
    batches = BATCHES_RE.findall(getattr(run, "last_out", ""))
    compiled_after = scrape(run.srv.vs_url).get(many.COMPILED)
    run.facts["timed"].update(
        volumes=len(run.vids),
        batches=sum(int(b) for b in batches) if batches else None,
        rpcs_per_command=run.rpcs_per_command,
        programs_compiled_in_window=(
            None if compiled_before is None or compiled_after is None else int(compiled_after - compiled_before)),
    )


def verify(run) -> None:
    if run.fault == "flip_shard_byte":
        common.flip_byte(checks.shard_path(_base(run, run.vids[0]), 11), run.seed)
    # every sweep of the window wrote the files the last one wrote, which the lines below hold to the reference
    crcs = run.cycle_crcs or [[None]]
    run.check("encodes_differing", sum(1 for c in crcs if c != crcs[-1] or None in c), 0)
    run.check("encodes_off_the_chip", run.off_chip, 0)
    listed = many._listed(run)
    run.check("shards_not_listed",
              sum(1 for vid in run.vids for s in range(SHARDS) if run.srv.vs_url not in listed[vid].get(s, ())), 0)
    run.check("dat_files_left", sum(1 for vid in run.vids if os.path.exists(_base(run, vid) + ".dat")), 0)
    for vid in run.vids:
        got = checks.check_shards(_base(run, vid), run.orig_dat[vid], run.seed,
                                  int(run.traffic["parity_rows_checked"]))
        for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
            run.check(f"v{vid}.{name}", got[name], 0)
        run.check(f"v{vid}.final_gets_wrong", many._final_gets(run, vid, int(run.traffic["final_gets"])), 0)
    # the last sweep's data shards decode back to the volumes, too
    run.srv.shell(common.LOCK.format("; ".join(f"ec.decode -volumeId {vid}" for vid in run.vids)))
    for vid in run.vids:
        dat = _base(run, vid) + ".dat"
        same = os.path.exists(dat) and checks.file_sha(dat) == run.dat_sha[vid]
        run.check(f"v{vid}.final_dat_differing", 0 if same else 1, 0)
