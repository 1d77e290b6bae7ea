"""Traffic `encode_sweep_spread`: the maintenance script's pass as a cluster
runs it. ONE source server holds every volume that filled up; `ec.encode`
without `-volumeId` encodes them there in one batch and spreads each volume's
14 shards over four servers in four racks, again and again, at a pace the
cell fixes.

Set-up is `encode_sweep`'s (volumes from the seed, hard links to each `.dat`
and `.idx`, the chip-owning server over them) plus `rebuild_serverlost`'s three
CPU-only peers (`harness/peers.py`), and one whole cycle to warm every shape
and every peer's copy path. Window: every `period_s` seconds (or as soon after
as the restore is done) ONE timed `shell -c "lock; ec.encode -force
-checkpoint <file>; unlock"`; then, untimed, the CRC32 of every shard file on
every server is kept, every server's counters are asked what the command did,
and the cluster is put back WITHOUT writing a volume again: every shard deleted
on all four servers (`VolumeEcShardsDelete`), the original `.dat` and `.idx`
linked back on the source, `VolumeMount`, wait until the master lists four
normal volumes and no EC shard, `settle_disk`. The window ends at the later of
`--seconds` and the `min_commands`-th timed command (a rate over twelve
commands is steadier than one over the six that 20 s hold), on a sweep, whose
shards are then checked where the spread put them; every earlier sweep has to
have written the same CRC32 under every shard id, and `ec.decode` has to give
every `.dat` back from the last one's. Rate = bytes of sealed volume (all of
them) over the seconds of the timed commands alone, all of them
(`common.bulk_rate`).

`correct` holds the deployment's guarantees, never which RPC did the work nor
which server got which shard: the placement's reference is the guarantee
itself (no server over 4 shards of a volume, every shard on exactly one
server and listed there), read from the files on disk and the master's
listing, independent of `placement.plan_spread`. What the program's counters
say of HOW it did it goes on the result line as facts, inside `"timed"`:
`volumes`, `batches` (device dispatches, from the command's own output),
`rpcs_per_command` (by method, over all four servers, the last timed
command's), `copied_bytes_per_command` (what the three peers pulled),
`shards_per_server` (the last sweep's, from the files, sorted) and
`programs_compiled_in_window`."""

from __future__ import annotations

import collections
import concurrent.futures
import glob
import multiprocessing
import os
import shutil
import time

from drivers import common
from drivers import encode_cycle as cycle
from drivers import encode_sweep as sweep
from drivers import rebuild_1lost_each as many
from drivers import rebuild_serverlost as serverlost
from harness import checks
from harness.peers import Peers, scrape
from harness.server import http_json

FAULTS = ("flip_peer_shard_byte", "plant_fifth_shard", "flip_first_encode", "broken_apply")

SHARDS = sweep.SHARDS
COPY_RPCS = ("VolumeEcShardsCopy",)
HANDOVER_RPCS = ("VolumeMarkReadonly", "VolumeMarkWritable", "VolumeEcShardsMount", "VolumeEcShardsDelete",
                 "VolumeDelete")
RPCS = sweep.ENCODE_RPCS + COPY_RPCS + HANDOVER_RPCS
PULLED = 'weedtpu_ec_copy_bytes_total{side="pulled"}'


def _on_disk(run) -> dict[int, dict[int, list[str]]]:
    """volume -> shard id -> the names of the servers in whose directory its file lies."""
    out: dict[int, dict[int, list[str]]] = {vid: {} for vid in run.vids}
    for name, directory in run.dirs.items():
        for vid in run.vids:
            for p in glob.glob(os.path.join(directory, f"{vid}.ec[0-9][0-9]")):
                out[vid].setdefault(int(p[-2:]), []).append(name)
    return out


def _per_server(held: dict[int, list[str]]) -> collections.Counter:
    """server name -> how many of one volume's shard files lie in its directory."""
    return collections.Counter(name for names in held.values() for name in names)


def _shard_file(run, name: str, vid: int, shard: int) -> str:
    return checks.shard_path(os.path.join(run.dirs[name], str(vid)), shard)


def _shard_crcs(run) -> list:
    """CRC32 under every (volume, shard id), wherever the file lies; None for
    a shard that lies on no server or on more than one."""
    held = _on_disk(run)
    paths = [_shard_file(run, held[vid][s][0], vid, s) if len(held[vid].get(s, ())) == 1 else ""
             for vid in run.vids for s in range(SHARDS)]
    with concurrent.futures.ThreadPoolExecutor(SHARDS) as pool:
        return list(pool.map(cycle._file_crc, paths))


def _restore(run) -> None:
    """Untimed: four normal volumes on the source, no shard anywhere, and not a
    byte of a volume written."""
    for vid in run.vids:
        for server in (run.srv, *run.peers):
            server.delete_shards(vid, list(range(SHARDS)))
        os.link(run.orig_dat[vid], sweep._base(run, vid) + ".dat")
        os.link(run.orig_idx[vid], sweep._base(run, vid) + ".idx")
        run.srv.mount_volume(vid)
    for vid in run.vids:
        run.srv.wait_volume(vid)
    serverlost._wait(lambda: not any(many._listed(run).values()), "the master lists no EC shard")
    common.require(not any(_on_disk(run).values()), "shard files survived VolumeEcShardsDelete")
    common.settle_disk()


def _rose(run, key: str, names=None) -> float:
    """How far a counter rose over the last command, summed over the servers
    named (all four); one the program lacks rose by nothing."""
    return sum(after.get(key, 0.0) - before.get(key, 0.0)
               for name, (before, after) in run.marks_all.items() if names is None or name in names)


def _command_span(run) -> dict:
    """What the last `ec.encode` said of itself on its `shell.command` span, as
    the master's ring keeps the child's trace (a log line's worth, never a
    metric): `rpcs`, `overlapped`, `ckpt_writes` and, where the program has
    them, `copies` and `spread`. {} where there is no such trace."""
    got = http_json(f"http://{run.srv.master_http}/debug/traces?kind=shell.script&limit=1")
    for t in got.get("traces", []):
        for sp in t["root"].get("spans", ()):
            if sp["name"] == "shell.command" and (sp.get("attrs") or {}).get("command") == "ec.encode":
                return {k: v for k, v in sp["attrs"].items() if k not in ("command", "modules")}
    return {}


def _timed_sweep(run, width: int) -> float | None:
    """-> the command's wall seconds, or None where it failed."""
    before = {name: scrape(url) for name, url in run.urls.items()}
    run.attempted += 1
    try:
        wall = common.timed_op(run, sweep._sweep, {"width": width})
    except common.BenchError as e:
        print(f"benchmark: timed ec.encode failed: {e}", flush=True)
        run.failed += 1
        return None
    run.marks_all = {name: (before[name], scrape(url)) for name, url in run.urls.items()}
    run.marks = run.marks_all["chip"]  # what `encode_sweep._look` reads
    peers = [p.rack for p in run.peers]
    seconds = {name: {m: round(_rose(run, many.RPC_S.format(m), (name,)), 4) for m in RPCS}
               for name in run.marks_all}
    run.rpcs_per_command = {m: int(_rose(run, many.RPC_N.format(m))) for m in RPCS}
    run.copied_bytes = int(_rose(run, PULLED, peers))
    # the chip server's two classes as `encode_sweep` takes them (the source's side of
    # the cut-over: it serves no copy RPC), then the copies and the hand-over everywhere
    for name, methods, where in (("sweep_encode_rpc", sweep.ENCODE_RPCS, ("chip",)),
                                 ("sweep_cutover_rpc", sweep.CUTOVER_RPCS, ("chip",)),
                                 ("sweepspread_copy", COPY_RPCS, peers),
                                 ("sweepspread_handover", HANDOVER_RPCS, None)):
        run.facts["samples"].setdefault(name, []).append(
            sum(_rose(run, many.RPC_S.format(m), where) for m in methods))
    common.say(command=len(run.timed), wall=round(wall, 4), rpc_seconds=seconds, rpcs=run.rpcs_per_command,
               copied_bytes=run.copied_bytes, span=_command_span(run),
               output=run.last_out.strip().splitlines()[1:-1])
    return wall


def _look(run) -> None:
    """The chip server's account of the last command (`encode_sweep._look`:
    every volume's bytes encoded THERE, by its device backend alone), and the
    peers': none of them encoded a byte."""
    sweep._look(run)
    if _rose(run, sweep.ENCODED_BYTES, [p.rack for p in run.peers]):
        run.off_chip += 1


def setup(run) -> None:
    run.vids = [int(v) for v in run.traffic["volume_ids"]]
    common.require(len(run.vids) == int(run.config["volumes"]), "traffic and configuration disagree on the volumes")
    cluster = run.config["cluster"]
    free = os.statvfs(run.work)
    common.say(phase="disk", work=run.work, free_bytes=free.f_bavail * free.f_frsize)
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
        os.link(sweep._base(run, vid) + ".dat", run.orig_dat[vid])
        os.link(sweep._base(run, vid) + ".idx", run.orig_idx[vid])
    with run.phase("dat_sha"), concurrent.futures.ThreadPoolExecutor(len(run.vids)) as pool:
        run.dat_sha = dict(zip(run.vids, pool.map(checks.file_sha, [run.orig_dat[v] for v in run.vids])))
    with run.phase("boot"):
        run.boot(run.vids[0])
        for vid in run.vids[1:]:
            run.srv.wait_volume(vid)
    with run.phase("peers"):
        run.peers = Peers(cluster["peer_racks"], run.work, run.out_dir, run.srv.master)
        run.peers.start()
        run.dirs = {"chip": run.data_dir, **{p.rack: p.data_dir for p in run.peers}}
        run.urls = {"chip": run.srv.vs_url, **{p.rack: p.url for p in run.peers}}

        def joined() -> bool:
            topo = http_json(f"http://{run.srv.master_http}/dir/status")["Topology"]
            return set(run.urls.values()) == {
                n["url"] for racks in topo["data_centers"].values() for ns in racks.values() for n in ns}
        serverlost._wait(joined, "the master lists all four servers")
    common.require(len(run.dirs) == int(run.config["servers"]), "the cluster is not the configuration's")
    common.settle_disk()
    if run.trace:
        common.shell_noop_ms(run)
    run.checkpoint = os.path.join(run.work, "ec_encode.checkpoint")
    run.off_chip = 0
    run.rpcs_per_command = run.copied_bytes = None
    with run.phase("warm_sweep"):
        sweep._sweep(run)
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
    at_least = int(run.traffic["min_commands"])
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
            held = _on_disk(run)[run.vids[-1]]
            common.flip_byte(_shard_file(run, held[3][0], run.vids[-1], 3), run.seed)
        run.cycle_crcs.append(_shard_crcs(run))
        _look(run)
        # the window ends at the later of --seconds and the `min_commands`-th command
        if time.monotonic() >= t0 + run.seconds and len(run.timed) >= at_least:
            break
        _restore(run)
        time.sleep(max(0.0, t0 + len(run.timed) * period - time.monotonic()))
    common.bulk_rate(run, "encode", sum(run.ds[vid].dat_bytes for vid in run.vids))
    batches = sweep.BATCHES_RE.findall(getattr(run, "last_out", ""))
    compiled_after = scrape(run.srv.vs_url).get(many.COMPILED)
    on_each = sum((_per_server(held) for held in _on_disk(run).values()), collections.Counter())
    run.facts["timed"].update(
        volumes=len(run.vids),
        batches=sum(int(b) for b in batches) if batches else None,
        rpcs_per_command=run.rpcs_per_command,
        copied_bytes_per_command=run.copied_bytes,
        shards_per_server=sorted((on_each[name] for name in run.dirs), reverse=True),
        programs_compiled_in_window=(
            None if compiled_before is None or compiled_after is None else int(compiled_after - compiled_before)),
    )


def _link_shards(run, vid: int, held: dict[int, list[str]]) -> str:
    """A directory of hard links to the volume's shard files where they lie
    (the first of two where a shard lies twice), and to the source's index
    files: what `check_shards` reads."""
    d = os.path.join(run.work, f"live{vid}")
    os.makedirs(d)
    for s, names in held.items():
        os.link(_shard_file(run, names[0], vid, s), checks.shard_path(os.path.join(d, str(vid)), s))
    for ext in (".ecx", ".eci"):
        src = sweep._base(run, vid) + ext
        if os.path.exists(src):
            os.link(src, os.path.join(d, str(vid) + ext))
    return os.path.join(d, str(vid))


def _plant_fifth_shard(run) -> None:
    """The control of the placement check: a server that holds four shards of
    the first volume gets a copy of a fifth, which then lies on two servers."""
    vid = run.vids[0]
    held = _on_disk(run)[vid]
    full = min(name for name, n in _per_server(held).items() if n >= checks.PARITY)
    s = min(s for s, names in held.items() if full not in names)
    shutil.copy(_shard_file(run, held[s][0], vid, s), _shard_file(run, full, vid, s))


def verify(run) -> None:
    held = _on_disk(run)
    if run.fault == "flip_peer_shard_byte":
        vid = run.vids[0]
        s = min(s for s, names in held[vid].items() if names[0] != "chip")
        common.flip_byte(_shard_file(run, held[vid][s][0], vid, s), run.seed)
    if run.fault == "plant_fifth_shard":
        _plant_fifth_shard(run)
        held = _on_disk(run)
    # every sweep of the window wrote the files the last one wrote, which the lines below hold to the reference
    crcs = run.cycle_crcs or [[None]]
    run.check("encodes_differing", sum(1 for c in crcs if c != crcs[-1] or None in c), 0)
    run.check("encodes_off_the_chip", run.off_chip, 0)
    # the placement, from the files: every shard on exactly one server, no server over 4 of a volume
    run.check("shards_not_on_one_server",
              sum(1 for vid in run.vids for s in range(SHARDS) if len(held[vid].get(s, ())) != 1), 0)
    run.check("servers_over_4_of_a_volume",
              sum(1 for vid in run.vids for n in _per_server(held[vid]).values() if n > checks.PARITY), 0)
    # and the master lists each shard on the server that holds it, and nowhere else
    listed = many._listed(run)
    run.check("shards_not_listed",
              sum(1 for vid in run.vids for s in range(SHARDS)
                  if sorted(listed[vid].get(s, ())) != sorted(run.urls[n] for n in held[vid].get(s, ()))), 0)
    run.check("dat_files_left", sum(1 for vid in run.vids for d in run.dirs.values()
                                    if os.path.exists(os.path.join(d, f"{vid}.dat"))), 0)
    run.check("copies_left", sum(len(glob.glob(os.path.join(d, "*.cpy"))) for d in run.dirs.values()), 0)
    for vid in run.vids:
        got = checks.check_shards(_link_shards(run, vid, held[vid]), run.orig_dat[vid], run.seed,
                                  int(run.traffic["parity_rows_checked"]))
        for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
            run.check(f"v{vid}.{name}", got[name], 0)
    # seeded needles of every volume where the master's lookup sends them, none reconstructed
    run.check("final_gets_wrong", serverlost._final_gets(run, int(run.traffic["final_gets"]) * len(run.vids)), 0)
    # the last sweep's data shards decode back to the volumes, on whichever server ec.decode gathers them
    run.srv.shell(common.LOCK.format("; ".join(f"ec.decode -volumeId {vid}" for vid in run.vids)))
    for vid in run.vids:
        dats = [p for d in run.dirs.values() if os.path.exists(p := os.path.join(d, f"{vid}.dat"))]
        same = len(dats) == 1 and checks.file_sha(dats[0]) == run.dat_sha[vid]
        run.check(f"v{vid}.final_dat_differing", 0 if same else 1, 0)
    run.peers.stop_all()
