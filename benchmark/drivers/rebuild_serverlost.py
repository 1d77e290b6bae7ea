"""Traffic `rebuild_serverlost`: a cluster of four volume servers, each a rack
of its own, holds the 28 shards of two EC volumes as `ec.encode` spread them;
one server is lost and the operator's `ec.rebuild` brings its shards back.

Set-up builds both volumes from the seed in the chip-owning server's
directory, boots it, joins three CPU-only peers (`harness/peers.py`), runs
`ec.encode` of both volumes (generate on the chip server, spread, cut-over),
keeps the sha256 of the shards that the server in the configuration's
`lost_rack` holds, and runs one whole cycle to warm every shape. It does not
check the 28 shards one by one: `verify` checks the 14 of each volume that serve
at the end, and every shard a cycle lost is compared with the one rebuilt.

One cycle: untimed, stop that peer by SIGTERM and wait until the master lists
none of its shards; timed, `shell -c "lock; ec.rebuild; unlock"`, one command
for both volumes, no flags; untimed, each rebuilt shard compared by sha256 with
the stopped server's own file, the command's output and the servers' counters
asked where the decode ran, the rebuilder's directory searched for survivor
copies left behind; then the restore: the rebuilt shards deleted where they
were put, the peer restarted over its untouched directory, the master listing
its shards again. The last cycle of the window is not restored: `verify` finds
the cluster as `ec.rebuild` left it, all 14 shards of both volumes on the three
live servers. Rate = bytes of lost shard restored (both volumes') over the seconds of
the timed commands alone, all of them (`common.bulk_rate`)."""

from __future__ import annotations

import concurrent.futures
import glob
import http.client
import multiprocessing
import os
import random
import re
import time

from drivers import common
from harness import checks, volumes
from harness.peers import Peers, scrape
from harness.server import DEVICE_BACKENDS, http_json

FAULTS = ("flip_shard_byte", "broken_apply")

TOTAL = checks.DATA + checks.PARITY
REBUILT_RE = re.compile(r"^ec\.rebuild volume (\d+): rebuilt \[([0-9, ]*)\] on (\S+)", re.M)
GATHER_BYTES = 'weedtpu_ec_copy_bytes_total{side="pulled"}'
RPC_S = 'weedtpu_rpc_server_seconds_sum{{method="{}"}}'
RPCS = ("VolumeEcShardsCopy", "VolumeEcShardFileCopy", "VolumeEcShardsRebuild",
        "VolumeEcShardsDelete", "VolumeEcShardsMount")
RUNS = "weedtpu_ec_rebuild_runs_total"


def _listed(run) -> dict[int, dict[int, list[str]]]:
    """The master's registry: volume -> shard -> urls of its holders."""
    topo = http_json(f"http://{run.srv.master_http}/dir/status")["Topology"]
    return {
        vid: {int(s): urls for s, urls in topo.get("ec_volumes", {}).get(str(vid), {}).items() if urls}
        for vid in run.vids
    }


def _wait(cond, what: str, timeout: float = 120.0) -> None:
    t0 = time.monotonic()
    while not cond():
        common.require(time.monotonic() - t0 < timeout, f"timed out waiting until {what}")
        time.sleep(0.05)


def _shards_in(directory: str, vid: int) -> set[int]:
    return {int(p[-2:]) for p in glob.glob(os.path.join(directory, f"{vid}.ec[0-9][0-9]"))}


def _lose(run) -> None:
    run.lost_peer.stop()
    _wait(lambda: not any(run.lost_peer.url in urls for m in _listed(run).values() for urls in m.values()),
          "the master lists none of the stopped server's shards")
    common.settle_disk()


def _live(run) -> dict:
    """url -> the chip server and every peer that runs."""
    return {run.srv.vs_url: run.srv, **{p.url: p for p in run.peers if p.alive()}}


def _rebuild(run) -> None:
    run.last_out = run.srv.shell(common.LOCK.format("ec.rebuild"))


def _restore(run) -> None:
    """Back to the spread: what a live server holds beyond its own shards goes
    (the rebuilt ones, wherever the command put them; what a failed command
    left), the stopped peer comes back over its untouched directory."""
    for url, server in _live(run).items():
        for vid in run.vids:
            extra = sorted(_shards_in(run.dirs[url], vid) - run.held[vid][url])
            if extra:
                server.delete_shards(vid, extra)
    run.lost_peer.start()
    run.lost_peer.wait_ready()
    _wait(lambda: all(run.lost_peer.url in _listed(run)[vid].get(s, ()) for vid in run.vids for s in run.lost[vid]),
          "the master lists the restarted server's shards again")


def _scrape_all(run) -> dict[str, dict[str, float]]:
    """`/metrics` of every live server, the chip server under "chip", a peer
    under its rack."""
    out = {"chip": scrape(run.srv.vs_url)}
    out.update({p.rack: scrape(p.url) for p in run.peers if p.alive()})
    return out


def _delta(run, key: str, servers=None) -> float:
    """How far a counter rose over the last command, summed over `servers`
    (all that were live); a counter the program lacks rose by nothing."""
    before, after = run.marks
    return sum(m.get(key, 0.0) - before.get(name, {}).get(key, 0.0)
               for name, m in after.items() if servers is None or name in servers)


def _rpc_timeline(run, since: float) -> list:
    """The RPCs of the last command, as the servers' trace rings hold them (the
    shell's trace id rides on each, heartbeats carry none): [seconds after
    `since` on the wall clock, seconds inside, server, method], in order. What
    a slow command waited for shows here, between or inside them."""
    out = []
    for name, server in [("chip", run.srv.vs_url)] + [(p.rack, p.url) for p in run.peers if p.alive()]:
        for t in http_json(f"http://{server}/debug/traces?kind=rpc.server&limit=1000").get("traces", []):
            if t["start"] >= since:
                out.append([round(t["start"] - since, 3), round(t["duration_s"], 3), name,
                            t["root"].get("attrs", {}).get("method")])
    return sorted(out)


def _look(run) -> None:
    """What the last command left: shards that differ, rebuilds that ran off
    the chip, survivor copies left on the rebuilder."""
    named = {int(v): (sorted(int(s) for s in ids.split(",") if s.strip()), url)
             for v, ids, url in REBUILT_RE.findall(run.last_out)}
    for vid in run.vids:
        ids, url = named.get(vid, ([], ""))
        if url != run.srv.vs_url or ids != sorted(run.lost[vid]):
            run.off_chip += 1
        for s in run.lost[vid]:
            path = checks.shard_path(os.path.join(run.data_dir, str(vid)), s)
            if not os.path.exists(path) or checks.file_sha(path) != run.shard_sha[vid][s]:
                run.shards_differ += 1
        run.copies_left += len(_shards_in(run.data_dir, vid) - run.own[vid] - run.lost[vid])
    run.copies_left += len(glob.glob(os.path.join(run.data_dir, "*.cpy")))
    # the counters' account of the same: every volume's decode counted by the
    # chip server's device backend, none by another backend or server
    runs = {k for m in run.marks[1].values() for k in m if k.startswith(RUNS)}
    on_chip = sum(_delta(run, f'{RUNS}{{backend="{b}"}}', ("chip",)) for b in DEVICE_BACKENDS)
    if on_chip != len(run.vids) or sum(_delta(run, k) for k in runs) != on_chip:
        run.off_chip += 1


def _lose_and_rebuild(run, timed: bool) -> float | None:
    """-> the command's wall seconds, or None where it failed."""
    _lose(run)
    before, since = _scrape_all(run), time.time()
    try:
        if timed:
            run.attempted += 1
            wall = common.timed_op(run, _rebuild, {})
        else:
            wall = 0.0
            _rebuild(run)
    except common.BenchError as e:
        print(f"benchmark: the {'timed' if timed else 'warm'} ec.rebuild failed: {e}", flush=True)
        if timed:
            run.failed += 1
        else:
            run.shards_differ += sum(len(s) for s in run.lost.values())
        return None
    run.marks = (before, _scrape_all(run))
    if timed:
        # seconds inside the RPCs of the command, by server: thread time where
        # calls run side by side (the two pulls of a volume's gather do)
        by_server = {name: {m: round(_delta(run, RPC_S.format(m), (name,)), 4) for m in RPCS}
                     for name in run.marks[1]}
        run.facts["samples"].setdefault("gather", []).append(_delta(run, RPC_S.format("VolumeEcShardsCopy")))
        run.facts["samples"].setdefault("rebuild_rpc", []).append(_delta(run, RPC_S.format("VolumeEcShardsRebuild")))
        common.say(command=len(run.timed), wall=round(wall, 4), rpc_seconds=by_server,
                   gather_bytes=_delta(run, GATHER_BYTES), output=run.last_out.strip().splitlines()[1:-1],
                   rpcs=_rpc_timeline(run, since))
    return wall


def _build(args) -> volumes.Dataset:
    return volumes.build(*args)


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
            run.ds = dict(zip(run.vids, pool.map(_build, jobs)))
    run.orig_dat = {}
    for vid in run.vids:
        run.orig_dat[vid] = os.path.join(run.work, f"orig{vid}.dat")
        os.link(os.path.join(run.data_dir, f"{vid}.dat"), run.orig_dat[vid])
    with run.phase("boot"):
        run.boot(run.vids[0])
        for vid in run.vids[1:]:
            run.srv.wait_volume(vid)
    with run.phase("peers"):
        run.peers = Peers(cluster["peer_racks"], run.work, run.out_dir, run.srv.master)
        run.peers.start()
        run.lost_peer = run.peers.by_rack(cluster["lost_rack"])
        run.dirs = dirs = {run.srv.vs_url: run.data_dir, **{p.url: p.data_dir for p in run.peers}}

        def joined() -> bool:
            topo = http_json(f"http://{run.srv.master_http}/dir/status")["Topology"]
            urls = {n["url"] for racks in topo["data_centers"].values() for ns in racks.values() for n in ns}
            return urls == set(dirs)
        _wait(joined, "the master lists all four servers")
    with run.phase("encode"):
        run.srv.shell(common.LOCK.format("; ".join(f"ec.encode -volumeId {v} -force" for v in run.vids)))
    with run.phase("shard_sha"):
        listed = _listed(run)
        run.held, run.own, run.lost, run.shard_sha = {}, {}, {}, {}
        for vid in run.vids:
            held = {url: {s for s, urls in listed[vid].items() if url in urls} for url in dirs}
            common.require(sorted(s for ss in held.values() for s in ss) == list(range(TOTAL)),
                           f"volume {vid}: the spread is not one holder per shard: {held}")
            for url, ss in held.items():
                common.require(ss == _shards_in(dirs[url], vid) and 1 <= len(ss) <= checks.PARITY,
                               f"volume {vid}: {url} lists {sorted(ss)}, holds {sorted(_shards_in(dirs[url], vid))}")
            run.held[vid] = held
            run.own[vid] = held[run.srv.vs_url]
            run.lost[vid] = held[run.lost_peer.url]
            run.shard_sha[vid] = {
                s: checks.file_sha(checks.shard_path(os.path.join(run.lost_peer.data_dir, str(vid)), s))
                for s in run.lost[vid]}
        run.shard_bytes = os.path.getsize(checks.shard_path(os.path.join(run.data_dir, str(run.vids[0])),
                                                            min(run.own[run.vids[0]])))
        common.say(phase="spread", lost={v: sorted(s) for v, s in run.lost.items()},
                   rebuilder_holds={v: sorted(s) for v, s in run.own.items()}, shard_bytes=run.shard_bytes)
    run.shards_differ = run.off_chip = run.copies_left = 0
    with run.phase("warm_cycle"):
        if _lose_and_rebuild(run, timed=False) is not None:
            common.say(phase="warm_command", output=run.last_out.strip().splitlines())
            _look(run)
        _restore(run)


def window(run) -> None:
    run.timed = []
    t_end = time.monotonic() + run.seconds
    while True:
        wall = _lose_and_rebuild(run, timed=True)
        if wall is None:
            break
        run.timed.append(wall)
        last = time.monotonic() >= t_end
        if last and run.fault == "flip_shard_byte":
            vid = run.vids[0]
            common.flip_byte(checks.shard_path(os.path.join(run.data_dir, str(vid)), min(run.lost[vid])), run.seed)
        _look(run)
        if last:
            break
        _restore(run)
    lost_bytes = sum(len(s) for s in run.lost.values()) * run.shard_bytes
    common.bulk_rate(run, "rebuild", lost_bytes)


def _link_live_shards(run, vid: int, listed: dict[int, list[str]]) -> str:
    """A directory of hard links to the volume's shard files where the master
    lists them, and to the rebuilder's index files: what `check_shards` reads."""
    d = os.path.join(run.work, f"live{vid}")
    os.makedirs(d)
    for s, urls in listed.items():
        src = checks.shard_path(os.path.join(run.dirs[urls[0]], str(vid)), s)
        if os.path.exists(src):
            os.link(src, checks.shard_path(os.path.join(d, str(vid)), s))
    for ext in (".ecx", ".eci"):
        src = os.path.join(run.data_dir, str(vid) + ext)
        if os.path.exists(src):
            os.link(src, os.path.join(d, str(vid) + ext))
    return os.path.join(d, str(vid))


def _final_gets(run, n: int) -> int:
    """GET seeded needles of every volume where the master's lookup sends them;
    -> how many came back with another status than 200, other bytes than the
    seed gives, or reconstructed."""
    rng = random.Random(run.seed ^ 0x6E7)
    conns: dict[str, http.client.HTTPConnection] = {}
    wrong = 0
    try:
        for k in range(n):
            vid = run.vids[k % len(run.vids)]
            ds = run.ds[vid]
            i = rng.randrange(len(ds.keys))
            found = http_json(f"http://{run.srv.master_http}/dir/lookup?volumeId={vid}")
            urls = [loc["url"] for loc in found.get("locations", [])]
            if not urls:
                wrong += 1
                continue
            url = rng.choice(sorted(urls))
            if url not in conns:
                host, port = url.split(":")
                conns[url] = http.client.HTTPConnection(host, int(port), timeout=120)
            try:
                conns[url].request("GET", "/" + ds.fid(i))
                resp = conns[url].getresponse()
                body = resp.read()
            except OSError:  # the lookup named a server that is not there
                conns.pop(url).close()
                wrong += 1
                continue
            if (resp.status != 200 or body != ds.payload(i)
                    or resp.getheader("X-Weedtpu-Read-Class", "") != "ec_intact"):
                wrong += 1
    finally:
        for c in conns.values():
            c.close()
    return wrong


def verify(run) -> None:
    run.check("rebuilt_shards_differing", run.shards_differ, 0)
    run.check("rebuilds_off_the_chip", run.off_chip, 0)
    run.check("survivor_copies_left", run.copies_left, 0)
    listed = _listed(run)
    live = set(_live(run))
    run.check("shards_not_listed_on_a_live_server",
              sum(1 for vid in run.vids for s in range(TOTAL)
                  if not any(u in live for u in listed[vid].get(s, ()))), 0)
    for vid in run.vids:
        on_live = {s: [u for u in urls if u in live] for s, urls in listed[vid].items()}
        base = _link_live_shards(run, vid, {s: u for s, u in on_live.items() if u})
        got = checks.check_shards(base, run.orig_dat[vid], run.seed, int(run.traffic["parity_rows_checked"]))
        for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
            run.check(f"v{vid}.{name}", got[name], 0)
    run.check("final_gets_wrong", _final_gets(run, int(run.traffic["final_gets"])), 0)
    run.peers.stop_all()
