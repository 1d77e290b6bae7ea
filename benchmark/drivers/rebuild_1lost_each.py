"""Traffic `rebuild_1lost_each`: a server or a disk of a wide cluster dies, and
every EC volume it had a part in misses ONE shard, a different id from volume
to volume; the operator's one `ec.rebuild` brings them all back.

Set-up builds the configuration's volumes from the seed (one data set per
volume id) in the chip-owning server's directory, boots it, encodes them all
through the shell (all 14 shards of each stay here, as in `warm10p4`), keeps
the sha256 of the shard each volume will lose (`lost_shard_of_volume`), and
runs one whole cycle to warm every shape. Window: repeat {untimed
`VolumeEcShardsDelete` of each volume's lost shard, wait until the master's
topology has lost them all; timed `shell -c "lock; ec.rebuild; unlock"`,
flagless, one command for all the volumes; untimed, every rebuilt shard
compared by sha256 and the server's counters asked where the decodes ran}, a
closed loop of one, until `--seconds` have passed; an operation that has
started is finished. Rate = bytes of lost shard restored (all volumes') over
the seconds of the timed commands alone, all of them (`common.bulk_rate`).

`correct` holds the deployment's guarantees, never which RPC did the work: a
program that rebuilds volume by volume is as correct here as one that batches.
What the program's counters say of HOW it did it goes on the result line as
facts, inside `"timed"` (the one place of the line a driver fills): `volumes`,
`signature_groups` (from the command's own output), `rpcs_per_command` (by
method, on the chip server, the last timed command's), and
`programs_compiled_in_window` (the codec's compile counter over the window);
a fact the program has no counter or line for is null."""

from __future__ import annotations

import concurrent.futures
import http.client
import multiprocessing
import os
import random
import re
import time

from drivers import common
from harness import checks, volumes
from harness.peers import scrape
from harness.server import DEVICE_BACKENDS, http_json

FAULTS = ("flip_shard_byte", "broken_apply")

TOTAL = checks.DATA + checks.PARITY
RPC_S = 'weedtpu_rpc_server_seconds_sum{{method="{}"}}'
RPC_N = 'weedtpu_rpc_server_seconds_count{{method="{}"}}'
REBUILD_RPCS = ("VolumeEcShardsRebuildBatch", "VolumeEcShardsRebuild")
RPCS = ("VolumeStatus", *REBUILD_RPCS, "VolumeEcShardsCopy", "VolumeEcShardsMount", "VolumeEcShardsDelete")
RUNS = "weedtpu_ec_rebuild_runs_total"
COMPILED = "weedtpu_codec_programs_compiled_total"
GROUPS_RE = re.compile(r"^ec\.rebuild batch on \S+: \d+ volumes in (\d+) signature groups", re.M)


def _base(run, vid: int) -> str:
    return os.path.join(run.data_dir, str(vid))


def _lost_path(run, vid: int) -> str:
    return checks.shard_path(_base(run, vid), run.lost[vid])


def _listed(run) -> dict[int, dict[int, list[str]]]:
    """The master's registry: volume -> shard -> urls of its holders."""
    topo = http_json(f"http://{run.srv.master_http}/dir/status")["Topology"]
    return {
        vid: {int(s): urls for s, urls in topo.get("ec_volumes", {}).get(str(vid), {}).items() if urls}
        for vid in run.vids
    }


def _lose(run) -> None:
    for vid in run.vids:
        run.srv.delete_shards(vid, [run.lost[vid]])
    left = [vid for vid in run.vids if os.path.exists(_lost_path(run, vid))]
    common.require(not left, f"the lost shards of volumes {left} survived VolumeEcShardsDelete")
    t0 = time.monotonic()
    while any(run.lost[vid] in shards for vid, shards in _listed(run).items()):
        common.require(time.monotonic() - t0 < 60, "the master never noticed the lost shards")
        time.sleep(0.05)
    common.settle_disk()


def _rebuild(run) -> None:
    run.last_out = run.srv.shell(common.LOCK.format("ec.rebuild"))


def _rose(run, key: str) -> float:
    """How far a counter of the chip server rose over the last command; one
    the program lacks rose by nothing."""
    before, after = run.marks
    return after.get(key, 0.0) - before.get(key, 0.0)


def _look(run) -> None:
    """What the last command left: shards that differ from the ones lost, and
    decodes the chip server's device backend did not count as its own."""
    for vid in run.vids:
        path = _lost_path(run, vid)
        if not os.path.exists(path) or checks.file_sha(path) != run.shard_sha[vid]:
            run.shards_differ += 1
    runs = {k for k in run.marks[1] if k.startswith(RUNS)}
    on_chip = sum(_rose(run, f'{RUNS}{{backend="{b}"}}') for b in DEVICE_BACKENDS)
    if on_chip != len(run.vids) or sum(_rose(run, k) for k in runs) != on_chip:
        run.off_chip += 1


def _rebuild_spans(run, since: float) -> list:
    """The rebuild RPCs of the last command as the chip server's trace ring
    holds them (a sample: a log line, never a metric): per RPC its method,
    seconds, the run span's attributes (`batch`, `signature_groups`, `ring`,
    `lanes`, `batches`) and, by span name, [count, milliseconds in all]."""
    out = []
    got = http_json(f"http://{run.srv.vs_url}/debug/traces?kind=rpc.server&limit=1000")
    for t in got.get("traces", []):
        method = t["root"].get("attrs", {}).get("method")
        if t["start"] < since or method not in REBUILD_RPCS:
            continue
        by_name: dict[str, list] = {}
        attrs = {}

        def walk(sp: dict) -> None:
            n = by_name.setdefault(sp["name"], [0, 0.0])
            n[0] += 1
            n[1] = round(n[1] + sp["dur_ms"], 3)
            if sp["name"] == "rebuild.run":
                attrs.update(sp.get("attrs") or {})
            for c in sp.get("spans", ()):
                walk(c)
        for sp in t["root"].get("spans", ()):
            walk(sp)
        out.append({"method": method, "s": round(t["duration_s"], 4), "run": attrs, "spans": by_name})
    return out


def _lose_and_rebuild(run, timed: bool) -> float | None:
    """-> the command's wall seconds, or None where it failed."""
    _lose(run)
    before, since = scrape(run.srv.vs_url), time.time()
    try:
        if timed:
            run.attempted += 1
            wall = common.timed_op(run, _rebuild, {"width": len(run.vids) * run.shard_bytes})
        else:
            wall = 0.0
            _rebuild(run)
    except common.BenchError as e:
        print(f"benchmark: the {'timed' if timed else 'warm'} ec.rebuild failed: {e}", flush=True)
        if timed:
            run.failed += 1
        else:
            run.shards_differ += len(run.vids)
        return None
    run.marks = (before, scrape(run.srv.vs_url))
    if timed:
        seconds = {m: round(_rose(run, RPC_S.format(m)), 4) for m in RPCS}
        run.rpcs_per_command = {m: int(_rose(run, RPC_N.format(m))) for m in RPCS}
        run.facts["samples"].setdefault("rebuild_rpc", []).append(
            sum(_rose(run, RPC_S.format(m)) for m in REBUILD_RPCS))
        common.say(command=len(run.timed), wall=round(wall, 4), rpc_seconds=seconds,
                   rpcs=run.rpcs_per_command, output=run.last_out.strip().splitlines()[1:-1],
                   rebuilds=_rebuild_spans(run, since))
    return wall


def _build(args) -> volumes.Dataset:
    return volumes.build(*args)


def setup(run) -> None:
    run.vids = [int(v) for v in run.traffic["volume_ids"]]
    common.require(len(run.vids) == int(run.config["volumes"]), "traffic and configuration disagree on the volumes")
    run.lost = {int(v): int(s) for v, s in run.config["lost_shard_of_volume"].items()}
    common.require(sorted(run.lost) == run.vids, "the configuration names no lost shard for some volume")
    with run.phase("volume"):
        # one process per volume: each is seeded from --seed and its volume id
        jobs = [(run.data_dir, vid, run.seed * 1000 + vid, run.dataset) for vid in run.vids]
        with concurrent.futures.ProcessPoolExecutor(
                len(jobs), mp_context=multiprocessing.get_context("spawn")) as pool:
            run.ds = dict(zip(run.vids, pool.map(_build, jobs)))
    run.orig_dat = {}
    for vid in run.vids:
        run.orig_dat[vid] = os.path.join(run.work, f"orig{vid}.dat")
        os.link(_base(run, vid) + ".dat", run.orig_dat[vid])
    with run.phase("boot"):
        run.boot(run.vids[0])
        for vid in run.vids[1:]:
            run.srv.wait_volume(vid)
    with run.phase("encode"):
        run.srv.shell(common.LOCK.format("; ".join(f"ec.encode -volumeId {v} -force" for v in run.vids)))
    with run.phase("shard_sha"):
        run.shard_sha = {vid: checks.file_sha(_lost_path(run, vid)) for vid in run.vids}
        sizes = {os.path.getsize(_lost_path(run, vid)) for vid in run.vids}
        common.require(len(sizes) == 1, f"the volumes' shards differ in size: {sorted(sizes)}")
        run.shard_bytes = sizes.pop()
        common.say(phase="lost", lost=run.lost, shard_bytes=run.shard_bytes)
    run.shards_differ = run.off_chip = 0
    run.rpcs_per_command = None
    with run.phase("warm_cycle"):
        if _lose_and_rebuild(run, timed=False) is not None:
            common.say(phase="warm_command", output=run.last_out.strip().splitlines())
            _look(run)


def window(run) -> None:
    run.timed = []
    compiled_before = scrape(run.srv.vs_url).get(COMPILED)
    t_end = time.monotonic() + run.seconds
    while True:
        wall = _lose_and_rebuild(run, timed=True)
        if wall is None:
            break
        run.timed.append(wall)
        last = time.monotonic() >= t_end
        if last and run.fault == "flip_shard_byte":
            common.flip_byte(_lost_path(run, run.vids[0]), run.seed)
        _look(run)
        if last:
            break
    common.bulk_rate(run, "rebuild", len(run.vids) * run.shard_bytes)
    groups = GROUPS_RE.findall(getattr(run, "last_out", ""))
    compiled_after = scrape(run.srv.vs_url).get(COMPILED)
    run.facts["timed"].update(
        volumes=len(run.vids),
        signature_groups=int(groups[0]) if len(groups) == 1 else None,
        rpcs_per_command=run.rpcs_per_command,
        programs_compiled_in_window=(
            None if compiled_before is None or compiled_after is None else int(compiled_after - compiled_before)),
    )


def _final_gets(run, vid: int, n: int) -> int:
    """GET `n` seeded needles of one volume; -> how many came back with
    another status than 200, other bytes than the seed gives, or reconstructed."""
    ds = run.ds[vid]
    rng = random.Random((run.seed ^ 0x6E7) + vid)
    host, port = run.srv.vs_url.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=120)
    wrong = 0
    try:
        for i in rng.sample(range(len(ds.keys)), min(n, len(ds.keys))):
            conn.request("GET", "/" + ds.fid(i))
            resp = conn.getresponse()
            body = resp.read()
            if (resp.status != 200 or body != ds.payload(i)
                    or resp.getheader("X-Weedtpu-Read-Class", "") != "ec_intact"):
                wrong += 1
    finally:
        conn.close()
    return wrong


def verify(run) -> None:
    run.check("rebuilt_shards_differing", run.shards_differ, 0)
    run.check("rebuilds_off_the_chip", run.off_chip, 0)
    listed = _listed(run)
    run.check("shards_not_listed",
              sum(1 for vid in run.vids for s in range(TOTAL) if run.srv.vs_url not in listed[vid].get(s, ())), 0)
    for vid in run.vids:
        got = checks.check_shards(_base(run, vid), run.orig_dat[vid], run.seed,
                                  int(run.traffic["parity_rows_checked"]))
        for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
            run.check(f"v{vid}.{name}", got[name], 0)
        run.check(f"v{vid}.final_gets_wrong", _final_gets(run, vid, int(run.traffic["final_gets"])), 0)
