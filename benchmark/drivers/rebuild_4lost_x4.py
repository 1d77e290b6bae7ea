"""Traffic `rebuild_4lost_x4`: a rack of a four-rack spread goes, every EC
volume misses four of its 14 shards, and the rebuilder is a volume server that
owns all four chips of its host: its codec is the mesh backend, its batches
lie on the four devices, its decode is one program across them.

Set-up puts the configuration's `server_env` into this process's environment
(the server child inherits it: `WEEDTPU_BACKEND=mesh`, the operator's own
seam, nothing else), builds the configuration's volumes from the seed (one data
set per volume id) in the chip-owning server's directory, boots it, asks its
`/status` for what the configuration's `status` says it must report (off
rehearsal: a rehearsal runs the one-device backend on the CPU by the harness's
own line), encodes the volumes through the shell (all 14 shards of each stay
here; the set-up's encodes run the mesh's column-sharded apply and are checked
against the reference after the window, not timed), keeps the sha256 of the
shards that will be lost, and runs one whole loss and rebuild to warm every
shape. Window: repeat {untimed `VolumeEcShardsDelete` of the lost shards of
every volume, wait until the master's topology has lost them all, `os.sync()`;
timed ONE `shell -c "lock; ec.rebuild; unlock"`, flagless; untimed, every
rebuilt shard compared by sha256 and the server's counters asked where and on
how many devices the batches ran}, one command in flight, until `--seconds`
have passed AND at least `min_commands` were timed; an operation that has
started is finished. Rate = bytes of lost shard restored (all volumes': the
sum of the eight files' sizes, since two volumes of one size in MiB may differ
by a 1 MiB row) over the seconds of the timed commands alone, all of them
(`common.bulk_rate`).

A traced stretch's `known` width is the command's columns over the cell's
chips: what ONE chip has to move, since `program_roofline_pct` adds a least
time per chip.

`correct` holds the deployment's guarantees, among them that the decodes ran
on a mesh of four devices. What the program's counters say beyond that goes on
the result line as facts inside `"timed"`: `volumes`, `rpcs_per_command`,
`mesh_batches_per_command` (by `variant/devices`), `programs_compiled_in_window`
(the codec's own compile counter; the check `compiles_in_window` is the
harness's count from the server's log); a counter the program lacks
(the parent of PR 48 has no mesh counters) reads null or nothing, its sample
is absent and its per-layer metric is left out."""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
import re
import time

from drivers import common
from drivers import rebuild_1lost_each as many
from harness import checks
from harness.peers import scrape
from harness.server import DEVICE_BACKENDS, http_json

FAULTS = ("flip_shard_byte",)

MESH_S = 'weedtpu_ec_mesh_seconds_total{{stage="{}"}}'
MESH_BATCHES_RE = re.compile(r'^weedtpu_ec_mesh_batches_total\{variant="([^"]*)",devices="([^"]*)"\}$')


def _lost_paths(run, vid: int) -> list[str]:
    return [checks.shard_path(many._base(run, vid), s) for s in run.lost]


def _lose(run) -> None:
    for vid in run.vids:
        run.srv.delete_shards(vid, run.lost)
    left = [p for vid in run.vids for p in _lost_paths(run, vid) if os.path.exists(p)]
    common.require(not left, f"{left} survived VolumeEcShardsDelete")
    t0 = time.monotonic()
    while any(s in shards for shards in many._listed(run).values() for s in run.lost):
        common.require(time.monotonic() - t0 < 60, "the master never noticed the lost shards")
        time.sleep(0.05)
    common.settle_disk()


def _rebuild(run) -> None:
    run.last_out = run.srv.shell(common.LOCK.format("ec.rebuild"))


def _mesh_batches(run) -> dict[str, int]:
    """The batches the mesh backend counted over the last command, by
    `variant/devices`; empty on a program without the counter."""
    out = {}
    for key in run.marks[1]:
        m = MESH_BATCHES_RE.match(key)
        if m and many._rose(run, key):
            out["/".join(m.groups())] = int(many._rose(run, key))
    return out


def _look(run) -> None:
    """What the last command left: shards that differ from the ones lost,
    decodes the chip server's device backend did not count as its own, and
    batches the mesh counted on another number of devices than the cell's
    chips (off rehearsal: a rehearsal's backend has one device)."""
    for vid in run.vids:
        for s, path in zip(run.lost, _lost_paths(run, vid)):
            if not os.path.exists(path) or checks.file_sha(path) != run.shard_sha[vid, s]:
                run.shards_differ += 1
    runs = {k for k in run.marks[1] if k.startswith(many.RUNS)}
    on_chip = sum(many._rose(run, f'{many.RUNS}{{backend="{b}"}}') for b in DEVICE_BACKENDS)
    if on_chip != len(run.vids) or sum(many._rose(run, k) for k in runs) != on_chip:
        run.off_chip += 1
    if not run.rehearse:
        run.off_mesh += sum(n for key, n in _mesh_batches(run).items() if key.split("/")[1] != str(run.chips))


def _rebuild_spans(run, since: float) -> list:
    """`rebuild_1lost_each._rebuild_spans` with the `form=` the dispatches
    said: per rebuild RPC of the last command, from the chip server's trace
    ring (a sample: a log line, never a metric), its method, seconds, the run
    span's attributes, `forms` (form -> dispatches) and, by span name, [count,
    milliseconds in all] (`mesh.put` under `rebuild.dispatch`, `mesh.restore`
    under `rebuild.sync`)."""
    out = []
    got = http_json(f"http://{run.srv.vs_url}/debug/traces?kind=rpc.server&limit=1000")
    for t in got.get("traces", []):
        method = t["root"].get("attrs", {}).get("method")
        if t["start"] < since or method not in many.REBUILD_RPCS:
            continue
        by_name: dict[str, list] = {}
        attrs, forms = {}, {}

        def walk(sp: dict) -> None:
            n = by_name.setdefault(sp["name"], [0, 0.0])
            n[0] += 1
            n[1] = round(n[1] + sp["dur_ms"], 3)
            if sp["name"] == "rebuild.run":
                attrs.update(sp.get("attrs") or {})
            if sp["name"] == "rebuild.dispatch":
                form = (sp.get("attrs") or {}).get("form")
                forms[form] = forms.get(form, 0) + 1
            for c in sp.get("spans", ()):
                walk(c)
        for sp in t["root"].get("spans", ()):
            walk(sp)
        out.append({"method": method, "s": round(t["duration_s"], 4), "run": attrs, "forms": forms, "spans": by_name})
    return out


def _lose_and_rebuild(run, timed: bool) -> float | None:
    """-> the command's wall seconds, or None where it failed."""
    _lose(run)
    before, since = scrape(run.srv.vs_url), time.time()
    try:
        if timed:
            run.attempted += 1
            wall = common.timed_op(run, _rebuild, {"width": run.lost_bytes // len(run.lost) // run.chips})
        else:
            wall = 0.0
            _rebuild(run)
    except common.BenchError as e:
        print(f"benchmark: the {'timed' if timed else 'warm'} ec.rebuild failed: {e}", flush=True)
        if timed:
            run.failed += 1
        else:
            run.shards_differ += len(run.vids) * len(run.lost)
        return None
    run.marks = (before, scrape(run.srv.vs_url))
    if timed:
        seconds = {m: round(many._rose(run, many.RPC_S.format(m)), 4) for m in many.RPCS}
        run.rpcs_per_command = {m: int(many._rose(run, many.RPC_N.format(m))) for m in many.RPCS}
        run.mesh_batches = _mesh_batches(run) or None
        samples = run.facts["samples"]
        samples.setdefault("rebuild_rpc", []).append(
            sum(many._rose(run, many.RPC_S.format(m)) for m in many.REBUILD_RPCS))
        mesh_seconds = {}
        for stage in ("put", "restore"):
            if MESH_S.format(stage) in run.marks[1]:  # a program without the counter gives no sample
                mesh_seconds[stage] = many._rose(run, MESH_S.format(stage))
                samples.setdefault("mesh_" + stage, []).append(mesh_seconds[stage])
        common.say(command=len(run.timed), wall=round(wall, 4), rpc_seconds=seconds, rpcs=run.rpcs_per_command,
                   mesh_seconds={k: round(v, 4) for k, v in mesh_seconds.items()}, mesh_batches=run.mesh_batches,
                   output=run.last_out.strip().splitlines()[1:-1], rebuilds=_rebuild_spans(run, since))
    return wall


def _status_differing(run) -> int:
    """How many of the facts the configuration's `status` names the server's
    `/status` reports otherwise (`run.backend`: read at boot, and `run.py`
    refuses a run whose backend changed under it)."""
    return sum(1 for k, v in run.config["status"].items() if run.backend.get(k) != v)


def setup(run) -> None:
    run.vids = [int(v) for v in run.traffic["volume_ids"]]
    common.require(len(run.vids) == int(run.config["volumes"]), "traffic and configuration disagree on the volumes")
    common.require(run.chips == int(run.config["chips"]), "the cell and its configuration disagree on the chips")
    run.lost = [int(s) for s in run.config["lost_shards"]]
    os.environ.update(run.config["server_env"])  # the server child inherits it: the operator's seam
    with run.phase("volume"):
        # one process per volume: each is seeded from --seed and its volume id
        jobs = [(run.data_dir, vid, run.seed * 1000 + vid, run.dataset) for vid in run.vids]
        with concurrent.futures.ProcessPoolExecutor(
                len(jobs), mp_context=multiprocessing.get_context("spawn")) as pool:
            run.ds = dict(zip(run.vids, pool.map(many._build, jobs)))
    run.orig_dat = {}
    for vid in run.vids:
        run.orig_dat[vid] = os.path.join(run.work, f"orig{vid}.dat")
        os.link(many._base(run, vid) + ".dat", run.orig_dat[vid])
    with run.phase("boot"):
        run.boot(run.vids[0])
        for vid in run.vids[1:]:
            run.srv.wait_volume(vid)
    if not run.rehearse:
        common.require(_status_differing(run) == 0,
                       f"the server's /status is not the configuration's {run.config['status']}: {run.backend}")
    with run.phase("encode"):
        run.srv.shell(common.LOCK.format("; ".join(f"ec.encode -volumeId {v} -force" for v in run.vids)))
    with run.phase("shard_sha"):
        run.shard_sha = {(vid, s): checks.file_sha(path)
                         for vid in run.vids for s, path in zip(run.lost, _lost_paths(run, vid))}
        # two volumes of one size in MiB may differ by a 1 MiB row: a command's bytes are the sum
        run.lost_bytes = sum(os.path.getsize(p) for vid in run.vids for p in _lost_paths(run, vid))
        common.say(phase="lost", lost=run.lost, lost_bytes_per_command=run.lost_bytes)
    run.shards_differ = run.off_chip = run.off_mesh = 0
    run.rpcs_per_command = run.mesh_batches = None
    with run.phase("warm_cycle"):
        if _lose_and_rebuild(run, timed=False) is not None:
            common.say(phase="warm_command", output=run.last_out.strip().splitlines())
            _look(run)


def window(run) -> None:
    run.timed = []
    compiled_before = scrape(run.srv.vs_url).get(many.COMPILED)
    t_end = time.monotonic() + run.seconds
    while True:
        wall = _lose_and_rebuild(run, timed=True)
        if wall is None:
            break
        run.timed.append(wall)
        last = time.monotonic() >= t_end and len(run.timed) >= int(run.traffic["min_commands"])
        if last and run.fault == "flip_shard_byte":
            common.flip_byte(_lost_paths(run, run.vids[0])[0], run.seed)
        _look(run)
        if last:
            break
    common.bulk_rate(run, "rebuild", run.lost_bytes)
    compiled_after = scrape(run.srv.vs_url).get(many.COMPILED)
    # the harness's own count, as `run.py` makes it for its `compiles` line: what
    # the server's log says it compiled since set-up ended, whatever the program counts
    log = run.srv.log_text()[run.warm_mark:]
    run.compiles_in_window = log.count("Finished XLA compilation of ") - log.count("Persistent compilation cache hit")
    run.facts["timed"].update(
        volumes=len(run.vids), rpcs_per_command=run.rpcs_per_command,
        mesh_batches_per_command=run.mesh_batches,
        programs_compiled_in_window=(
            None if compiled_before is None or compiled_after is None else int(compiled_after - compiled_before)))


def verify(run) -> None:
    run.check("rebuilt_shards_differing", run.shards_differ, 0)
    run.check("rebuilds_off_the_chip", run.off_chip, 0)
    run.check("batches_off_the_mesh", run.off_mesh, 0)
    run.check("status_differing", 0 if run.rehearse else _status_differing(run), 0)
    run.check("compiles_in_window", run.compiles_in_window, 0)
    listed = many._listed(run)
    run.check("shards_not_listed",
              sum(1 for vid in run.vids for s in range(many.TOTAL) if run.srv.vs_url not in listed[vid].get(s, ())), 0)
    for vid in run.vids:
        got = checks.check_shards(many._base(run, vid), run.orig_dat[vid], run.seed,
                                  int(run.traffic["parity_rows_checked"]))
        for name in ("files_missing", "crc_mismatches", "data_cells_differing", "parity_cells_differing"):
            run.check(f"v{vid}.{name}", got[name], 0)
        run.check(f"v{vid}.final_gets_wrong", many._final_gets(run, vid, int(run.traffic["final_gets"])), 0)
