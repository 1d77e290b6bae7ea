"""EC lifecycle shell commands — ec.encode / ec.rebuild / ec.decode /
ec.balance, mirroring weed/shell/command_ec_encode.go, command_ec_rebuild.go,
command_ec_decode.go, command_ec_balance.go + command_ec_common.go
[VERIFY: mount empty; SURVEY.md §3.1/§3.3]. Fan-out over nodes uses a
thread pool (errgroup analog)."""

from __future__ import annotations

import functools
import json
import os
import threading
from concurrent import futures
from typing import Optional, TextIO

from seaweedfs_tpu.ec.constants import DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT
from seaweedfs_tpu.ec.shard_bits import ShardBits
from seaweedfs_tpu.obs import trace as trace_obs
from seaweedfs_tpu.shell import (
    CommandEnv,
    ShellCommand,
    ShellError,
    grpc_addr,
    parse_flags,
    register,
)
from seaweedfs_tpu.utils.door import Door

#: thunks `_parallel` runs at once: a volume's spread copies, a rebuild's
#: pulls, and the volumes of one `ec.encode` batch whose freezes, and later
#: whose cut-overs, run side by side (`_encode_batch`)
_POOL = 8


class EncodeCheckpoint:
    """Persisted ec.encode work-list (SURVEY §5: "encode of 10k volumes
    resumes"): a batch over many volumes survives interruption — the rerun
    skips completed vids. One JSON file, keyed by the volume-selection
    criteria so a checkpoint from a different selection is never misapplied.
    `mark(vid)` returns only after a file that holds `vid` has been written,
    fsync'd and renamed; volumes that finish while another's write is running
    share the next write (`utils/door.py`: a group commit, nothing deferred).
    [ref: weed/shell/command_ec_encode.go — mount empty; upstream restarts
    from scratch, this is the resume SURVEY §5 calls out as required.]"""

    def __init__(self, path: str, selector: dict):
        self.path = path
        self.selector = selector
        #: the volumes the file on disk holds, and the files written so far
        self.done: set[int] = set()
        self.writes = 0
        self._door = Door(self._write)

    def load_done(self) -> set[int]:
        try:
            with open(self.path, encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError):
            return set()
        if data.get("selector") != self.selector:
            return set()  # different batch criteria: ignore, will overwrite
        self.done = {int(v) for v in data.get("done", [])}
        return self.done

    def mark(self, vid: int) -> None:
        self._door.through(vid)

    def _write(self, vids: list[int]) -> None:
        done = self.done | set(vids)
        tmp = self.path + ".tmp"  # the door lets one writer through at a time
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"selector": self.selector, "done": sorted(done)}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)
        self.done = done
        self.writes += 1

    def finish(self) -> None:
        try:
            os.remove(self.path)
        except OSError:
            pass


def _node_ec_load(node: dict) -> int:
    """Total EC shards currently on the node."""
    return sum(
        ShardBits(e.get("shard_bits", 0)).shard_id_count()
        for e in node.get("ec_shards", [])
    )


def _node_shards_of(node: dict, vid: int) -> list[int]:
    for e in node.get("ec_shards", []):
        if int(e.get("volume_id", -1)) == vid:
            return ShardBits(e.get("shard_bits", 0)).shard_ids()
    return []


def _volume_locations(nodes: list[dict], vid: int) -> list[dict]:
    return [n for n in nodes if any(int(v["id"]) == vid for v in n.get("volumes", []))]


def allocate_shards(
    nodes: list[dict],
    total: int = TOTAL_SHARDS_COUNT,
    data_shards: int = DATA_SHARDS_COUNT,
    given: Optional[dict[str, int]] = None,
) -> dict[str, list[int]]:
    """Balanced, FAILURE-DOMAIN-CAPPED spread of `total` shard ids over
    nodes — the shared `ec/placement.py` planner: each shard goes to the
    least-loaded node whose rack still has headroom under the
    no-domain-holds-more-than-m cap (the invariant that makes a whole-
    rack loss survivable by construction); on topologies with too few
    racks the cap relaxes minimally instead of failing. `given` (url ->
    shards) is what the caller has planned onto a node since `nodes` was
    read, counted into its load: the earlier volumes of a sweep."""
    if not nodes:
        raise ShellError("no volume servers available")
    from seaweedfs_tpu.ec import placement
    from seaweedfs_tpu.utils import config as _config

    given = given or {}
    return placement.plan_spread(
        nodes,
        total,
        max(1, total - data_shards),
        cap_override=int(_config.env("WEEDTPU_PLACEMENT_MAX_PER_DOMAIN")),
        load_of=lambda n: _node_ec_load(n) + given.get(n["url"], 0),
    )


def _in_trace(fn):
    """`fn` for a pool thread, under the submitting thread's ambient span:
    ContextVars do not cross a pool's submission, and an RPC sent without
    the command's trace id is recorded by no server."""
    parent = trace_obs.current()

    def run(*args, **kw):
        with trace_obs.attach(parent):
            return fn(*args, **kw)

    return run


def _parallel(work: list) -> None:
    """Run thunks concurrently, re-raising the first failure."""
    if not work:
        return
    with futures.ThreadPoolExecutor(max_workers=_POOL) as pool:
        for f in [pool.submit(_in_trace(t)) for t in work]:
            f.result()


class _WholeLines:
    """A command's output where several threads write it: every write (a
    line, whole) under one lock."""

    def __init__(self, w: TextIO):
        self._w = w
        self._mu = threading.Lock()

    def write(self, text: str) -> int:
        with self._mu:
            return self._w.write(text)


# -- ec.encode ---------------------------------------------------------------


#: a batch of a sweep closes at this many volumes or bytes of .dat, whichever
#: comes first (a volume over the bytes is a batch of one): until a batch's
#: cut-overs run (after its one generate RPC, `_POOL` volumes side by
#: side), its server holds every volume's shards beside its .dat, and the
#: checkpoint marks nothing of it
ENCODE_BATCH_MAX_VOLUMES = 16
ENCODE_BATCH_MAX_BYTES = 16 << 30


def _encode_batches(plans: list[dict]) -> list[list[dict]]:
    """The selected volumes as batches: source server by source server (the
    first replica holder, in the order of each server's first volume), cut
    at ENCODE_BATCH_MAX_VOLUMES / ENCODE_BATCH_MAX_BYTES."""
    by_source: dict[str, list[dict]] = {}
    for plan in plans:
        by_source.setdefault(plan["locations"][0]["url"], []).append(plan)
    out: list[list[dict]] = []
    for group in by_source.values():
        batch: list[dict] = []
        for plan in group:
            if batch and (
                len(batch) >= ENCODE_BATCH_MAX_VOLUMES
                or sum(p["size"] for p in batch) + plan["size"] > ENCODE_BATCH_MAX_BYTES
            ):
                out.append(batch)
                batch = []
            batch.append(plan)
        out.append(batch)
    return out


def _encode_batch(
    env: CommandEnv,
    nodes: list[dict],
    plans: list[dict],
    w: TextIO,
    large_block_size: int = 0,
    small_block_size: int = 0,
    inline: bool = False,
    on_done=None,
) -> tuple[list[int], int]:
    """One source server's volumes of a sweep (a lone `-volumeId` too).
    Each is frozen on every replica (SURVEY.md §3.1); the server generates
    them all in ONE `VolumeEcShardsGenerateBatch`, their rows sharing one
    pipeline's batches (`-inline` finalizes a volume's own encode-on-write
    state, so there each volume keeps its `VolumeEcShardsGenerate`); then
    each volume's own cut-over: spread (where the sweep planned it:
    `plan["alloc"]`), mount, delete of the original, `on_done(vid)`, in
    that order. The freezes, and later the cut-overs, of
    the batch's volumes run side by side, `_POOL` volumes at once (a
    batch of one on this thread); all of them together keep at most `_POOL`
    `VolumeEcShardsCopy` in flight against the source server, what one
    volume's spread alone may put there. A volume that fails anywhere is made
    writable again and reported, and the others complete.
    -> (the volumes that were not encoded, the cut-overs that began while
    another's was running)."""
    src_addr = grpc_addr(plans[0]["locations"][0])
    error: dict[int, str] = {}
    mode: dict[int, str] = {}
    w = _WholeLines(w)

    def fail(plan: dict, e) -> None:
        error[plan["vid"]] = str(e) if isinstance(e, ShellError) else f"{type(e).__name__}: {e}"

    def side_by_side(step, of: list[dict]) -> None:
        if len(of) > 1:
            _parallel([functools.partial(step, plan) for plan in of])
        else:
            for plan in of:
                step(plan)

    # 1. freeze writes on every replica; a freeze is rolled back below if
    # anything later fails, or the volume is stuck readonly forever
    def freeze(plan: dict) -> None:
        try:
            for loc in plan["locations"]:
                env.vs_call(grpc_addr(loc), "VolumeMarkReadonly", {"volume_id": plan["vid"]})
        except Exception as e:  # noqa: BLE001 — this volume's alone
            fail(plan, e)

    side_by_side(freeze, plans)
    # 2. generate all 14 shards + .ecx of each on the first replica holder
    block_sizes = {}
    if large_block_size:
        block_sizes["large_block_size"] = large_block_size
    if small_block_size:
        block_sizes["small_block_size"] = small_block_size
    frozen = [p for p in plans if p["vid"] not in error]
    if inline:
        # finalize from the server's encode-on-write stripe state —
        # byte-identical shards, the encode already amortized into ingest;
        # the server falls back to the warm conversion when no usable inline
        # state exists and reports which path ran
        for plan in frozen:
            req = {"volume_id": plan["vid"], "collection": plan["collection"], "inline": True}
            try:
                resp = env.vs_call(src_addr, "VolumeEcShardsGenerate", {**req, **block_sizes})
                mode[plan["vid"]] = resp.get("mode") or ""
            except Exception as e:  # noqa: BLE001
                fail(plan, e)
    elif frozen:
        from seaweedfs_tpu.ec import placement

        req = placement.rebuild_batch_request((p["vid"], p["collection"]) for p in frozen)
        try:
            resp = env.vs_call(
                src_addr, "VolumeEcShardsGenerateBatch", {**req, **block_sizes},
                timeout=600 * len(frozen),
            )
            results = {int(r["volume_id"]): r for r in resp.get("results", [])}
            for plan in frozen:
                r = results.get(plan["vid"])
                if r is None or r.get("error"):
                    error[plan["vid"]] = (r or {}).get("error") or "no result"
            w.write(
                f"ec.encode batch on {plans[0]['locations'][0]['url']}: {len(frozen)} "
                f"volumes in {int(resp.get('batches', 0))} batches\n"
            )
        except Exception as e:  # noqa: BLE001 — the call itself: every volume of it
            for plan in frozen:
                fail(plan, e)
    # 3.-5. each volume's own cut-over, or its freeze rolled back
    copy_gate = threading.BoundedSemaphore(_POOL)
    count = threading.Lock()
    running = overlapped = 0

    def cutover(plan: dict) -> None:
        nonlocal running, overlapped
        vid = plan["vid"]
        if vid not in error:
            with count:
                overlapped += running > 0
                running += 1
            try:
                _spread_cutover(
                    env, nodes, plan["locations"], vid, plan["collection"], w, mode.get(vid),
                    copy_gate, plan["alloc"],
                )
                if on_done is not None:
                    on_done(vid)
            except Exception as e:  # noqa: BLE001
                fail(plan, e)
            finally:
                with count:
                    running -= 1
        if vid in error:
            for loc in plan["locations"]:
                try:
                    env.vs_call(grpc_addr(loc), "VolumeMarkWritable", {"volume_id": vid})
                except Exception:  # noqa: BLE001 — best-effort rollback
                    pass
            w.write(f"ec.encode volume {vid}: NOT encoded: {error[vid]}\n")

    side_by_side(cutover, plans)
    return sorted(error), overlapped


def _spread_cutover(
    env: CommandEnv,
    nodes: list[dict],
    locations: list[dict],
    vid: int,
    collection: str,
    w: TextIO,
    gen_mode: Optional[str],
    copy_gate: threading.BoundedSemaphore,
    alloc: dict[str, list[int]],
) -> None:
    """One generated volume's cut-over: its shards spread as `alloc` says
    (url -> shard ids: the sweep's plan, `_plan_encode`) and mounted, then
    the original and its replicas deleted. `copy_gate` bounds the copies that
    the cut-overs of one source server's volumes pull from it at once."""
    source = locations[0]
    src_addr = grpc_addr(source)
    # 3. spread: targets pull from source

    def copy_and_mount(node: dict, sids: list[int]):
        def run():
            addr = grpc_addr(node)
            if node["url"] != source["url"]:
                with copy_gate:
                    env.vs_call(
                        addr,
                        "VolumeEcShardsCopy",
                        {
                            "volume_id": vid,
                            "collection": collection,
                            "shard_ids": sids,
                            "source_data_node": src_addr,
                            "copy_ecx_file": True,
                        },
                    )
                env.vs_call(
                    addr,
                    "VolumeEcShardsMount",
                    {"volume_id": vid, "collection": collection, "shard_ids": sids},
                )
            return None

        return run

    _parallel([copy_and_mount(n, sids) for url, sids in alloc.items()
               for n in nodes if n["url"] == url])
    # 4. source keeps only its allocated shards (delete remounts the rest).
    # Single-node clusters keep everything: an empty shard_ids list means
    # "delete ALL" to the RPC, so it must not be sent at all.
    kept = alloc.get(source["url"], [])
    moved = [s for s in range(TOTAL_SHARDS_COUNT) if s not in kept]
    if moved:
        env.vs_call(
            src_addr,
            "VolumeEcShardsDelete",
            {"volume_id": vid, "collection": collection, "shard_ids": moved},
        )
    if kept:
        env.vs_call(
            src_addr,
            "VolumeEcShardsMount",
            {"volume_id": vid, "collection": collection, "shard_ids": kept},
        )
    # 5. drop the original volume + replicas — cut-over complete
    for loc in locations:
        env.vs_call(grpc_addr(loc), "VolumeDelete", {"volume_id": vid})
    mode_note = f" ({gen_mode} encode)" if gen_mode else ""
    w.write(f"ec.encode volume {vid}: spread {_fmt_alloc(alloc)}{mode_note}\n")


def _fmt_alloc(alloc: dict[str, list[int]]) -> str:
    return " ".join(f"{u}={','.join(map(str, s))}" for u, s in sorted(alloc.items()))


def do_ec_encode(args: list[str], env: CommandEnv, w: TextIO) -> None:
    fl = parse_flags(
        args,
        volumeId=0,
        collection="",
        fullPercent=95.0,
        quietFor=0,  # seconds since the last write; 0 disables the filter
        force=False,
        largeBlockSize=0,
        smallBlockSize=0,
        inline=False,  # finalize from encode-on-write state (WEEDTPU_INLINE_EC)
        checkpoint=".ec_encode.checkpoint",
    )
    env.confirm_locked()
    before = env.rpcs
    with trace_obs.span("shell.plan") as planning:
        planned = _plan_encode(fl, env, w)
        if planning is not None:
            planning.annotate(volumes=len(planned[1]) if planned else 0, rpcs=env.rpcs - before)
    if planned is None:
        return
    nodes, plans, ckpt = planned

    failed: list[int] = []
    overlapped = 0
    for batch in _encode_batches(plans):
        not_encoded, beside = _encode_batch(
            env,
            nodes,
            batch,
            w,
            large_block_size=fl.largeBlockSize,
            small_block_size=fl.smallBlockSize,
            inline=bool(fl.inline),
            # a volume is done when ITS cut-over is complete, batch or no batch
            on_done=ckpt.mark if ckpt is not None else None,
        )
        failed += not_encoded
        overlapped += beside
    # the shards the sweep left on each server, most first (level where the
    # plan was), and the copies that took them there: one a volume and target
    spread = {n["url"]: 0 for n in nodes}
    copies = 0
    for plan in plans:
        if plan["vid"] not in failed:
            for url, sids in plan["alloc"].items():
                spread[url] += len(sids)
                copies += url != plan["locations"][0]["url"]
    trace_obs.annotate(
        overlapped=overlapped, ckpt_writes=ckpt.writes if ckpt is not None else 0, copies=copies,
        spread="/".join(map(str, sorted(spread.values(), reverse=True))),
    )
    if failed:
        raise ShellError(f"ec.encode: volumes {failed} were not encoded")
    if ckpt is not None:
        ckpt.finish()  # batch complete: a future batch starts fresh


def _plan_encode(fl, env: CommandEnv, w: TextIO):
    """`ec.encode` before its first freeze (the `shell.plan` span): the
    topology, the selection, the checkpoint, where each volume lives and
    where its shards will (`alloc`: the spread of ALL the command's volumes
    is planned here, from the one topology snapshot, each volume's with the
    shards given to the volumes before it counted into every server's load,
    so a sweep comes out level and not every volume on the same servers; a
    volume that later fails keeps its share, the others their plan).
    -> (nodes, plans, checkpoint or None), or None where nothing matches."""
    topo = env.volume_list()
    nodes = env.topology_nodes()
    limit = int(topo.get("volume_size_limit", 0)) or 1
    # each volume's real collection comes from the topology, not the flag —
    # the flag only SELECTS volumes
    coll_of: dict[int, str] = {}
    size_of: dict[int, int] = {}
    for n in nodes:
        for v in n.get("volumes", []):
            coll_of[int(v["id"])] = v.get("collection", "")
            size_of[int(v["id"])] = max(size_of.get(int(v["id"]), 0), int(v.get("size", 0)))
    vids: list[int] = []
    if fl.volumeId:
        if fl.volumeId not in coll_of:
            raise ShellError(f"volume {fl.volumeId} not found on any node")
        vids = [fl.volumeId]
    else:
        import time as _time

        now = _time.time()
        # aggregate across replicas FIRST: the quiet check must see the
        # NEWEST write on any replica — a stale replica's old mtime would
        # otherwise select a volume that is actively taking writes
        sizes: dict[int, int] = {}
        newest: dict[int, int] = {}
        for n in nodes:
            for v in n.get("volumes", []):
                vid = int(v["id"])
                if v.get("collection", "") != fl.collection:
                    continue
                sizes[vid] = max(sizes.get(vid, 0), int(v.get("size", 0)))
                newest[vid] = max(newest.get(vid, 0), int(v.get("last_modified", 0)))
        vids = sorted(
            vid
            for vid, size in sizes.items()
            if (fl.force or size >= limit * fl.fullPercent / 100.0)
            # -quietFor: a volume still taking writes must not be EC-frozen
            # (the reference's default encode safety filter)
            and not (fl.quietFor and now - newest[vid] < fl.quietFor)
        )
    if not vids:
        w.write("ec.encode: no matching volumes\n")
        return None
    # batch resume: single -volumeId runs don't checkpoint (nothing to skip)
    ckpt = None
    done: set[int] = set()
    if not fl.volumeId and fl.checkpoint:
        ckpt = EncodeCheckpoint(
            fl.checkpoint,
            {
                "collection": fl.collection,
                "fullPercent": fl.fullPercent,
                "quietFor": fl.quietFor,
                "force": bool(fl.force),
            },
        )
        # no intersection with the current selection: a volume whose
        # cut-over completed may still linger in a stale topology view —
        # skipping it is exactly the point
        done = ckpt.load_done()
        if done:
            w.write(f"ec.encode: resuming, {len(done)} volume(s) already done\n")
    # plan first: what is left of the selection, where each volume lives
    plans: list[dict] = []
    given: dict[str, int] = {}
    for vid in vids:
        if vid in done:
            w.write(f"ec.encode volume {vid}: skip (checkpointed)\n")
            continue
        locations = _volume_locations(nodes, vid)
        if not locations:
            raise ShellError(f"volume {vid} not found on any node")
        alloc = allocate_shards(nodes, given=given)
        for url, sids in alloc.items():
            given[url] = given.get(url, 0) + len(sids)
        plans.append(
            {"vid": vid, "collection": coll_of[vid], "locations": locations, "size": size_of[vid],
             "alloc": alloc}
        )
    return nodes, plans, ckpt


register(
    ShellCommand(
        "ec.encode",
        "ec.encode -volumeId <id> | -collection <name> [-fullPercent 95] "
        "[-quietFor <secs>] [-force] [-inline] [-checkpoint <file>]\n"
        "\tencode a volume into 14 EC shards, spread them, delete the original;\n"
        "\twithout -volumeId a sweep: the spread of ALL its volumes is planned\n"
        "\tbefore the first is frozen, each volume's with the shards already given\n"
        "\tto the others counted, so the sweep leaves the servers level (no rack\n"
        "\tover 4 of a volume's 14, as ever); every selected volume, source server by\n"
        "\tsource server in ONE VolumeEcShardsGenerateBatch (their rows share one\n"
        "\tpipeline's device batches; at most 16 volumes or 16 GiB a batch), then\n"
        "\teach volume's own cut-over (spread, mount, delete of the original, in\n"
        "\tthat order), eight volumes of the batch side by side, so their lines\n"
        "\tcome in the order they finish; a volume that fails is made writable\n"
        "\tagain and reported (NOT encoded), the others complete, the command ends\n"
        "\tin an error naming it; sweeps checkpoint a volume when its cut-over is\n"
        "\tcomplete (the file fsynced before it counts; volumes that finish\n"
        "\ttogether share a write) and resume on rerun;\n"
        "\t-inline finalizes from the server's encode-on-write stripe state\n"
        "\t(WEEDTPU_INLINE_EC=on) instead of re-encoding the sealed .dat —\n"
        "\tbyte-identical shards, warm fallback when no usable inline state",
        do_ec_encode,
    )
)


# -- ec.rebuild --------------------------------------------------------------


def _shard_holders(nodes: list[dict], vid: int) -> dict[int, list[dict]]:
    out: dict[int, list[dict]] = {}
    for n in nodes:
        for sid in _node_shards_of(n, vid):
            out.setdefault(sid, []).append(n)
    return out


class CopiesFailed(ShellError):
    """Some pull of a gather failed. `landed`: the shard ids that are on the
    node now, or may be: what the caller has to drop."""

    def __init__(self, message: str, landed: list[int]):
        super().__init__(message)
        self.landed = landed


def _copy_missing_to(env: CommandEnv, node: dict, vid: int, collection: str,
                     holders: dict[int, list[dict]],
                     only: Optional[set] = None) -> list[int]:
    """Pull every survivor shard `node` lacks onto it (restricted to the
    `only` set when given); returns the shard ids temporarily copied (for
    cleanup). Where a pull fails, `CopiesFailed` carries the same list."""
    local = set(_node_shards_of(node, vid))
    by_source: dict[str, list[int]] = {}
    for sid, hs in holders.items():
        if sid in local or (only is not None and sid not in only):
            continue
        src = next((h for h in hs if h["url"] != node["url"]), None)
        if src is None:
            continue
        by_source.setdefault(grpc_addr(src), []).append(sid)
    first = not local  # no local shards: also pull the index files
    # Pull from every source in parallel (command_ec_rebuild.go's
    # prepareDataToRecover analog): each source writes disjoint .ecNN files
    # on the rebuilder, and the .ecx/.ecj pull rides exactly one call, so
    # the copies are independent. Wall time = slowest source, not the sum.
    jobs = []
    for src_addr, sids in sorted(by_source.items()):
        jobs.append((src_addr, sids, first))
        first = False
    # on the node once its pull has ended, and in part where the pull failed
    # (the files before the one that broke are renamed already)
    copied = [sid for _, sids, _ in jobs for sid in sids]
    errs: list[str] = []
    with futures.ThreadPoolExecutor(max_workers=min(_POOL, max(1, len(jobs)))) as pool:
        futs = {
            pool.submit(
                _in_trace(env.vs_call),
                grpc_addr(node),
                "VolumeEcShardsCopy",
                {
                    "volume_id": vid,
                    "collection": collection,
                    "shard_ids": sids,
                    "source_data_node": src_addr,
                    "copy_ecx_file": with_ecx,
                },
            ): src_addr
            for src_addr, sids, with_ecx in jobs
        }
        for fut in futures.as_completed(futs):
            try:
                fut.result()
            except Exception as e:  # noqa: BLE001
                errs.append(f"{futs[fut]}: {e}")
    if errs:
        raise CopiesFailed(
            f"volume {vid}: shard copies to {node['url']} failed: {'; '.join(errs)}", copied
        )
    return copied


def _ec_collections(env: CommandEnv, topo: Optional[dict] = None) -> dict[int, str]:
    """vid -> collection, from the master's EC registry (`topo`: a
    `VolumeList` answer the caller has already)."""
    if topo is None:
        topo = env.volume_list()
    return {int(vid): coll for vid, coll in topo.get("ec_collections", {}).items()}


def pick_rebuilder(
    nodes: list[dict], holders: dict[int, list[dict]], missing: list[int], parity: int
) -> dict:
    """Where one volume's rebuild lands: `placement.pick_rebuild_target`, the
    ONE definition the master's scheduler (ec/fleet.py) uses too — a node
    whose codec runs on a device, then the one already holding the most
    shards (fewest copies — or, in -remote mode, the fewest slabs streamed
    over the network), then the least EC-loaded, then url. `nodes` are
    `env.topology_nodes()` dicts: each carries its rack, its shards and
    the `ec_backend` its heartbeat reported."""
    from seaweedfs_tpu.ec import placement
    from seaweedfs_tpu.utils import config as _config

    return placement.pick_rebuild_target(
        # equal holders rank by cluster-wide EC load, as the scheduler feeds it
        [dict(n, ec_load=_node_ec_load(n)) for n in nodes],
        {sid: [h["url"] for h in hs] for sid, hs in holders.items()},
        {n["url"]: placement.domain_of(n) for n in nodes},
        missing,
        parity,
        cap_override=int(_config.env("WEEDTPU_PLACEMENT_MAX_PER_DOMAIN")),
    )


def do_ec_rebuild(args: list[str], env: CommandEnv, w: TextIO) -> None:
    """Plan every EC volume of the selection (what is missing, who holds
    what, the geometry, the rebuilder: `_plan_rebuild`, one `VolumeList`),
    then rebuild. First, rebuilder by
    rebuilder, the volumes whose survivors are ALL on their rebuilder
    already go together in ONE `VolumeEcShardsRebuildBatch` (a lone one
    too), whose packed pipeline fills and drains once for all of them.
    Then the volumes that need survivor copies, in plan order, through
    `_rebuild_pipelined`: each keeps its own copy, single-volume RPC and
    drop of the copies, and the next volume's copies are gathered while
    this one is rebuilt, so that a rebuilder's disk holds at most TWO
    volumes' temporary copies at any moment. `-remote` stays volume by
    volume: its options are the single RPC's."""
    fl = parse_flags(args, collection="", remote=False, trace="auto")
    trace_mode = str(fl.trace).strip().lower()
    if trace_mode not in ("on", "off", "auto"):
        raise ShellError(f"-trace must be on|off|auto, got {fl.trace!r}")
    env.confirm_locked()
    before = env.rpcs
    with trace_obs.span("shell.plan") as planning:
        by_rebuilder = _plan_rebuild(fl, env, w)
        if planning is not None:
            planning.annotate(
                volumes=sum(map(len, by_rebuilder.values())), rpcs=env.rpcs - before
            )
    failed: list[int] = []
    for plans in by_rebuilder.values():
        if fl.remote:
            for plan in plans:
                _rebuild_remote(env, plan, trace_mode, w)
            continue
        batch = [p for p in plans if p["local"]]
        if batch:
            failed += _rebuild_many(env, batch, w)
    if by_rebuilder and not fl.remote:
        _rebuild_pipelined(
            env, [p for plans in by_rebuilder.values() for p in plans if not p["local"]], w
        )
    if failed:
        raise ShellError(f"ec.rebuild: volumes {failed} were not rebuilt")


def _plan_rebuild(fl, env: CommandEnv, w: TextIO) -> dict[str, list[dict]]:
    """`ec.rebuild` before its first copy or rebuild (the `shell.plan`
    span), from ONE `VolumeList`: every EC volume of the selection that
    misses shards, with who holds what, its geometry (the master's
    `ec_geometry`, as the holders heartbeat it; a `VolumeStatus` only for a
    volume the answer names no geometry for) and its rebuilder.
    -> rebuilder url -> its volumes' plans, in id order."""
    topo = env.volume_list()
    nodes = env.topology_nodes(topo)
    colls = _ec_collections(env, topo)
    geometry = topo.get("ec_geometry", {})
    ec_vids = sorted(
        {int(e["volume_id"]) for n in nodes for e in n.get("ec_shards", [])}
    )
    if fl.collection:
        ec_vids = [v for v in ec_vids if colls.get(v, "") == fl.collection]
    by_rebuilder: dict[str, list[dict]] = {}  # url -> its volumes, in id order
    for vid in ec_vids:
        holders = _shard_holders(nodes, vid)
        # geometry-flexible volumes (ec.convert targets) record their own
        # (k, k+m): missing-shard detection over the legacy 14 would never
        # see a lost shard id >= 14 of a 20+4 volume, and the survivor
        # gate would mis-assess 12+3. Any holder knows it and heartbeats it
        # (a cut-over's heartbeat reaches the master before `ec.convert`
        # returns); old servers report 0 -> legacy.
        geo = geometry.get(str(vid)) or {}
        k, total = int(geo.get("data_shards") or 0), int(geo.get("total_shards") or 0)
        if not (k and total):
            # an old master, or holders that heartbeat no geometry: the
            # holder of the most shards is asked, as before
            k, total = DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT
            witness = max(nodes, key=lambda n: len(_node_shards_of(n, vid)))
            try:
                st = env.vs_call(
                    grpc_addr(witness), "VolumeStatus", {"volume_id": vid}, timeout=10
                )
                k = int(st.get("data_shards") or 0) or k
                total = int(st.get("total_shards") or 0) or total
            except Exception:  # noqa: BLE001 — unknown geometry: legacy bounds
                pass
        missing = [s for s in range(total) if s not in holders]
        if not missing:
            continue
        if len(holders) < k:
            w.write(
                f"ec.rebuild volume {vid}: only {len(holders)} shards survive, "
                f"need {k} — data LOST\n"
            )
            continue
        rebuilder = pick_rebuilder(nodes, holders, missing, max(1, total - k))
        by_rebuilder.setdefault(rebuilder["url"], []).append(
            {
                "vid": vid,
                "collection": colls.get(vid, ""),
                "holders": holders,
                "rebuilder": rebuilder,
                # nothing to copy: every survivor is on the rebuilder already
                "local": set(holders) <= set(_node_shards_of(rebuilder, vid)),
            }
        )
    return by_rebuilder


def _rebuild_remote(env: CommandEnv, plan: dict, trace_mode: str, w: TextIO) -> None:
    """The distributed path: NO bulk survivor pre-copy. The rebuilder
    streams survivor input from peer holders while decoding —
    trace-repair projections when the holders speak them
    (-trace auto/on), full slabs otherwise — writes +
    CRC-verifies the missing .ecNN files, and mounts only those."""
    vid, collection, rebuilder = plan["vid"], plan["collection"], plan["rebuilder"]
    addr = grpc_addr(rebuilder)
    resp = env.vs_call(
        addr,
        "VolumeEcShardsRebuild",
        {
            "volume_id": vid,
            "collection": collection,
            "remote": True,
            "trace_mode": trace_mode,
        },
        timeout=600,
    )
    rebuilt = resp.get("rebuilt_shard_ids", [])
    if rebuilt:
        env.vs_call(
            addr,
            "VolumeEcShardsMount",
            {"volume_id": vid, "collection": collection, "shard_ids": rebuilt},
        )
    detail = ""
    if resp.get("remote_survivors"):
        detail = f" (remote survivors {resp['remote_survivors']}"
        if resp.get("failed_over"):
            detail += f", failed over {resp['failed_over']}"
        if resp.get("mode"):
            detail += f", {resp['mode']} mode"
            if resp.get("wire_bytes") is not None:
                detail += f" moved {resp['wire_bytes']} bytes"
            if resp.get("trace_fallback"):
                detail += f", trace fell back: {resp['trace_fallback']}"
        detail += ")"
    w.write(
        f"ec.rebuild volume {vid}: rebuilt {rebuilt} on "
        f"{rebuilder['url']}{detail}\n"
    )


def _rebuild_pipelined(env: CommandEnv, plans: list[dict], w: TextIO) -> None:
    """The volumes that need survivor copies, in plan order, each upstream's
    copy-then-rebuild on its rebuilder (copies, `VolumeEcShardsRebuild`,
    `VolumeEcShardsDelete` of the copies), as a pipeline of two stages: the
    gather of volume n+1 runs on a worker beside the rebuild and the drop of
    volume n. A gather starts when the one before it has ENDED (two gathers
    never share the sockets, the receiver and the disk) and the volume
    before that has been DROPPED: a rebuilder's disk holds at most two
    volumes' temporary copies. Rebuilds and drops stay on the calling
    thread, in plan order, never two at once, never before all of a
    volume's copies have landed. A lone volume is a pipeline of one.

    Whatever fails, nothing temporary is left when the error goes up: a
    volume's copies go whether or not its rebuild came back (what a gather
    landed before one of its pulls failed, too), and a gather in flight is
    awaited and what it landed is dropped."""

    def gather(plan: dict) -> tuple[list[int], Optional[CopiesFailed]]:
        try:
            return _copy_missing_to(
                env, plan["rebuilder"], plan["vid"], plan["collection"], plan["holders"]
            ), None
        except CopiesFailed as e:
            return e.landed, e

    def drop(plan: dict, copied: list[int]) -> None:
        # delete remounts local = original + rebuilt (never with an empty
        # list: the server reads that as every shard)
        if copied:
            env.vs_call(
                grpc_addr(plan["rebuilder"]),
                "VolumeEcShardsDelete",
                {"volume_id": plan["vid"], "collection": plan["collection"], "shard_ids": copied},
            )

    def rebuild(plan: dict) -> list[int]:
        vid, rebuilder = plan["vid"], plan["rebuilder"]
        try:
            resp = env.vs_call(
                grpc_addr(rebuilder),
                "VolumeEcShardsRebuild",
                {"volume_id": vid, "collection": plan["collection"]},
            )
        except Exception as e:  # noqa: BLE001 — the error names its volume
            raise ShellError(
                f"ec.rebuild volume {vid}: NOT rebuilt on {rebuilder['url']}: {e}"
            ) from e
        return resp.get("rebuilt_shard_ids", [])

    overlapped = 0  # gathers that ran beside the rebuild of the volume before
    try:
        with futures.ThreadPoolExecutor(max_workers=1) as pool:
            ahead = pool.submit(_in_trace(gather), plans[0]) if plans else None
            for n, plan in enumerate(plans):
                copied, gather_failed = ahead.result()
                ahead = None
                try:
                    try:
                        if gather_failed is not None:
                            raise gather_failed
                        if n + 1 < len(plans):
                            ahead = pool.submit(_in_trace(gather), plans[n + 1])
                            overlapped += 1
                        rebuilt = rebuild(plan)
                    finally:
                        drop(plan, copied)
                except BaseException:
                    if ahead is not None:
                        drop(plans[n + 1], ahead.result()[0])
                    raise
                w.write(
                    f"ec.rebuild volume {plan['vid']}: rebuilt {rebuilt} on "
                    f"{plan['rebuilder']['url']}\n"
                )
    finally:
        w.write(
            f"ec.rebuild: {len(plans)} volumes with copies, "
            f"{overlapped} gathered beside a rebuild\n"
        )
        trace_obs.annotate(overlapped=overlapped)


def _rebuild_many(env: CommandEnv, plans: list[dict], w: TextIO) -> list[int]:
    """One rebuilder's volumes that need no survivor copy, as ONE batch:
    one `VolumeEcShardsRebuildBatch` rebuilds and mounts them all, in the
    order of the plan. A volume whose part of the batch failed prints its
    error and the others complete. -> the volumes that were not rebuilt."""
    from seaweedfs_tpu.ec import placement

    rebuilder = plans[0]["rebuilder"]
    resp = env.vs_call(
        grpc_addr(rebuilder),
        "VolumeEcShardsRebuildBatch",
        placement.rebuild_batch_request((p["vid"], p["collection"]) for p in plans),
        timeout=300 * len(plans),
    )
    results = {int(r["volume_id"]): r for r in resp.get("results", [])}
    w.write(
        f"ec.rebuild batch on {rebuilder['url']}: {len(plans)} volumes in "
        f"{int(resp.get('signature_groups', 0))} signature groups\n"
    )
    failed: list[int] = []
    for plan in plans:
        vid = plan["vid"]
        r = results.get(vid) or {}
        error = r.get("error") or ("" if r else "no result")
        if error:
            failed.append(vid)
            w.write(f"ec.rebuild volume {vid}: NOT rebuilt on {rebuilder['url']}: {error}\n")
        else:
            w.write(
                f"ec.rebuild volume {vid}: rebuilt "
                f"{[int(s) for s in r.get('rebuilt_shard_ids', [])]} on {rebuilder['url']}\n"
            )
    return failed


register(
    ShellCommand(
        "ec.rebuild",
        "ec.rebuild [-collection <name>] [-remote] [-trace on|off|auto]\n\tfind "
        "EC volumes with lost shards and reconstruct them on a rebuilder node\n"
        "\t(planned from ONE VolumeList: the master's answer carries every "
        "volume's\n\tgeometry, a holder is asked only where it names none;\n"
        "\tthose with every survivor on their rebuilder already, in ONE "
        "batch;\n\tthose that need survivor copies one rebuild at a time, the "
        "next volume's\n\tcopies gathered meanwhile: a rebuilder's disk holds at "
        "most two volumes'\n\ttemporary copies, and none when the command "
        "returns);\n"
        "\t-remote streams survivors from their holders through the network-\n"
        "\toverlapped rebuild pipeline instead of bulk-copying shard files "
        "first;\n\t-trace (with -remote) controls repair-bandwidth projections: "
        "holders ship\n\tGF-projected rows instead of full slabs (on = wherever "
        "holders support\n\tit, auto = only when it also moves fewer bytes; any "
        "failure falls back\n\tto slabs)",
        do_ec_rebuild,
    )
)


# -- ec.convert --------------------------------------------------------------


def do_ec_convert(args: list[str], env: CommandEnv, w: TextIO) -> None:
    """Re-encode an aging EC volume into a different registered code
    family (geometry) without a decode->re-encode round trip: data blocks
    regroup, new parity is a GF projection of surviving shards, progress
    is journaled crash-resumable, and the old geometry serves reads until
    the verified cut-over. The converting node needs the source data
    shards locally, so missing survivors are pulled first (the ec.decode
    pre-copy discipline); stale old-geometry shards on OTHER nodes are
    deleted after cut-over, leaving the converted volume whole on the
    converter — ec.balance re-spreads it."""
    fl = parse_flags(
        args,
        volumeId=0,
        collection="",
        family="",
        nocutover=False,
    )
    if not fl.family:
        raise ShellError("ec.convert needs -family <registered code family>")
    env.confirm_locked()
    nodes = env.topology_nodes()
    colls = _ec_collections(env)
    ec_vids = sorted(
        {int(e["volume_id"]) for n in nodes for e in n.get("ec_shards", [])}
    )
    if fl.volumeId:
        if fl.volumeId not in ec_vids:
            raise ShellError(f"ec volume {fl.volumeId} not found")
        ec_vids = [fl.volumeId]
    elif fl.collection:
        ec_vids = [v for v in ec_vids if colls.get(v, "") == fl.collection]
    if not ec_vids:
        w.write("ec.convert: no matching EC volumes\n")
        return
    for vid in ec_vids:
        collection = colls.get(vid, "")
        holders = _shard_holders(nodes, vid)
        # converter = the node already holding the most shards (fewest
        # survivor copies before the conversion can read the full stripe)
        converter = max(nodes, key=lambda n: len(_node_shards_of(n, vid)))
        addr = grpc_addr(converter)
        # the conversion reads at most k source shards (all data when
        # healthy; parity only stands in for data shards missing
        # everywhere) — pre-copy exactly that set, not every survivor
        only: Optional[set] = None
        try:
            st = env.vs_call(addr, "VolumeStatus", {"volume_id": vid}, timeout=10)
            k = int(st.get("data_shards") or 0)
        except Exception:  # noqa: BLE001 — unknown geometry: copy all
            k = 0
        if k > 0:
            everywhere = set(holders) | set(_node_shards_of(converter, vid))
            data_have = sorted(s for s in everywhere if s < k)[:k]
            only = set(data_have) | set(
                sorted(s for s in everywhere if s >= k)[
                    : max(0, k - len(data_have))
                ]
            )
        copied = _copy_missing_to(
            env, converter, vid, collection, holders, only=only
        )
        resp = env.vs_call(
            addr,
            "VolumeEcShardsConvert",
            {
                "volume_id": vid,
                "collection": collection,
                "target_family": fl.family,
                "cutover": not fl.nocutover,
            },
            timeout=600,
        )
        if not fl.nocutover and resp.get("mode") != "noop":
            # old-geometry shards elsewhere are stale after cut-over —
            # drop them so lookups stop routing reads at dead layouts
            for n in nodes:
                sids = _node_shards_of(n, vid)
                if n["url"] == converter["url"] or not sids:
                    continue
                env.vs_call(
                    grpc_addr(n),
                    "VolumeEcShardsDelete",
                    {
                        "volume_id": vid,
                        "collection": collection,
                        "shard_ids": sids,
                    },
                )
        elif resp.get("mode") == "noop":
            # a noop where the converter already holds the COMPLETE target
            # set while other nodes still hold shards is the signature of
            # a previous ec.convert dying between its cut-over RPC and
            # this cleanup loop: those leftovers are old-GEOMETRY shards a
            # new-geometry locate must never route a read to. Deleting is
            # not safe to automate from here (a healthy resident volume
            # plus deliberate replica copies looks the same), so surface
            # it loudly with the exact remedy.
            held = set(_node_shards_of(converter, vid)) | set(copied)
            tgt_ids = {int(s) for s in resp.get("shard_ids") or []}
            leftovers = [
                (n["url"], _node_shards_of(n, vid))
                for n in nodes
                if n["url"] != converter["url"] and _node_shards_of(n, vid)
            ]
            if tgt_ids and tgt_ids <= held and leftovers:
                for url, sids in leftovers:
                    w.write(
                        f"ec.convert volume {vid}: WARNING possible stale "
                        f"old-geometry shards {sids} on {url} (interrupted "
                        "post-cutover cleanup?) — verify and remove with "
                        "ec.verify / VolumeEcShardsDelete, then ec.balance\n"
                    )
        w.write(
            f"ec.convert volume {vid}: {resp.get('src_family')} -> "
            f"{resp.get('target_family')} ({resp.get('mode')}) on "
            f"{converter['url']}: read {resp.get('bytes_read')} wrote "
            f"{resp.get('bytes_written')} bytes"
            + (
                f", reconstructed {resp['reconstructed_bytes']} degraded"
                if resp.get("reconstructed_bytes")
                else ""
            )
            + ("" if fl.nocutover else ", cut over")
            + "\n"
        )


register(
    ShellCommand(
        "ec.convert",
        "ec.convert -volumeId <id> | -collection <name> -family <name> "
        "[-nocutover]\n"
        "\tre-encode an EC volume into another registered code family "
        "(geometry)\n\twithout decoding: data blocks regroup, new parity "
        "is a GF projection of\n\tsurviving shards, progress journals "
        "crash-resumable (.ecc), and the old\n\tgeometry keeps serving "
        "until the verified cut-over; -nocutover stages the\n\tconverted "
        "set (<base>.cv.*) and leaves retirement to a later call",
        do_ec_convert,
    )
)


# -- ec.verify ---------------------------------------------------------------


def do_ec_verify(args: list[str], env: CommandEnv, w: TextIO) -> None:
    """CRC-verify EC shards against their .eci records on every holder —
    the control-plane face of the scrubber's math (VolumeEcShardsVerify).
    Read-only by default; -quarantine pulls failing shards from serving
    and hands them to the holders' automatic-repair queues."""
    fl = parse_flags(args, volumeId=0, collection="", quarantine=False)
    if fl.quarantine:
        env.confirm_locked()  # mutates serving state on the holders
    nodes = env.topology_nodes()
    colls = _ec_collections(env)
    ec_vids = sorted(
        {int(e["volume_id"]) for n in nodes for e in n.get("ec_shards", [])}
    )
    if fl.volumeId:
        if fl.volumeId not in ec_vids:
            raise ShellError(f"ec volume {fl.volumeId} not found")
        ec_vids = [fl.volumeId]
    elif fl.collection:
        ec_vids = [v for v in ec_vids if colls.get(v, "") == fl.collection]
    bad_total = 0
    for vid in ec_vids:
        collection = colls.get(vid, "")
        for n in nodes:
            if not _node_shards_of(n, vid):
                continue
            try:
                resp = env.vs_call(
                    grpc_addr(n),
                    "VolumeEcShardsVerify",
                    {
                        "volume_id": vid,
                        "collection": collection,
                        "quarantine": bool(fl.quarantine),
                    },
                    timeout=600,  # a full-volume CRC pass, not a ping
                )
            except Exception as e:  # noqa: BLE001 — report, keep verifying
                w.write(f"ec.verify volume {vid} @{n['url']}: ERROR {e}\n")
                bad_total += 1
                continue
            verdicts = {
                int(s): v for s, v in (resp.get("verdicts") or {}).items()
            }
            bad = {s: v for s, v in verdicts.items() if v != "ok"}
            bad_total += len(bad)
            line = " ".join(
                f"{s}={verdicts[s]}" for s in sorted(verdicts)
            ) or "(no local shards)"
            if not resp.get("has_crcs"):
                line += " [no .eci CRC record — unverifiable]"
            if resp.get("quarantined"):
                line += f" [quarantined {sorted(resp['quarantined'])} for repair]"
            w.write(f"ec.verify volume {vid} @{n['url']}: {line}\n")
    w.write(
        f"ec.verify: {bad_total} shard(s) failed verification\n"
        if bad_total
        else "ec.verify: all shards verified clean\n"
    )


register(
    ShellCommand(
        "ec.verify",
        "ec.verify [-volumeId <id>] [-collection <name>] [-quarantine]\n"
        "\tCRC-verify every holder's EC shards against the .eci record "
        "(the scrub\n\tmath, on demand) and print per-shard verdicts; "
        "-quarantine also pulls\n\tfailing shards from serving and queues "
        "their automatic trace-repair",
        do_ec_verify,
    )
)


# -- ec.decode ---------------------------------------------------------------


def do_ec_decode(args: list[str], env: CommandEnv, w: TextIO) -> None:
    fl = parse_flags(args, volumeId=0, collection="")
    env.confirm_locked()
    nodes = env.topology_nodes()
    colls = _ec_collections(env)
    ec_vids = sorted(
        {int(e["volume_id"]) for n in nodes for e in n.get("ec_shards", [])}
    )
    if fl.volumeId:
        if fl.volumeId not in ec_vids:
            raise ShellError(f"ec volume {fl.volumeId} not found")
        ec_vids = [fl.volumeId]
    elif fl.collection:
        ec_vids = [v for v in ec_vids if colls.get(v, "") == fl.collection]
    for vid in ec_vids:
        collection = colls.get(vid, "")
        holders = _shard_holders(nodes, vid)
        target = max(nodes, key=lambda n: len(_node_shards_of(n, vid)))
        addr = grpc_addr(target)
        # the volume's recorded geometry, not the legacy 10/14: a
        # converted (12+3, 20+4) volume has a different survivor gate and
        # remnant-shard range (old servers report 0 -> legacy bounds)
        k, total = DATA_SHARDS_COUNT, TOTAL_SHARDS_COUNT
        try:
            st = env.vs_call(addr, "VolumeStatus", {"volume_id": vid}, timeout=10)
            k = int(st.get("data_shards") or 0) or k
            total = int(st.get("total_shards") or 0) or total
        except Exception:  # noqa: BLE001 — unknown geometry: legacy bounds
            pass
        if len(holders) < k:
            w.write(f"ec.decode volume {vid}: insufficient shards — data LOST\n")
            continue
        _copy_missing_to(env, target, vid, collection, holders)
        env.vs_call(
            addr, "VolumeEcShardsToVolume", {"volume_id": vid, "collection": collection}
        )
        # remove EC remnants everywhere (the .dat volume now lives on target)
        for n in nodes:
            if _node_shards_of(n, vid) or n["url"] == target["url"]:
                env.vs_call(
                    grpc_addr(n),
                    "VolumeEcShardsDelete",
                    {
                        "volume_id": vid,
                        "collection": collection,
                        "shard_ids": list(range(total)),
                    },
                )
        w.write(f"ec.decode volume {vid}: restored as normal volume on {target['url']}\n")


register(
    ShellCommand(
        "ec.decode",
        "ec.decode [-volumeId <id>] [-collection <name>]\n\tconvert EC shard sets "
        "back into normal volumes",
        do_ec_decode,
    )
)


# -- ec.balance --------------------------------------------------------------


def pick_balance_move(
    placement: dict[str, dict[int, set]],
    by_url: dict[str, dict],
    heaviest: str,
    lightest: str,
    colls: dict[int, str],
    collection_filter: str,
):
    """Choose which (vid, shard) to move heaviest -> lightest. Among the
    volumes with a movable shard, prefer the one whose shards are most
    CONCENTRATED in the heavy node's rack relative to the light node's —
    the move then also improves rack spread (command_ec_balance.go
    balances racks before nodes). Pure so the ordering is unit-testable.
    Returns (vid, sid) or None."""

    def rack_shards(vid: int, rack: str) -> int:
        return sum(
            len(placement[u].get(vid, ()))
            for u in placement
            if by_url[u]["rack"] == rack
        )

    src_rack = by_url[heaviest]["rack"]
    dst_rack = by_url[lightest]["rack"]
    candidates = []
    for vid, sids in placement[heaviest].items():
        if collection_filter and colls.get(vid, "") != collection_filter:
            continue
        movable = sids - placement[lightest].get(vid, set())
        if not movable:
            continue
        spread_gain = rack_shards(vid, src_rack) - rack_shards(vid, dst_rack)
        candidates.append((-spread_gain, vid, min(movable)))
    if not candidates:
        return None
    _key, vid, sid = min(candidates)
    return vid, sid


def _move_shard(
    env: CommandEnv, src: dict, dst: dict, vid: int, collection: str,
    sid: int, dst_has_vid: bool,
) -> None:
    """One shard migration dst <- src via the copy/mount/delete RPC
    discipline (PR 12's shard-copy machinery)."""
    env.vs_call(
        grpc_addr(dst),
        "VolumeEcShardsCopy",
        {
            "volume_id": vid,
            "collection": collection,
            "shard_ids": [sid],
            "source_data_node": grpc_addr(src),
            "copy_ecx_file": not dst_has_vid,
        },
    )
    env.vs_call(
        grpc_addr(dst),
        "VolumeEcShardsMount",
        {"volume_id": vid, "collection": collection, "shard_ids": [sid]},
    )
    env.vs_call(
        grpc_addr(src),
        "VolumeEcShardsDelete",
        {"volume_id": vid, "collection": collection, "shard_ids": [sid]},
    )


def fix_placement_moves(
    placement_map: dict[str, dict[int, set]],
    by_url: dict[str, dict],
    parity_of,
    cap_override: int = 0,
    only_vids=None,
):
    """Plan the migrations that restore the failure-domain invariant:
    for every (stripe, domain) holding more than m shards, move the
    excess (highest shard ids first) to nodes in domains with headroom,
    least-loaded first. Pure: yields (vid, sid, src_url, dst_url); the
    caller executes every planned move — `placement_map` is mutated AS
    the plan is built, so a caller-side skip would desynchronize the
    map from the cluster (filter with `only_vids` instead)."""
    from seaweedfs_tpu.ec import placement as pl

    moves: list[tuple[int, int, str, str]] = []
    domains = {u: pl.domain_of(n) for u, n in by_url.items()}
    vids = sorted({vid for per in placement_map.values() for vid in per})
    if only_vids is not None:
        vids = [v for v in vids if v in set(only_vids)]
    for vid in vids:
        parity = parity_of(vid)
        cap = pl.max_per_domain(parity, cap_override)
        holders = {}
        for u, per in placement_map.items():
            for s in per.get(vid, ()):
                holders.setdefault(s, []).append(u)
        for dom, sids in pl.stripe_violations(
            holders, domains, parity, cap_override
        ):
            excess = sids[cap:]
            for sid in excess:
                src_url = next(
                    u for u in holders.get(sid, []) if domains[u] == dom
                )

                def dom_count(d: tuple) -> int:
                    return len(
                        {
                            s
                            for u, per in placement_map.items()
                            if domains[u] == d
                            for s in per.get(vid, ())
                        }
                    )

                candidates = [
                    u
                    for u in placement_map
                    if domains[u] != dom
                    and sid not in placement_map[u].get(vid, ())
                    and dom_count(domains[u]) < cap
                ]
                if not candidates:
                    continue  # nowhere legal: reported, not worsened
                dst_url = min(
                    candidates,
                    key=lambda u: (
                        sum(len(s) for s in placement_map[u].values()),
                        u,
                    ),
                )
                moves.append((vid, sid, src_url, dst_url))
                placement_map[src_url][vid].discard(sid)
                placement_map[dst_url].setdefault(vid, set()).add(sid)
    return moves


def do_ec_balance(args: list[str], env: CommandEnv, w: TextIO) -> None:
    fl = parse_flags(args, collection="", fixPlacement=False)
    env.confirm_locked()
    nodes = env.topology_nodes()
    colls = _ec_collections(env)
    if not nodes:
        raise ShellError("no volume servers")
    # live shard map: url -> {vid -> set(sids)}
    placement: dict[str, dict[int, set]] = {
        n["url"]: {
            int(e["volume_id"]): set(ShardBits(e.get("shard_bits", 0)).shard_ids())
            for e in n.get("ec_shards", [])
        }
        for n in nodes
    }
    by_url = {n["url"]: n for n in nodes}

    def load(url: str) -> int:
        return sum(len(s) for s in placement[url].values())

    moves = 0
    if fl.fixPlacement:
        # restore the failure-domain invariant FIRST (a rack holding >m
        # shards of one stripe): correctness moves beat load moves
        def parity_of(vid: int) -> int:
            holders = [
                u for u, per in placement.items() if per.get(vid)
            ]
            for u in holders:
                try:
                    st = env.vs_call(
                        grpc_addr(by_url[u]), "VolumeStatus",
                        {"volume_id": vid}, timeout=10,
                    )
                    total = int(st.get("total_shards") or 0)
                    data = int(st.get("data_shards") or 0)
                    if total and data:
                        return max(1, total - data)
                except Exception:  # noqa: BLE001 — next holder
                    continue
            return TOTAL_SHARDS_COUNT - DATA_SHARDS_COUNT
        from seaweedfs_tpu.utils import config as _config

        planned = fix_placement_moves(
            placement, by_url, parity_of,
            cap_override=int(_config.env("WEEDTPU_PLACEMENT_MAX_PER_DOMAIN")),
            # filter BEFORE planning: the planner mutates `placement` as
            # it plans, so every planned move must actually execute
            only_vids=(
                [v for v in colls if colls.get(v, "") == fl.collection]
                if fl.collection
                else None
            ),
        )
        for vid, sid, src_url, dst_url in planned:
            _move_shard(
                env, by_url[src_url], by_url[dst_url], vid,
                colls.get(vid, ""), sid,
                # placement was already mutated by the planner: "had the
                # volume before this move" = any shard besides sid
                bool(placement[dst_url].get(vid, set()) - {sid}),
            )
            moves += 1
        if planned:
            w.write(
                f"ec.balance: fixed placement with {len(planned)} "
                "domain-cap move(s)\n"
            )
    while True:
        urls = sorted(placement, key=load)
        lightest, heaviest = urls[0], urls[-1]
        if load(heaviest) - load(lightest) <= 1:
            break
        picked = pick_balance_move(
            placement, by_url, heaviest, lightest, colls, fl.collection
        )
        if picked is None:
            break
        vid, sid = picked
        if fl.fixPlacement:
            # the load loop must not re-break the invariant the fix
            # phase just restored: refuse a move that would push the
            # destination's rack past the domain cap (stop balancing —
            # pick would re-propose the same move forever)
            from seaweedfs_tpu.ec import placement as _pl

            domains = {u: _pl.domain_of(n) for u, n in by_url.items()}
            holders: dict[int, list[str]] = {}
            for u, per in placement.items():
                for s in per.get(vid, ()):
                    holders.setdefault(s, []).append(u)
            # model the move: sid leaves heaviest, lands on lightest
            holders[sid] = [
                u for u in holders.get(sid, []) if u != heaviest
            ] + [lightest]
            if _pl.stripe_violations(
                holders, domains, parity_of(vid),
                int(_config.env("WEEDTPU_PLACEMENT_MAX_PER_DOMAIN")),
            ):
                w.write(
                    "ec.balance: stopping — the next load move would "
                    "violate the domain cap\n"
                )
                break
        collection = colls.get(vid, "")
        src, dst = by_url[heaviest], by_url[lightest]
        env.vs_call(
            grpc_addr(dst),
            "VolumeEcShardsCopy",
            {
                "volume_id": vid,
                "collection": collection,
                "shard_ids": [sid],
                "source_data_node": grpc_addr(src),
                "copy_ecx_file": not placement[lightest].get(vid),
            },
        )
        env.vs_call(
            grpc_addr(dst),
            "VolumeEcShardsMount",
            {"volume_id": vid, "collection": collection, "shard_ids": [sid]},
        )
        env.vs_call(
            grpc_addr(src),
            "VolumeEcShardsDelete",
            {"volume_id": vid, "collection": collection, "shard_ids": [sid]},
        )
        placement[heaviest][vid].discard(sid)
        if not placement[heaviest][vid]:
            del placement[heaviest][vid]
        placement[lightest].setdefault(vid, set()).add(sid)
        moves += 1
    w.write(f"ec.balance: moved {moves} shards\n")


register(
    ShellCommand(
        "ec.balance",
        "ec.balance [-collection <name>] [-fixPlacement]\n\teven out EC "
        "shard counts across volume servers; -fixPlacement first migrates "
        "shards\n\tout of failure domains holding more than m shards of a "
        "stripe (the\n\tno-rack-holds->m invariant), via the copy/mount/"
        "delete shard machinery",
        do_ec_balance,
    )
)


# -- ec.trace ----------------------------------------------------------------


def _fetch_json(url: str, timeout: float = 10.0) -> dict:
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def do_ec_trace(args: list[str], env: CommandEnv, w: TextIO) -> None:
    """Pull retained weedtrace span trees from every volume server's
    `/debug/traces` ring and render them slowest-first — the operator
    answer to "WHY was that read slow": per-stage wall times (lookup vs
    fetch vs hedge vs coalesce wait vs decode) for the tail requests the
    ring always keeps. Read-only; no cluster lock."""
    fl = parse_flags(
        args,
        server="",      # substring filter on the node url
        klass="",       # healthy | ec_intact | degraded | put | ...
        kind="",        # http.read | http.write | rpc.server | ...
        minMs=0.0,      # only traces at least this slow
        limit=5,        # per server
        traceId="",     # one specific id (post-incident grep)
    )
    # the master's ring too (master.http roots, its rpc.server
    # continuations, the `shell.script` trees `-c` children handed over):
    # "cluster-wide" must include every process that retains traces
    masters = env.master_call("ListClusterNodes", {}).get("masters") or [
        {"http_address": env.master_address, "grpc_address": env.master_address}
    ]  # a master that predates the field: its gRPC address, as before
    nodes = [{"url": m["http_address"], "grpc": m["grpc_address"]} for m in masters] + [
        dict(n, grpc=grpc_addr(n)) for n in env.topology_nodes()
    ]
    if fl.server:
        nodes = [n for n in nodes if fl.server in n["url"]]
    if not nodes:
        raise ShellError("no matching servers")
    shown = 0
    found: list[tuple[dict, dict]] = []  # -traceId: (node, trace), joined below
    for n in sorted(nodes, key=lambda n: n["url"]):
        q = f"?limit={1000000 if fl.traceId else int(fl.limit)}"
        if fl.klass:
            q += f"&class={fl.klass}"
        if fl.kind:
            q += f"&kind={fl.kind}"
        if fl.minMs:
            q += f"&min_ms={fl.minMs}"
        try:
            payload = _fetch_json(f"http://{n['url']}/debug/traces{q}")
        except Exception as e:  # noqa: BLE001 — a dead node has no ring
            w.write(f"# {n['url']}: unreachable ({e})\n")
            continue
        traces = payload.get("traces", [])
        if fl.traceId:
            traces = [t for t in traces if t.get("trace_id") == fl.traceId]
        st = payload.get("stats", {})
        w.write(
            f"# {n['url']}: {len(traces)} shown "
            f"(ring kept {st.get('kept', '?')}/{st.get('offered', '?')} "
            f"offered; tracing "
            f"{'on' if payload.get('enabled') else 'OFF'})\n"
        )
        if fl.traceId:
            found += [(n, t) for t in traces]
            continue
        for t in traces:
            w.write(trace_obs.render_trace(t) + "\n")
            shown += 1
    # one id, cluster-wide: a `-c` script's own tree first, each server's half
    # of an RPC under the `rpc.client` span that waited for it; then, server
    # by server, what had no caller among the script's spans. Servers of one
    # process share a ring: a trace is printed once.
    served = [(n["grpc"], t) for n, t in found if t["kind"] != "shell.script"]
    seen: set[tuple] = set()
    for n, t in found:
        if t["kind"] == "shell.script" and trace_obs.identity(t) not in seen:
            seen.add(trace_obs.identity(t))
            w.write(trace_obs.render_trace(t, served) + "\n")
            shown += 1
    url_of = {n["grpc"]: n["url"] for n, _ in found}
    for server, t in served:
        if trace_obs.identity(t) not in seen:
            seen.add(trace_obs.identity(t))
            w.write(f"# {url_of[server]}:\n" + trace_obs.render_trace(t) + "\n")
            shown += 1
    if not shown:
        w.write("ec.trace: no retained traces matched\n")


register(
    ShellCommand(
        "ec.trace",
        "ec.trace [-server <url-substr>] [-klass <class>] [-kind <kind>] "
        "[-minMs <ms>] [-limit <n>] [-traceId <id>]\n"
        "\trender retained weedtrace span trees from the master's and the volume "
        "servers'\n\t/debug/traces rings, slowest first — per-stage wall times "
        "(lookup/fetch/hedge/\n\tcoalesce/decode) for tail requests; -traceId "
        "finds one request cluster-wide\n\tand joins it: the `shell.script` tree a "
        "`shell -c` child handed to the master\n\t(start, plan, every RPC as "
        "`rpc.client`), and under each `rpc.client` the\n\t`rpc.server` tree of the "
        "same id from whichever server's ring holds it",
        do_ec_trace,
    )
)


# -- ec.status ---------------------------------------------------------------


def _scrape_metrics(url: str, timeout: float = 5.0) -> list[tuple[str, dict, float]]:
    """Parse one node's Prometheus /metrics text into
    [(bare_name, labels, value)] — just enough of the exposition format
    for the health summary (no external client on this image)."""
    import re as _re
    import urllib.request

    out: list[tuple[str, dict, float]] = []
    with urllib.request.urlopen(f"http://{url}/metrics", timeout=timeout) as r:
        text = r.read().decode()
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name_part, _, value = line.rpartition(" ")
        m = _re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?$", name_part)
        if not m:
            continue
        labels = {}
        if m.group(2):
            for pair in _re.findall(r'(\w+)="([^"]*)"', m.group(2)):
                labels[pair[0]] = pair[1]
        try:
            out.append((m.group(1), labels, float(value)))
        except ValueError:
            continue
    return out


def _metric_sum(rows, name: str, **match) -> float:
    return sum(
        v for n, labels, v in rows
        if n == name and all(labels.get(k) == str(val) for k, val in match.items())
    )


def _fleet_risk_lines(env: CommandEnv) -> list[str]:
    """The fleet-risk section of ec.status: the master scheduler's
    redundancy histogram (stripes by shards lost — the "am I about to
    lose data" view), failure-domain violations, and repair queue depth
    / inflight / recent events."""
    try:
        st = env.master_call("RepairStatus", {})
    except Exception as e:  # noqa: BLE001 — old master: no fleet section
        return [f"fleet: unavailable ({e})"]
    hist = st.get("redundancy_histogram") or {}
    hist_s = " ".join(
        f"{k}-lost={hist[k]}" for k in sorted(hist, key=lambda x: int(x))
    ) or "-"
    lines = [
        "fleet: scheduler="
        + ("on" if st.get("enabled") else "off (WEEDTPU_REPAIR=off)")
        + f" queue={st.get('queue_depth', 0)} inflight={st.get('inflight', 0)}"
        + f" stripes[{hist_s}]"
    ]
    batches = st.get("batches") or []
    if batches:
        fused = st.get("fused_volumes_total", 0)
        last = batches[-1]
        lines.append(
            f"fleet: batches={len(batches)} fused_volumes={fused} last["
            f"volumes={last.get('volumes', 0)}"
            f" sigs={last.get('signature_groups', 0)}"
            f" dispatches={last.get('dispatch_groups', 0)}"
            f" wall={last.get('wall_s', 0.0):.2f}s]"
        )
    suspects = st.get("suspects") or []
    if suspects:
        lines.append(f"fleet: suspects={' '.join(suspects)}")
    for v in st.get("violations") or []:
        lines.append(f"fleet: VIOLATION {v}")
    events = st.get("events") or []
    for e in events[-5:]:
        lines.append(
            f"fleet: [{e['seq']}] vid={e['volume_id']} "
            f"missing={e['missing']} {e['state']}"
            + (f" -> {e['target']}" if e.get("target") else "")
            + (f" ({e['detail']})" if e.get("detail") else "")
        )
    return lines


def do_ec_status(args: list[str], env: CommandEnv, w: TextIO) -> None:
    """One-screen cluster health summary: the master's fleet-risk view
    (redundancy histogram, placement violations, repair queue) plus
    per-server quarantined shards (with reasons, from VolumeStatus),
    scrub progress, rebuild/convert inflight (live weedtpu_rpc_inflight
    gauges), the decoded-interval read cache (hit/miss/hit-rate, bytes
    resident, evictions, invalidations), and the codec backend each
    server selected. Read-only; no cluster lock."""
    parse_flags(args)
    nodes = env.topology_nodes()
    if not nodes:
        raise ShellError("no volume servers")
    for line in _fleet_risk_lines(env):
        w.write(line + "\n")
    for n in sorted(nodes, key=lambda n: n["url"]):
        url = n["url"]
        ec_vids = sorted(
            int(e["volume_id"]) for e in n.get("ec_shards", [])
        )
        quarantined: list[str] = []
        for vid in ec_vids:
            try:
                st = env.vs_call(
                    grpc_addr(n), "VolumeStatus", {"volume_id": vid}, timeout=10
                )
            except Exception:  # noqa: BLE001 — racing unmount: skip
                continue
            for s, reason in sorted((st.get("quarantined") or {}).items()):
                quarantined.append(f"{vid}.{int(s):02d}={reason}")
        try:
            rows = _scrape_metrics(url)
        except Exception as e:  # noqa: BLE001 — node HTTP down
            w.write(f"{url}: UNREACHABLE ({e})\n")
            continue
        scrub_mb = _metric_sum(rows, "weedtpu_scrub_bytes_scanned_total") / 1e6
        cycles = int(_metric_sum(rows, "weedtpu_scrub_cycles_total"))
        found = int(_metric_sum(rows, "weedtpu_scrub_corruptions_found_total"))
        repairs_ok = int(_metric_sum(rows, "weedtpu_scrub_repairs_total", result="ok"))
        repairs_fail = int(
            _metric_sum(rows, "weedtpu_scrub_repairs_total", result="failed")
        )
        rebuild_inflight = int(
            _metric_sum(rows, "weedtpu_rpc_inflight", method="VolumeEcShardsRebuild")
        )
        convert_inflight = int(
            _metric_sum(rows, "weedtpu_rpc_inflight", method="VolumeEcShardsConvert")
        )
        rebuilds_done = int(_metric_sum(rows, "weedtpu_ec_rebuild_seconds_count"))
        converts_done = int(_metric_sum(rows, "weedtpu_ec_convert_seconds_count"))
        try:
            backend = _backend_brief(_server_status(url).get("ec_backend") or {})
        except Exception:  # noqa: BLE001 — metrics answered, status did not
            backend = "?"
        # xorsched schedule-cache state (only exported once the server has
        # dispatched through the xorsched path at least once)
        xs_hits = int(_metric_sum(rows, "weedtpu_xorsched_schedule_cache", event="hits"))
        xs_miss = int(_metric_sum(rows, "weedtpu_xorsched_schedule_cache", event="misses"))
        xs_size = int(_metric_sum(rows, "weedtpu_xorsched_schedule_cache", event="size"))
        xs_cap = int(_metric_sum(rows, "weedtpu_xorsched_schedule_cache", event="cap"))
        xs = (
            f" xorsched={xs_hits}hit/{xs_miss}miss({xs_size}/{xs_cap})"
            if xs_hits or xs_miss
            else ""
        )
        # decoded-interval cache: is degraded hot-set traffic actually
        # being served from cache, and is the budget churning (evictions)
        # or being flushed by topology events (invalidations)?
        cache_hits = int(_metric_sum(rows, "weedtpu_read_cache_hits_total"))
        cache_misses = int(_metric_sum(rows, "weedtpu_read_cache_misses_total"))
        cache_mb = _metric_sum(rows, "weedtpu_read_cache_bytes") / 1e6
        cache_evict = int(_metric_sum(rows, "weedtpu_read_cache_evictions_total"))
        cache_inval = int(
            _metric_sum(rows, "weedtpu_read_cache_invalidations_total")
        )
        cache_rate = (
            f"{cache_hits / (cache_hits + cache_misses):.0%}"
            if cache_hits + cache_misses
            else "-"
        )
        w.write(
            f"{url}: ec_volumes={len(ec_vids)} "
            f"quarantined=[{' '.join(quarantined) or '-'}] "
            f"scrub={scrub_mb:.1f}MB/{cycles}cyc found={found} "
            f"repairs={repairs_ok}ok/{repairs_fail}failed "
            f"rebuild={rebuild_inflight}inflight/{rebuilds_done}done "
            f"convert={convert_inflight}inflight/{converts_done}done "
            f"cache={cache_hits}hit/{cache_misses}miss({cache_rate}) "
            f"{cache_mb:.1f}MB evict={cache_evict} inval={cache_inval} "
            f"backend={backend}{xs}\n"
        )


register(
    ShellCommand(
        "ec.status",
        "ec.status\n\tone-screen cluster health: the master's fleet-risk "
        "view (stripes by\n\tremaining redundancy, failure-domain "
        "violations, repair queue/events),\n\tplus per-server quarantined "
        "shards (+reasons), scrub progress, live\n\trebuild/convert "
        "inflight, repair outcomes, the decoded-interval\n\tread-cache "
        "hit rate / footprint / churn, and the selected codec\n\tbackend",
        do_ec_status,
    )
)


# -- ec.backend --------------------------------------------------------------


def _server_status(url: str) -> dict:
    """One volume server's HTTP /status document."""
    return _fetch_json(f"http://{url}/status", timeout=5.0)


def _backend_brief(sel: dict) -> str:
    """`backend(source)[@platform xN]` — the ec.status column."""
    dev = sel.get("device") or {}
    on = f"@{dev.get('platform')}x{dev.get('count')}" if dev else ""
    return f"{sel.get('backend', '?')}({sel.get('source', '?')}){on}"


def do_ec_backend(args: list[str], env: CommandEnv, w: TextIO) -> None:
    """Operator view of each volume server's encoder selection audit, read
    from the servers' /status: which codec backend the server's
    `new_encoder("auto")` picked and why, and the device jax reported to
    it. The shell builds no encoder of its own: the process that owns the
    chip is the only one that can say what it runs on, and a tool that
    asked jax would take the chip from it. Read-only; no cluster lock."""
    parse_flags(args)
    nodes = env.topology_nodes()
    if not nodes:
        raise ShellError("no volume servers")
    for n in sorted(nodes, key=lambda n: n["url"]):
        url = n["url"]
        try:
            sel = dict(_server_status(url).get("ec_backend") or {})
        except Exception as e:  # noqa: BLE001 — node HTTP down
            w.write(f"ec.backend: {url}: UNREACHABLE ({e})\n")
            continue
        mesh_dec = sel.pop("mesh", None)  # nested decision: own line below
        dev = sel.pop("device", None) or {}
        if dev:
            sel["device"] = (
                f"{dev.get('platform')}:{dev.get('kind')}x{dev.get('count')}"
            )
        w.write(
            f"ec.backend: {url}: "
            + " ".join(f"{k}={sel[k]}" for k in sorted(sel) if sel[k] is not None)
            + "\n"
        )
        if isinstance(mesh_dec, dict) and sel.get("backend") != "mesh":
            w.write(
                f"ec.backend: {url}: mesh not promoted: "
                f"{mesh_dec.get('reason', 'n/a')}\n"
            )


register(
    ShellCommand(
        "ec.backend",
        "ec.backend\n\treport each volume server's backend selection audit, "
        "read from its\n\t/status (backend, device, evidence file, mesh "
        "shape/evidence round when\n\tthe pod path is promoted, and the "
        "reason a conservative default held)",
        do_ec_backend,
    )
)
