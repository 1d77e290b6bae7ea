"""Store — the per-server set of disk locations, normal volumes, and EC
volumes. Mirror of weed/storage/store.go + disk_location*.go + store_ec.go
[VERIFY: mount empty; SURVEY.md §2.1].
"""

from __future__ import annotations

import glob
import os
import re
import threading
from typing import Optional

from seaweedfs_tpu.ec.ec_volume import ECJ_COMPACT_THRESHOLD, EcVolume, ecj_compaction_due
from seaweedfs_tpu.ec.shard_bits import EcVolumeInfo, ShardBits
from seaweedfs_tpu.utils import glog
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.ops.rs_codec import Encoder, new_encoder
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.super_block import ReplicaPlacement, SuperBlock, TTL
from seaweedfs_tpu.storage.volume import Volume

_BASE_RE = re.compile(r"^(?:(?P<col>.+)_)?(?P<vid>\d+)$")


def parse_base_name(base: str) -> Optional[tuple[str, int]]:
    m = _BASE_RE.match(base)
    if not m:
        return None
    return m.group("col") or "", int(m.group("vid"))


class DiskLocation:
    def __init__(self, directory: str):
        # normpath: path-equality checks (e.g. resolving which location owns
        # a base path) must not break on a trailing slash in -dir
        self.directory = os.path.normpath(directory)
        os.makedirs(directory, exist_ok=True)
        self.volumes: dict[int, Volume] = {}
        self.ec_volumes: dict[int, EcVolume] = {}

    def load(self, encoder: Optional[Encoder] = None, needle_map_kind: str = "memory") -> None:
        # tiered volumes have no local .dat — discovered via .tierinfo
        discovered = glob.glob(os.path.join(self.directory, "*.dat")) + glob.glob(
            os.path.join(self.directory, "*.tierinfo")
        )
        for path in discovered:
            base = os.path.basename(path).rsplit(".", 1)[0]
            parsed = parse_base_name(base)
            if parsed is None:
                continue
            collection, vid = parsed
            if vid not in self.volumes:
                self.volumes[vid] = Volume(
                    self.directory, vid, collection, needle_map_kind=needle_map_kind
                )
        for ecx in glob.glob(os.path.join(self.directory, "*.ecx")):
            base = os.path.basename(ecx)[: -len(".ecx")]
            parsed = parse_base_name(base)
            if parsed is None:
                continue
            collection, vid = parsed
            base_path = os.path.join(self.directory, base)
            if vid not in self.ec_volumes and stripe.find_local_shards(base_path):
                try:
                    self.ec_volumes[vid] = EcVolume(base_path, encoder=encoder)
                except (ValueError, KeyError) as e:
                    # a shard set contradicting its .eci geometry (typed
                    # EcGeometryError — e.g. a crash mid-conversion-
                    # cutover) or a malformed/unusable .eci record (plain
                    # ValueError/KeyError out of geometry_from_info) must
                    # not kill server boot OR get served: skip it loudly —
                    # the convert resume path / operator finishes the
                    # swap, and the next load picks the healed volume up
                    glog.warning("skipping ec volume %d: %s", vid, e)


class Store:
    def __init__(
        self,
        directories: list[str],
        encoder: Optional[Encoder] = None,
        needle_map_kind: str = "memory",
    ):
        self.encoder = encoder or new_encoder()
        self.locations = [DiskLocation(d) for d in directories]
        # -index flag analog: memory rebuilds the id map in RAM per mount,
        # sorted_file binary-searches a persistent .sdx sidecar
        self.needle_map_kind = needle_map_kind
        self._lock = threading.RLock()
        #: vid -> the lock its EC mounts and unmounts take (mount_ec_volume)
        self._ec_mounting: dict[int, threading.Lock] = {}
        #: optional post-append hook `callback(vid)`, fired after every
        #: acked needle write/delete (both are .dat appends) — the inline-EC
        #: ingest manager polls its stripe builders through this seam. Must
        #: never raise into the write path (callers install a guarded fn).
        self.on_write: Optional[callable] = None

    def load(self) -> None:
        with self._lock:
            for loc in self.locations:
                loc.load(self.encoder, self.needle_map_kind)

    def close(self) -> None:
        with self._lock:
            for loc in self.locations:
                for v in loc.volumes.values():
                    v.close()
                for ev in loc.ec_volumes.values():
                    ev.close()

    # -- normal volumes ------------------------------------------------------

    def _pick_location(self) -> DiskLocation:
        return min(self.locations, key=lambda l: len(l.volumes) + len(l.ec_volumes))

    def create_volume(
        self,
        vid: int,
        collection: str = "",
        replication: str = "000",
        ttl: str = "",
        version: int = 3,
    ) -> Volume:
        with self._lock:
            if self.get_volume(vid) is not None:
                raise ValueError(f"volume {vid} already exists")
            sb = SuperBlock(
                version=version,
                replica_placement=ReplicaPlacement.parse(replication),
                ttl=TTL.parse(ttl),
            )
            loc = self._pick_location()
            v = Volume(
                loc.directory,
                vid,
                collection,
                super_block=sb,
                needle_map_kind=self.needle_map_kind,
            )
            loc.volumes[vid] = v
            return v

    def get_volume(self, vid: int) -> Optional[Volume]:
        for loc in self.locations:
            if vid in loc.volumes:
                return loc.volumes[vid]
        return None

    def get_ec_volume(self, vid: int) -> Optional[EcVolume]:
        for loc in self.locations:
            if vid in loc.ec_volumes:
                return loc.ec_volumes[vid]
        return None

    def write_needle(self, vid: int, n: Needle) -> tuple[int, int]:
        v = self.get_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        out = v.write_needle(n)
        if self.on_write is not None:
            self.on_write(vid)
        return out

    def read_needle(self, vid: int, needle_id: int, cookie: Optional[int] = None) -> Needle:
        v = self.get_volume(vid)
        if v is not None:
            return v.read_needle(needle_id, cookie)
        ev = self.get_ec_volume(vid)
        if ev is not None:
            return self.read_ec_needle(vid, needle_id, cookie)
        raise KeyError(f"volume {vid} not found")

    def delete_needle(self, vid: int, needle_id: int) -> bool:
        v = self.get_volume(vid)
        if v is not None:
            found = v.delete_needle(needle_id)
            if self.on_write is not None:
                self.on_write(vid)  # a tombstone is a .dat append too
            return found
        ev = self.get_ec_volume(vid)
        if ev is not None:
            return ev.delete_needle(needle_id)
        raise KeyError(f"volume {vid} not found")

    # -- EC volumes (store_ec.go analog) -------------------------------------

    def read_ec_needle(self, vid: int, needle_id: int, cookie: Optional[int] = None) -> Needle:
        ev = self.get_ec_volume(vid)
        if ev is None:
            raise KeyError(f"ec volume {vid} not found")
        blob = ev.read_needle_blob(needle_id)
        n = Needle.from_bytes(blob, ev.version)
        if n.id != needle_id:
            raise IOError(f"ec needle id mismatch: {n.id:x} != {needle_id:x}")
        if cookie is not None and n.cookie != cookie:
            raise PermissionError(f"needle {needle_id:x}: cookie mismatch")
        return n

    def _ec_mount_lock(self, vid: int) -> threading.Lock:
        with self._lock:
            return self._ec_mounting.setdefault(vid, threading.Lock())

    def mount_ec_volume(self, vid: int, base_path: str) -> EcVolume:
        """(Re)mount an EC volume from its files. EcVolume() opens the local
        shards and loads the `.ecx`, so it runs OUTSIDE the store lock (same
        discipline as mount_volume): mounts of different volumes run side by
        side. Mounts (and unmounts) of ONE vid go one at a time. A remount
        is built while the mount it replaces still serves; the store lock
        covers the swap into the map, and what was replaced is closed after
        it. The one remount that takes the old mount out of serving FIRST is
        the one whose constructor will fold the deletion journal into the
        `.ecx` (`ecj_compaction_due`): that unlinks the `.ecj`, and a delete
        the old mount journaled meanwhile, fsynced and acknowledged, would
        be unlinked with it. A mount built beside a serving one leaves the
        journal alone."""
        loc = next(
            (l for l in self.locations if os.path.dirname(base_path) == l.directory),
            self.locations[0],
        )
        with self._ec_mount_lock(vid):
            old = loc.ec_volumes.get(vid)
            if old is not None and ecj_compaction_due(base_path):
                with self._lock:
                    del loc.ec_volumes[vid]
                old.close()  # returns after the deletes it was journaling
                old = None
            ev = EcVolume(
                base_path,
                encoder=self.encoder,
                ecj_compact_threshold=ECJ_COMPACT_THRESHOLD if old is None else 0,
            )
            with self._lock:
                if old is not None:
                    # a delete the old mount took after `ev` read the journal
                    ev.inherit_deletes(old)
                loc.ec_volumes[vid] = ev
        if old is not None:
            old.close()
        return ev

    def unmount_ec_volume(self, vid: int) -> None:
        with self._ec_mount_lock(vid):
            with self._lock:
                gone = [loc.ec_volumes.pop(vid, None) for loc in self.locations]
            for ev in gone:
                if ev is not None:
                    ev.close()

    # -- status / heartbeat --------------------------------------------------

    def remove_volume(self, vid: int) -> bool:
        """Close and unlink a local volume's files. The store lock covers
        only the map pop — close() can block behind a minutes-long
        compaction's volume lock, and holding Store._lock through that
        would stall create/mount (and with them every Assign-driven grow)
        cluster-wide."""
        popped = []
        with self._lock:
            for loc in self.locations:
                v = loc.volumes.pop(vid, None)
                if v is not None:
                    popped.append(v)
        for v in popped:
            v.close()
            # .tierinfo included: leaving it would resurrect the volume as
            # a zombie on the next mount (load() discovers via *.tierinfo)
            for ext in (".dat", ".idx", ".sdx", ".sdx.meta", ".tierinfo"):
                p = v.base_path + ext
                if os.path.exists(p):
                    os.remove(p)
        return bool(popped)

    def unmount_volume(self, vid: int) -> bool:
        """Close a volume and stop serving it, KEEPING its files on disk
        (VolumeUnmount analog) — the inverse of mount_volume."""
        popped = []
        with self._lock:
            for loc in self.locations:
                v = loc.volumes.pop(vid, None)
                if v is not None:
                    popped.append(v)
        for v in popped:
            v.close()
        return bool(popped)

    def mount_volume(self, vid: int) -> bool:
        """(Re)open an unmounted volume from its on-disk files
        (VolumeMount analog). Returns False when no files exist.
        Volume() replays the index — potentially minutes — so it runs
        OUTSIDE the store lock (same discipline as remove_volume)."""
        import glob as _glob

        target: Optional[tuple[DiskLocation, str]] = None
        with self._lock:
            for loc in self.locations:
                if vid in loc.volumes:
                    return True  # already mounted
            for loc in self.locations:
                for path in _glob.glob(os.path.join(loc.directory, "*.dat")) + _glob.glob(
                    os.path.join(loc.directory, "*.tierinfo")
                ):
                    base = os.path.basename(path).rsplit(".", 1)[0]
                    parsed = parse_base_name(base)
                    if parsed is not None and parsed[1] == vid:
                        target = (loc, parsed[0])
                        break
                if target:
                    break
        if target is None:
            return False
        loc, collection = target
        v = Volume(loc.directory, vid, collection, needle_map_kind=self.needle_map_kind)
        with self._lock:
            if vid in loc.volumes:  # raced with another mount: keep theirs
                v.close()
            else:
                loc.volumes[vid] = v
        return True

    def expired_volume_ids(self) -> list[int]:
        """TTL volumes whose NEWEST write has aged out (the reference
        prunes ttl volumes the same way: .dat mtime is the last append,
        so mtime + ttl < now means every needle inside is past its TTL).
        Scan only — the volume server deletes under its per-volume
        maintenance mutex so a reap can never race a copy/encode."""
        import time as _time

        expired = []
        with self._lock:
            for loc in self.locations:
                for vid, v in loc.volumes.items():
                    ttl_s = v.super_block.ttl.seconds
                    if not ttl_s:
                        continue
                    mtime = v.last_modified()
                    if mtime and mtime + ttl_s < _time.time():
                        expired.append(vid)
        return expired

    def reap_expired_volumes(self) -> list[int]:
        """Standalone (no volume server) expiry pass, used by tests and
        local tools; servers go through expired_volume_ids() + their
        maintenance mutex instead."""
        expired = [
            vid
            for vid in self.expired_volume_ids()
            if (v := self.get_volume(vid)) is not None and not v.read_only
        ]
        for vid in expired:
            self.remove_volume(vid)
        return expired

    def volume_infos(self) -> list[dict]:
        out = []
        for loc in self.locations:
            # list(): heartbeats are composed beside mounts and deletes of
            # other volumes, and a dict that changes size under an iteration raises
            for vid, v in list(loc.volumes.items()):
                # lock-free snapshot: the heartbeat must not block behind a
                # long-running compaction's volume lock
                size, count, garbage = v.stats_snapshot()
                last_modified = v.last_modified()  # ec.encode -quietFor input
                out.append(
                    {
                        "id": vid,
                        "collection": v.collection,
                        "size": size,
                        "file_count": count,
                        "read_only": v.read_only,
                        "replica_placement": str(v.super_block.replica_placement),
                        "ttl": str(v.super_block.ttl),
                        "version": v.version,
                        "disk_type": "remote" if v.tiered else "",
                        "garbage_ratio": round(garbage, 4),
                        "last_modified": last_modified,
                    }
                )
        return out

    def ec_volume_infos(self) -> list[EcVolumeInfo]:
        out = []
        for loc in self.locations:
            for vid, ev in list(loc.ec_volumes.items()):
                parsed = parse_base_name(os.path.basename(ev.base))
                out.append(
                    EcVolumeInfo(
                        volume_id=vid,
                        collection=parsed[0] if parsed else "",
                        shard_bits=ShardBits.from_ids(ev.shard_ids),
                        shard_size=int(ev.shard_size or 0),
                        data_shards=int(ev.data_shards),
                        total_shards=int(ev.total_shards),
                    )
                )
        return out
