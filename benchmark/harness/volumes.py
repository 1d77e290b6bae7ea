"""Volumes made from the seed, written straight to `.dat/.idx` with the repo's
own `storage.Volume.write_needle` before the server boots (the store finds
`*.dat` when it loads). One general builder per dataset `kind`; a
configuration's file names the kind and its sizes.

The builder keeps, per needle, where its record lies in the `.dat`, so that
the benchmark can tell by plain arithmetic which reads must reconstruct."""

from __future__ import annotations

import dataclasses
import functools
import os
import random

import numpy as np

POOL_BYTES = 8 << 20


@dataclasses.dataclass
class Dataset:
    volume_id: int
    seed: int
    keys: np.ndarray      # uint64 needle ids
    cookies: np.ndarray   # uint32
    sizes: np.ndarray     # payload bytes
    starts: np.ndarray    # record start in the .dat
    ends: np.ndarray      # record end (before the next record's alignment)
    pool_offsets: np.ndarray | None = None  # kind pool1k: payload = pool[o:o+size]
    dat_bytes: int = 0

    def fid(self, i: int) -> str:
        return f"{self.volume_id},{int(self.keys[i]):x}{int(self.cookies[i]):08x}"

    def payload(self, i: int) -> bytes:
        if self.pool_offsets is not None:
            o = int(self.pool_offsets[i])
            return pool(self.seed)[o:o + int(self.sizes[i])]
        return mixed_payload(self.seed, i, int(self.sizes[i]))


def needle_sizes(size_mib: int, rng: random.Random) -> list[int]:
    """BASELINE.json config 1 ("ec.encode one 1 GB volume") as needles: 4 MiB
    objects for ~98% of the bytes plus eight small ones (1 KiB..256 KiB,
    log-uniform) per large one — 250 + 2,000 at the full 1 GiB. Copied from
    chip_smoke.py (PR 22)."""
    n_large = max(1, size_mib // 4 - size_mib // 64)
    sizes = [4 << 20] * n_large
    for _ in range(8 * n_large):
        sizes.append(int(1024 * 256 ** rng.random()))
    total = size_mib << 20
    while sum(sizes) < total + (total >> 6):  # land safely past the target
        sizes.append(4 << 20 if total >= 64 << 20 else 256 << 10)
    rng.shuffle(sizes)
    return sizes


def mixed_payload(seed: int, i: int, size: int) -> bytes:
    return np.random.default_rng([seed, i]).bytes(size)


@functools.lru_cache(maxsize=2)
def pool(seed: int) -> bytes:
    return np.random.default_rng([seed, 0x9001]).bytes(POOL_BYTES)


def build(data_dir: str, volume_id: int, seed: int, spec: dict) -> Dataset:
    """Write volume `volume_id` as `spec` (a configuration's `dataset`) says."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    kind = spec["kind"]
    rng = random.Random(seed)
    nrng = np.random.default_rng([seed, 0x5EED])
    pool_offsets = None
    if kind == "mixed4m":
        sizes = np.array(needle_sizes(int(spec["size_mib"]), rng), dtype=np.int64)
    elif kind == "pool1k":
        n = int(spec["files"])
        sizes = np.full(n, int(spec["object_bytes"]), dtype=np.int64)
        pool_offsets = nrng.integers(0, POOL_BYTES - int(spec["object_bytes"]), n, dtype=np.int64)
    else:
        raise ValueError(f"unknown dataset kind {kind!r}")
    n = len(sizes)
    keys = np.arange(1, n + 1, dtype=np.uint64)
    cookies = nrng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    ds = Dataset(volume_id, seed, keys, cookies, sizes,
                 np.zeros(n, dtype=np.int64), np.zeros(n, dtype=np.int64), pool_offsets)
    os.makedirs(data_dir, exist_ok=True)
    with Volume(data_dir, volume_id) as v:
        for i in range(n):
            offset, _ = v.write_needle(
                Needle(cookie=int(cookies[i]), id=int(keys[i]), data=ds.payload(i))
            )
            ds.starts[i] = offset
        dat_path = v.dat_path
    ds.dat_bytes = os.path.getsize(dat_path)
    # a record runs to where the next one starts; both are 8-byte aligned and
    # so is every block boundary, so alignment never adds a block
    ds.ends[:-1] = ds.starts[1:]
    ds.ends[-1] = ds.dat_bytes
    return ds


def crossing(ds: Dataset, lost_data: list[int], block: int, data_shards: int = 10) -> np.ndarray:
    """Which needles' records touch a `block`-byte row cell of a data shard in
    `lost_data`, by the striping rule alone: byte o of the .dat lies in shard
    (o // block) % data_shards. Holds while every row is a small-block row."""
    first = ds.starts // block
    last = (ds.ends - 1) // block
    hit = np.zeros(len(first), dtype=bool)
    for s in lost_data:
        hit |= first + ((s - first) % data_shards) <= last
    return hit
