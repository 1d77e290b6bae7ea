"""The comparisons that decide `correct`, against a plain reference that
imports nothing of the program: the striping rule as arithmetic, the numpy
GF(2^8) golden of `reference/gf8_ref.py`, zlib's CRC32 and sha256."""

from __future__ import annotations

import hashlib
import json
import os
import random
import zlib

import numpy as np

from reference import gf8_ref

DATA, PARITY = 10, 4


def file_sha(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while chunk := f.read(8 << 20):
            h.update(chunk)
    return h.hexdigest()


def shard_path(base: str, shard: int) -> str:
    return f"{base}.ec{shard:02d}"


def read_eci(base: str) -> dict:
    with open(base + ".eci") as f:
        return json.load(f)


def check_shards(base: str, orig_dat: str, seed: int, min_rows: int) -> dict:
    """All 14 shards + .ecx + .eci exist; every shard's CRC32 is the one .eci
    records; for every row up to 2 x min_rows, else a seeded sample of
    min_rows: the ten data cells are the original .dat's bytes at the place
    the striping rule gives them, and the four parity cells are the gf8
    reference's. -> counts of what differed (all must be 0)."""
    missing = [e for e in [".ecx", ".eci"] + [f".ec{s:02d}" for s in range(DATA + PARITY)]
               if not os.path.exists(base + e)]
    if missing:
        return {"files_missing": len(missing), "crc_mismatches": -1,
                "data_cells_differing": -1, "parity_cells_differing": -1, "rows_checked": 0}
    info = read_eci(base)
    block = int(info["small_block_size"])
    dat_size = int(info["dat_size"])
    if dat_size >= DATA * int(info["large_block_size"]):
        raise ValueError("the reference's striping covers small-block rows only")
    n_rows = -(-dat_size // (DATA * block))
    crcs = info.get("shard_crc32") or []
    bad_crc = 0
    for s in range(DATA + PARITY):
        crc = 0
        with open(shard_path(base, s), "rb") as f:
            while chunk := f.read(8 << 20):
                crc = zlib.crc32(chunk, crc)
        size = os.path.getsize(shard_path(base, s))
        if len(crcs) != DATA + PARITY or crc != crcs[s] or size != n_rows * block:
            bad_crc += 1
    rows = list(range(n_rows))
    if n_rows > 2 * min_rows:
        rows = sorted(random.Random(seed).sample(rows, min_rows))
    pm = gf8_ref.parity_matrix(DATA, PARITY)
    bad_data = bad_parity = 0
    files = [open(shard_path(base, s), "rb") for s in range(DATA + PARITY)]
    try:
        with open(orig_dat, "rb") as dat:
            for r in rows:
                dat.seek(r * DATA * block)
                want = np.zeros(DATA * block, dtype=np.uint8)
                got = dat.read(DATA * block)
                want[:len(got)] = np.frombuffer(got, dtype=np.uint8)
                want = want.reshape(DATA, block)
                cells = []
                for f in files:
                    f.seek(r * block)
                    cells.append(np.frombuffer(f.read(block), dtype=np.uint8))
                have = np.stack(cells)
                bad_data += int((have[:DATA] != want).any(axis=1).sum())
                bad_parity += int((have[DATA:] != gf8_ref.gf_mat_vec(pm, want)).any(axis=1).sum())
    finally:
        for f in files:
            f.close()
    return {"files_missing": 0, "crc_mismatches": bad_crc, "data_cells_differing": bad_data,
            "parity_cells_differing": bad_parity, "rows_checked": len(rows),
            "rows": n_rows, "shard_bytes": n_rows * block}
