#!/usr/bin/env python3
"""What a mesh batch's way back to the host costs, piece by piece: the probe
behind `parallel/backend.py` `MeshDispatch._restore` (not code a cell runs).

A bulk rebuild on the mesh backend dispatches a `(10, N)` slot over the
`dp x sp` devices and syncs a `(4, N)` result back. The program leaves it as
`(dp, 4, N / dp)` volumes sharded `P("dp", None, "sp")`: device (i, j) holds
columns `[i * N/dp + j * N/(dp*sp), ...)` of every row. This script times each
piece of bringing that back, on whatever devices jax has (a time from a CPU
run is not a device number), for the rebuild's slot on the default mesh
(2x2 on four chips) and the `ring` program:

  line 1  the shards' fetches, `np.asarray(shard.data)` one after the other
  line 2  started together (`copy_to_host_async` on each), then read
  line 3  read from as many threads as there are shards
  line 4  jax's assembly alone: a fresh `(dp, 4, N/dp)` and a copy of each
          fetched shard into it (what `np.asarray(global)` adds to line 1)
  line 5  the transposed copy alone: `(dp, 4, N/dp)` -> a fresh `(4, N)`
  line 6  `np.asarray(global)` and the transposed copy: the way back before
          this probe's PR, whole
  line 7  the straight copy alone: each fetched shard into its columns of a
          FRESH `(4, N)`; line 8 the same into a KEPT `(4, N)`
  line 9  line 2 and line 7: the whole way back into a fresh result
  line 10 line 2 and line 8: into a kept result
  line 11 `MeshDispatch.reconstruct` as it ships, `np.asarray(handle)`: the
          seconds `weedtpu_ec_mesh_seconds_total{stage="restore"}` counted,
          a batch

Every line is the median of `--repeats` after `--warmup` on a result whose
devices are done (a new dispatch before every repeat, outside the timing; no
host copy is cached anywhere), with the minor page faults of the process a
repeat beside it (`ru_minflt`: fresh pages show there). Each once on an idle
host and once beside the lanes' load: ten threads that `preadv` into a staging
slot's rows and eight that write rows to files. The timed thread is a worker
thread, as an RPC's is (malloc gives such a thread an arena of its own).
Results byte-exact against `ops/gf8` (the first, a middle and the last window
of the slot). What the host says of itself goes along: cores, `numactl
--hardware` where there is one, the NUMA nodes under `/sys`.

  python scripts/mesh_restore_probe.py --out chiprun_out/mesh_restore_probe.json
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      python scripts/mesh_restore_probe.py --width 262144 --repeats 2 --warmup 1
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import resource
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

ROWS, LOST = 10, (0, 3, 11, 13)  # RS(10+4), the worst legal loss
WINDOW = 65536


def timed(fn, repeats: int, warmup: int, before) -> dict:
    """Median ms of fn(before()), and the median of the process's minor page
    faults over a repeat (every thread's: beside the load it is the load's too)."""
    took, faults = [], []
    for i in range(warmup + repeats):
        arg = before()
        f0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        t0 = time.perf_counter()
        fn(arg)
        t1 = time.perf_counter()
        if i >= warmup:
            took.append(t1 - t0)
            faults.append(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f0)
    return {"ms": round(statistics.median(took) * 1e3, 4), "max_ms": round(max(took) * 1e3, 4),
            "minflt": int(statistics.median(faults))}


class LanesLoad:
    """Ten threads that preadv 1 MiB runs of a file into a slot's rows and
    eight that write 1 MiB rows to files of their own, until stopped: what the
    rebuild's shard lanes do beside a restore."""

    def __init__(self, directory: str, readers: int = 10, writers: int = 8, file_mib: int = 64):
        self._stop = threading.Event()
        self._dir = directory
        chunk = np.random.default_rng(49).integers(0, 256, size=1 << 20, dtype=np.uint8)
        self._src = os.path.join(directory, "survivor")
        with open(self._src, "wb") as f:
            for _ in range(file_mib):
                f.write(chunk)
        self._file_bytes = file_mib << 20
        self._chunk = chunk
        self.threads = [threading.Thread(target=self._read, args=(i,), daemon=True) for i in range(readers)]
        self.threads += [threading.Thread(target=self._write, args=(i,), daemon=True) for i in range(writers)]

    def _read(self, i: int) -> None:
        row = np.empty(4 << 20, dtype=np.uint8)
        fd = os.open(self._src, os.O_RDONLY)
        try:
            off = (i << 20) % self._file_bytes
            while not self._stop.is_set():
                for c in range(0, row.size, 1 << 20):
                    os.preadv(fd, [memoryview(row[c:c + (1 << 20)])], (off + c) % self._file_bytes)
                off = (off + row.size) % self._file_bytes
        finally:
            os.close(fd)

    def _write(self, i: int) -> None:
        with open(os.path.join(self._dir, f"rebuilt.{i}"), "wb") as f:
            while not self._stop.is_set():
                if f.tell() >= self._file_bytes:
                    f.seek(0)
                f.write(self._chunk)

    def __enter__(self):
        for t in self.threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        for t in self.threads:
            t.join()


def host_facts() -> dict:
    facts = {"cpus": os.cpu_count(), "affinity": len(os.sched_getaffinity(0)),
             "numa_nodes": sorted(os.path.basename(p) for p in glob.glob("/sys/devices/system/node/node[0-9]*"))}
    try:
        facts["numactl"] = subprocess.run(["numactl", "--hardware"], capture_output=True, text=True,
                                          timeout=10).stdout.strip().splitlines()
    except (OSError, subprocess.SubprocessError) as e:
        facts["numactl"] = f"not to be had: {e}"
    return facts


def probe(a) -> dict:
    import jax

    from seaweedfs_tpu import stats
    from seaweedfs_tpu.ops import gf8
    from seaweedfs_tpu.ops.rs_codec import Encoder
    from seaweedfs_tpu.parallel.backend import MeshDispatch, _copy_columns

    md = MeshDispatch(rebuild="ring")
    enc = Encoder(ROWS, 4, backend="numpy")
    survivors = [s for s in range(14) if s not in LOST][:ROWS]
    m = enc.reconstruction_matrix(survivors, list(LOST))
    rows, width = m.shape[0], a.width
    slot = np.random.default_rng(a.seed).integers(0, 256, size=(ROWS, width), dtype=np.uint8)
    wd = width // md.dp
    fn = md._rebuild_fn(m)
    placed = slot.reshape(ROWS, md.dp, wd).transpose(1, 0, 2)

    def done_result():
        """A new (dp, rows, wd) result whose devices are done: nothing of it on the host."""
        return jax.block_until_ready(fn.jitted(fn.place(placed)))

    def datas(dev):
        return [s.data for s in dev.addressable_shards]

    def fetched(dev):
        """(the shards' host arrays, each with the first flat column it holds)"""
        for d in datas(dev):
            d.copy_to_host_async()
        return [(np.asarray(s.data), (s.index[0].start or 0) * wd + (s.index[-1].start or 0))
                for s in dev.addressable_shards]

    def assembled(dev):
        return np.asarray(dev)

    def relaid(g):
        return np.ascontiguousarray(g.transpose(1, 0, 2).reshape(rows, width)[:, :width])

    def straight(parts, out):
        for host, c0 in parts:
            _copy_columns(out, c0, host[0])
        return out

    def assemble(parts):
        g = np.empty((md.dp, rows, wd), dtype=np.uint8)
        for host, c0 in parts:
            g[c0 // wd: c0 // wd + 1, :, c0 % wd: c0 % wd + host.shape[-1]] = host
        return g

    kept = np.empty((rows, width), dtype=np.uint8)
    pool = ThreadPoolExecutor(md.n_devices)
    restore_s = stats.EcMeshSeconds.labels("restore")

    def shipped(_):
        t0 = restore_s.value
        np.asarray(md.reconstruct(m, slot))
        shipped.took.append(restore_s.value - t0)

    shipped.took = []
    lines = {
        "line1_fetch_one_by_one": (lambda dev: [np.asarray(d) for d in datas(dev)], done_result),
        "line2_fetch_started_together": (fetched, done_result),
        "line3_fetch_from_threads": (lambda dev: list(pool.map(np.asarray, datas(dev))), done_result),
        "line4_assembly_alone": (assemble, lambda: fetched(done_result())),
        "line5_transposed_copy_alone": (relaid, lambda: assembled(done_result())),
        "line6_before_asarray_global_and_relay": (lambda dev: relaid(assembled(dev)), done_result),
        "line7_straight_copy_alone_fresh": (
            lambda parts: straight(parts, np.empty((rows, width), dtype=np.uint8)),
            lambda: fetched(done_result())),
        "line8_straight_copy_alone_kept": (lambda parts: straight(parts, kept), lambda: fetched(done_result())),
        "line9_whole_into_fresh": (
            lambda dev: straight(fetched(dev), np.empty((rows, width), dtype=np.uint8)), done_result),
        "line10_whole_into_kept": (lambda dev: straight(fetched(dev), kept), done_result),
        "line11_shipped_reconstruct_sync": (shipped, lambda: None),
    }

    # byte-exact: what ships, what it replaced and the probe's own pieces, against ops/gf8
    windows = [(0, WINDOW), (width // 2 - WINDOW // 2, width // 2 + WINDOW // 2), (width - WINDOW, width)] \
        if width > 4 * WINDOW else [(0, width)]
    want = [gf8.gf_mat_vec(m, slot[:, lo:hi]) for lo, hi in windows]
    handle = md.reconstruct(m, slot)
    got = {
        "shipped": np.asarray(handle),
        "before": relaid(assembled(done_result())),
        "straight_fresh": straight(fetched(done_result()), np.empty((rows, width), dtype=np.uint8)),
        "straight_kept": straight(fetched(done_result()), kept),
    }
    exact = {k: all((g[:, lo:hi] == w).all() for (lo, hi), w in zip(windows, want)) for k, g in got.items()}
    report = {"mesh": md.shape_str(), "variant": md.rebuild_variant, "slot": [ROWS, width],
              "result": [rows, width], "result_bytes": rows * width, "byte_exact": exact,
              "global_kept_no_host_copy": handle._dev._npy_value is None}

    def run_all(key: str) -> None:
        out = {}
        for name, (fn_, before) in lines.items():
            shipped.took = []
            out[name] = timed(fn_, a.repeats, a.warmup, before)
            if name.startswith("line11"):
                out[name]["restore_ms"] = round(statistics.median(shipped.took[a.warmup:]) * 1e3, 4)
        report[key] = out

    def measure() -> None:
        run_all("idle_host")
        with tempfile.TemporaryDirectory(dir=a.load_dir) as d, LanesLoad(d):
            time.sleep(0.2)
            run_all("beside_lanes_load")

    t = threading.Thread(target=measure, name="probe-rpc")
    t.start()
    t.join()
    pool.shutdown()
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--width", type=int, default=4194304, help="N of the (10, N) slot (default: the rebuild's)")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--seed", type=int, default=49)
    ap.add_argument("--load-dir", default=None, help="where the lanes' load keeps its files (default: the temp dir)")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    a = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
              "repeats": a.repeats, "host": host_facts()}
    report.update(probe(a))
    ok = all(report["byte_exact"].values()) and "beside_lanes_load" in report
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(json.dumps(report, indent=1))
    for key in ("idle_host", "beside_lanes_load"):
        print(f"--- {key}: mesh {report['mesh']} {report['variant']}, result {report['result']} ---")
        for name, v in report.get(key, {}).items():
            print(f"{name:44s} {json.dumps(v)}")
    print(json.dumps({k: report[k] for k in ("device", "host", "byte_exact", "global_kept_no_host_copy")}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
