#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Default (one chip): start `python -m seaweedfs_tpu server -filer` as the ONE
process that owns the TPU, and drive the erasure-coding main path through
the entry points a user would call: upload a >= 1 GiB volume over HTTP,
`ec.encode` it, read needles back EC-intact, delete 4 of the 14 shards,
read needles back degraded (decoded on the device), `ec.rebuild`, read
again, SIGTERM. Every byte that comes back is compared with what went in;
parity is recomputed with the numpy gf8 reference. This parent never
imports jax: a parent that touched jax would hold the chip its child needs.

  python chip_smoke.py                      # the driver's call: one chip
  python chip_smoke.py --platform cpu --size-mib 8   # rehearsal without a chip:
        runs every phase, prints "ok": false and exits 1 — never ok:true
  python chip_smoke.py --chips 4            # ONLY the cross-chip path: the
        mesh backend's encode + ring/alltoall rebuild in this one process

Lines before the last are smoke timings ("smoke": true), not benchmark
numbers. The last line of stdout is one JSON object,
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as the chip-owning process's jax reported it.
"""

from __future__ import annotations

import argparse
import hashlib
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
import zlib

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
LOST = (0, 3, 11, 13)  # two data + two parity shards: the worst loss RS(10+4) allows
DEVICE_BACKENDS = ("jax", "pallas", "mesh")


class PhaseError(Exception):
    pass


def emit(**rec) -> None:
    print(json.dumps({"smoke": True, **rec}), flush=True)


def sha(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


def file_sha(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while chunk := f.read(8 << 20):
            h.update(chunk)
    return h.hexdigest()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


# -- the workload: seeded needles ---------------------------------------------


def needle_sizes(size_mib: int, rng) -> list[int]:
    """BASELINE.json config 1 ("ec.encode one 1 GB volume") as needles:
    4 MiB objects for ~98% of the bytes plus eight small ones (1 KiB..256
    KiB, log-uniform) per large one — 250 + 2,000 at the full 1 GiB."""
    n_large = max(1, size_mib // 4 - size_mib // 64)
    sizes = [4 << 20] * n_large
    for _ in range(8 * n_large):
        sizes.append(int(1024 * 256 ** rng.random()))
    total = size_mib << 20
    while sum(sizes) < total + (total >> 6):  # land safely past the target
        sizes.append(4 << 20 if total >= 64 << 20 else 256 << 10)
    rng.shuffle(sizes)
    return sizes


class Uploader:
    """Keep-alive HTTP PUTs to the volume server from a few threads; the
    payloads are made from the seed, needle by needle, and only their
    sha256 is kept."""

    def __init__(self, vs_url: str, fids: list[str], sizes: list[int], seed: int):
        self.vs_url, self.fids, self.sizes, self.seed = vs_url, fids, sizes, seed
        self.hashes: list[str] = [""] * len(fids)
        self.errors: list[str] = []
        self._next = 0
        self._lock = threading.Lock()

    @staticmethod
    def payload(seed: int, i: int, size: int) -> bytes:
        import numpy as np

        return np.random.default_rng([seed, i]).bytes(size)

    def _work(self) -> None:
        host, port = self.vs_url.split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=120)
        while not self.errors:
            with self._lock:
                i = self._next
                self._next += 1
            if i >= len(self.fids):
                break
            body = self.payload(self.seed, i, self.sizes[i])
            self.hashes[i] = sha(body)
            try:
                conn.request("PUT", "/" + self.fids[i], body=body)
                resp = conn.getresponse()
                resp.read()
                if resp.status not in (200, 201):
                    raise PhaseError(f"PUT {self.fids[i]} -> HTTP {resp.status}")
            except Exception as e:  # noqa: BLE001 — reported by run()
                self.errors.append(f"{type(e).__name__}: {e}")
        conn.close()

    def run(self, threads: int = 4) -> None:
        ts = [threading.Thread(target=self._work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if self.errors:
            raise PhaseError(self.errors[0])


def get_round(vs_url: str, picks: list[int], fids, hashes, expect: str = "") -> dict[int, str]:
    """GET each picked needle, sha256-compare; -> index -> read class.
    With `expect`, every read must have been served in that class."""
    host, port = vs_url.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=120)
    classes: dict[int, str] = {}
    for i in picks:
        conn.request("GET", "/" + fids[i])
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise PhaseError(f"GET {fids[i]} -> HTTP {resp.status}: {body[:200]!r}")
        if sha(body) != hashes[i]:
            raise PhaseError(f"GET {fids[i]}: {len(body)} bytes came back altered")
        classes[i] = resp.getheader("X-Weedtpu-Read-Class", "")
        if expect and classes[i] != expect:
            raise PhaseError(f"GET {fids[i]} was served {classes[i]!r}, want {expect!r}")
    conn.close()
    return classes


# -- checks against the files, by the repo's own format code ------------------


def ec_base(data_dir: str, vid: int) -> str:
    return os.path.join(data_dir, str(vid))


def needles_touching(base: str, lost_data: set[int]) -> set[int]:
    """Needle ids whose on-disk record crosses a data shard in `lost_data`,
    from the .ecx index and the .eci geometry (numpy-only repo code)."""
    from seaweedfs_tpu.ec import locate, stripe
    from seaweedfs_tpu.storage import idx, types

    info = stripe.read_ec_info(base)
    large, small, dat_size = (
        info["large_block_size"], info["small_block_size"], info["dat_size"],
    )
    hit: set[int] = set()

    def visit(key: int, stored_offset: int, size: int) -> None:
        if types.is_deleted(size):
            return
        ivs = locate.locate_data(
            large, small, dat_size,
            types.offset_to_actual(stored_offset), types.actual_size(size),
        )
        if any(iv.to_shard_id_and_offset(large, small)[0] in lost_data for iv in ivs):
            hit.add(key)

    idx.walk_index_file(base + ".ecx", visit)
    return hit


def parity_rows_match(base: str, seed: int, min_rows: int) -> tuple[int, int]:
    """Recompute the parity of 1 MiB shard rows with the numpy gf8 reference
    and byte-compare with the parity shards: every row up to 2 x min_rows,
    else a seeded sample of min_rows. -> (rows in a shard, rows checked)."""
    import random

    import numpy as np

    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ops import gf8

    row = 1 << 20
    n_rows = -(-os.path.getsize(stripe.shard_file_name(base, 0)) // row)
    rows = list(range(n_rows))
    if n_rows > 2 * min_rows:
        rows = sorted(random.Random(seed).sample(rows, min_rows))
    pm = gf8.parity_matrix(10, 4)
    files = [open(stripe.shard_file_name(base, s), "rb") for s in range(14)]
    try:
        for r in rows:
            stack = []
            for f in files:
                f.seek(r * row)
                stack.append(np.frombuffer(f.read(row), dtype=np.uint8))
            want = gf8.gf_mat_vec(pm, np.stack(stack[:10]))
            if not np.array_equal(want, np.stack(stack[10:])):
                raise PhaseError(f"parity of row {r} != gf8 reference")
    finally:
        for f in files:
            f.close()
    return n_rows, len(rows)


def check_shards(base: str, seed: int, min_rows: int = 64) -> dict:
    """14 shards + .ecx + .eci exist; every shard's CRC32 is the one .eci
    records; parity of sampled 1 MiB rows recomputed with the numpy gf8
    reference equals the parity shards' bytes."""
    from seaweedfs_tpu.ec import stripe

    for ext in [".ecx", ".eci"] + [stripe.to_ext(s) for s in range(14)]:
        if not os.path.exists(base + ext):
            raise PhaseError(f"missing {base + ext}")
    info = stripe.read_ec_info(base)
    crcs = info.get("shard_crc32")
    if not crcs or len(crcs) != 14:
        raise PhaseError(f".eci records no per-shard CRCs: {info}")
    shard_size = os.path.getsize(stripe.shard_file_name(base, 0))
    for s in range(14):
        crc = 0
        with open(stripe.shard_file_name(base, s), "rb") as f:
            while chunk := f.read(8 << 20):
                crc = zlib.crc32(chunk, crc)
        if crc != crcs[s]:
            raise PhaseError(f"shard {s}: CRC32 {crc:#x} != .eci {crcs[s]:#x}")
    n_rows, checked = parity_rows_match(base, seed, min_rows)
    return {"shard_size": shard_size, "parity_rows_checked": checked, "rows": n_rows}


# -- the chip-owning child ----------------------------------------------------


class Server:
    def __init__(self, platform: str, data_dir: str, log_path: str):
        self.log_path = log_path
        self.ports = {k: free_port() for k in ("master", "master_http", "volume", "filer")}
        env = dict(os.environ)
        # JAX_COMPILATION_CACHE_DIR is inherited untouched where it is set;
        # this script sets no cache directory: utils.devices decides in
        # each process that imports jax
        env["JAX_PLATFORMS"] = platform  # "tpu": jax itself cannot slide to the CPU
        env["JAX_LOG_COMPILES"] = "1"
        env["PYTHONUNBUFFERED"] = "1"
        if platform != "tpu":
            # rehearsal: drive the same XLA path the chip runs, on the CPU
            env["WEEDTPU_BACKEND"] = "jax"
        self.log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "seaweedfs_tpu", "server",
                "-dir", data_dir, "-filer",
                "-masterPort", str(self.ports["master"]),
                "-masterHttpPort", str(self.ports["master_http"]),
                "-port", str(self.ports["volume"]),
                "-filerPort", str(self.ports["filer"]),
            ],
            cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
        )
        self.master = f"127.0.0.1:{self.ports['master']}"
        self.master_http = f"127.0.0.1:{self.ports['master_http']}"
        self.vs_url = f"127.0.0.1:{self.ports['volume']}"
        self.filer = f"127.0.0.1:{self.ports['filer']}"
        self.vs_grpc = ""

    def log_text(self) -> str:
        self.log.flush()
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def wait_ready(self, timeout: float = 300.0) -> None:
        """A real write probe through the filer: it answers reads before the
        volume tier has heartbeated in, so only a 201 proves the stack."""
        deadline = time.monotonic() + timeout
        last = ""
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise PhaseError(f"server exited {self.proc.returncode} during start-up")
            try:
                req = urllib.request.Request(
                    f"http://{self.filer}/chip_smoke/probe.txt", data=b"probe", method="PUT"
                )
                with urllib.request.urlopen(req, timeout=10) as r:
                    if r.status in (200, 201):
                        break
                    last = f"HTTP {r.status}"
            except Exception as e:  # noqa: BLE001 — not up yet
                last = f"{type(e).__name__}: {e}"
            time.sleep(0.5)
        else:
            raise PhaseError(f"no write probe succeeded in {timeout:.0f}s (last: {last})")
        with urllib.request.urlopen(f"http://{self.filer}/chip_smoke/probe.txt", timeout=10) as r:
            if r.read() != b"probe":
                raise PhaseError("write probe read back altered")
        for line in self.log_text().splitlines():
            if line.startswith("server: ") and " grpc " in line:
                self.vs_grpc = line.split("volume http ")[1].split(" grpc ")[1].split(",")[0]
        if not self.vs_grpc:
            raise PhaseError("server log does not name the volume server's grpc address")

    def shell(self, script: str, timeout: float = 1500.0) -> str:
        """A tool child: always JAX_PLATFORMS=cpu — only the server owns the chip."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master", self.master, "-c", script],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
        )
        if p.returncode != 0:
            raise PhaseError(f"shell -c {script!r} exited {p.returncode}:\n{p.stdout}{p.stderr}")
        return p.stdout

    def stop(self) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise PhaseError("server did not leave within 30 s of SIGTERM") from None
        return rc

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.log.close()


def run_one_chip(args) -> int:
    import random

    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.utils import native

    phase = "preflight"
    t_all = time.monotonic()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(OUT_DIR, exist_ok=True)
    srv = None
    try:
        t0 = time.monotonic()
        native.build()
        data_dir = os.path.join(work, "data")
        os.makedirs(data_dir)
        emit(phase=phase, seconds=round(time.monotonic() - t0, 3),
             cache_dir_env=os.environ.get("JAX_COMPILATION_CACHE_DIR") or None)

        phase = "boot"
        t0 = time.monotonic()
        srv = Server(args.platform, data_dir, os.path.join(OUT_DIR, "server.log"))
        srv.wait_ready()
        sel = http_json(f"http://{srv.vs_url}/status")["ec_backend"]
        device = sel.get("device") or {}
        on_chip = device.get("platform") == "tpu" and sel.get("backend") in DEVICE_BACKENDS
        emit(phase=phase, seconds=round(time.monotonic() - t0, 3), ec_backend=sel)
        if args.platform == "tpu" and not on_chip:
            raise PhaseError(f"the server does not run the codec on a TPU: {sel}")

        phase = "load"
        t0 = time.monotonic()
        rng = random.Random(args.seed)
        sizes = needle_sizes(args.size_mib, rng)
        a = http_json(f"http://{srv.master_http}/dir/assign?count={len(sizes)}")
        if a.get("error") or int(a.get("count", 0)) < len(sizes):
            raise PhaseError(f"assign count={len(sizes)}: {a}")
        vid_s, rest = a["fid"].split(",")
        vid, key0, cookie = int(vid_s), int(rest[:-8], 16), rest[-8:]
        fids = [f"{vid},{key0 + i:x}{cookie}" for i in range(len(sizes))]
        up = Uploader(srv.vs_url, fids, sizes, args.seed)
        up.run()
        hashes = up.hashes
        dat = ec_base(data_dir, vid) + ".dat"
        dat_size = os.path.getsize(dat)
        if dat_size < args.size_mib << 20:
            raise PhaseError(f"volume {vid} holds {dat_size} bytes, want >= {args.size_mib} MiB")
        emit(phase=phase, seconds=round(time.monotonic() - t0, 3), needles=len(sizes),
             bytes=sum(sizes), volume_id=vid, dat_bytes=dat_size)

        phase = "encode"
        t0 = time.monotonic()
        out = srv.shell(f"lock; ec.encode -volumeId {vid} -force; unlock")
        t_enc = time.monotonic() - t0
        base = ec_base(data_dir, vid)
        if os.path.exists(dat):
            raise PhaseError(f"ec.encode left {dat} behind: the cut-over did not finish")
        checked = check_shards(base, args.seed)
        saved = {s: file_sha(stripe.shard_file_name(base, s)) for s in LOST}
        emit(phase=phase, seconds=round(t_enc, 3), check_seconds=round(time.monotonic() - t0 - t_enc, 3),
             bytes=dat_size, shell=out.strip().splitlines()[-2:-1], **checked)

        phase = "read_intact"
        t0 = time.monotonic()
        order = list(range(len(fids)))
        rng.shuffle(order)
        n_round = min(200, len(order) // 3)
        picks = [order[:n_round], order[n_round:2 * n_round], order[2 * n_round:3 * n_round]]
        get_round(srv.vs_url, picks[0], fids, hashes, expect="ec_intact")
        emit(phase=phase, seconds=round(time.monotonic() - t0, 3), needles=len(picks[0]),
             bytes=sum(sizes[i] for i in picks[0]))

        phase = "read_degraded"
        t0 = time.monotonic()
        from seaweedfs_tpu import rpc
        from seaweedfs_tpu.pb import VOLUME_SERVICE

        with rpc.RpcClient(srv.vs_grpc) as c:
            c.call(VOLUME_SERVICE, "VolumeEcShardsDelete",
                   {"volume_id": vid, "collection": "", "shard_ids": list(LOST)}, timeout=60)
        gone = [s for s in LOST if os.path.exists(stripe.shard_file_name(base, s))]
        if gone:
            raise PhaseError(f"shards {gone} survived VolumeEcShardsDelete")
        touching = needles_touching(base, {s for s in LOST if s < 10})
        classes = get_round(srv.vs_url, picks[1], fids, hashes)
        must = [i for i in picks[1] if key0 + i in touching]
        not_degraded = {fids[i]: classes[i] for i in must if classes[i] != "degraded"}
        if not must or not_degraded:
            raise PhaseError(
                f"{len(must)} sampled needles cross a lost shard; not served degraded: "
                f"{list(not_degraded.items())[:5]}"
            )
        emit(phase=phase, seconds=round(time.monotonic() - t0, 3), needles=len(picks[1]),
             bytes=sum(sizes[i] for i in picks[1]), degraded=len(must),
             degraded_bytes=sum(sizes[i] for i in must), lost_shards=list(LOST))

        phase = "rebuild"
        t0 = time.monotonic()
        out = srv.shell("lock; ec.rebuild; unlock")
        t_reb = time.monotonic() - t0
        for s in LOST:
            p = stripe.shard_file_name(base, s)
            if not os.path.exists(p) or file_sha(p) != saved[s]:
                raise PhaseError(f"rebuilt shard {s} differs from the one that was deleted")
        get_round(srv.vs_url, picks[2], fids, hashes, expect="ec_intact")
        emit(phase=phase, seconds=round(t_reb, 3), check_seconds=round(time.monotonic() - t0 - t_reb, 3),
             rebuilt_bytes=len(LOST) * checked["shard_size"], needles=len(picks[2]),
             shell=out.strip().splitlines()[-2:])

        phase = "shutdown"
        t0 = time.monotonic()
        sel_end = http_json(f"http://{srv.vs_url}/status")["ec_backend"]
        if sel_end != sel:
            raise PhaseError(f"the server's backend changed under the run: {sel} -> {sel_end}")
        rc = srv.stop()
        log = srv.log_text()
        if rc != 0:
            raise PhaseError(f"server exited {rc} on SIGTERM")
        if "Traceback (most recent call last)" in log:
            raise PhaseError("the server's log holds a traceback")
        programs = log.count("Finished XLA compilation of ")
        hits = log.count("Persistent compilation cache hit")
        emit(phase=phase, seconds=round(time.monotonic() - t0, 3), xla_programs=programs,
             cache_hits=hits, xla_compiles=programs - hits,
             cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache"),
             total_seconds=round(time.monotonic() - t_all, 3))
    except Exception as e:  # noqa: BLE001 — every failure is reported, none passed over
        print(f"chip_smoke: phase {phase} FAILED: {type(e).__name__}: {e}", flush=True)
        if srv is not None:
            print("---- last 40 lines of the server log ----", flush=True)
            print("\n".join(srv.log_text().splitlines()[-40:]), flush=True)
        print(json.dumps({"ok": False, "phase": phase}), flush=True)
        return 1
    finally:
        if srv is not None:
            srv.kill()
        shutil.rmtree(work, ignore_errors=True)
    if "jax" in sys.modules:
        print("chip_smoke: the parent imported jax: it would have held the chip", flush=True)
        return 1
    print(json.dumps({"ok": on_chip, "device": {
        "platform": device.get("platform"), "kind": device.get("kind"), "count": device.get("count"),
    }}), flush=True)
    return 0 if on_chip else 1


# -- --chips 4: the cross-chip path, in this one process ----------------------


def run_mesh(args) -> int:
    """The mesh backend's file pipelines over four devices, and what they
    are compared with: the single-device jax encoder on device 0, the
    serial numpy rebuild, and the gf8 reference. No server, no child."""
    import numpy as np

    from seaweedfs_tpu.utils import native

    native.build()
    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ops.rs_codec import Encoder
    from seaweedfs_tpu.utils.devices import describe_devices

    device = describe_devices()
    on_chip = device["platform"] == "tpu"
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax reports {device}", flush=True)
        return 1
    work = tempfile.mkdtemp(prefix="chip_smoke_mesh_")
    phase = "load"
    try:
        t0 = time.monotonic()
        dirs = {}
        for name in ("mesh", "single"):
            dirs[name] = os.path.join(work, name)
            os.makedirs(dirs[name])
        base = os.path.join(dirs["mesh"], "1")
        rng = np.random.default_rng(args.seed)
        size = (args.size_mib << 20) + 12345  # ragged tail: the padded last row
        with open(base + ".dat", "wb") as f:
            left = size
            while left > 0:
                n = min(left, 64 << 20)
                f.write(rng.bytes(n))
                left -= n
        single = os.path.join(dirs["single"], "1")
        os.link(base + ".dat", single + ".dat")
        emit(phase=phase, seconds=round(time.monotonic() - t0, 3), bytes=size, device=device)

        phase = "encode"
        t0 = time.monotonic()
        stripe.write_ec_files(single, encoder=Encoder(10, 4, backend="jax"))
        t_single = time.monotonic() - t0
        meshes = {v: Encoder(10, 4, backend="mesh", mesh_rebuild=v) for v in ("ring", "alltoall")}
        t0 = time.monotonic()
        stripe.write_ec_files(base, encoder=meshes["ring"])
        t_mesh = time.monotonic() - t0
        md = meshes["ring"]._mesh_dispatch()
        if md.last_spread != args.chips:
            raise PhaseError(f"staged batch lay on {md.last_spread} devices, want {args.chips}")
        want = {s: file_sha(stripe.shard_file_name(single, s)) for s in range(14)}
        for s in range(14):
            if file_sha(stripe.shard_file_name(base, s)) != want[s]:
                raise PhaseError(f"mesh shard {s} != single-device jax shard")
        _, pm_rows = parity_rows_match(base, args.seed, 16)
        emit(phase=phase, mesh_seconds=round(t_mesh, 3), single_device_seconds=round(t_single, 3),
             mesh_shape=md.shape_str(), devices_holding_batch=md.last_spread,
             parity_rows_checked=pm_rows, bytes=size)

        phase = "rebuild"
        timings = {}
        for variant, enc in meshes.items():
            for s in LOST:
                os.remove(stripe.shard_file_name(base, s))
            t0 = time.monotonic()
            rebuilt = stripe.rebuild_ec_files(base, encoder=enc)
            timings[variant] = round(time.monotonic() - t0, 3)
            spread = enc._mesh_dispatch().last_spread
            if sorted(rebuilt) != list(LOST) or spread != args.chips:
                raise PhaseError(f"{variant}: rebuilt {rebuilt} on {spread} devices")
            for s in LOST:
                if file_sha(stripe.shard_file_name(base, s)) != want[s]:
                    raise PhaseError(f"{variant}: rebuilt shard {s} differs")
        for s in LOST:
            os.remove(stripe.shard_file_name(single, s))
        t0 = time.monotonic()
        stripe.rebuild_ec_files_serial(single, encoder=Encoder(10, 4, backend="numpy"))
        timings["serial_numpy"] = round(time.monotonic() - t0, 3)
        for s in LOST:
            if file_sha(stripe.shard_file_name(single, s)) != want[s]:
                raise PhaseError(f"serial numpy rebuild of shard {s} differs")
        emit(phase=phase, seconds=timings, lost_shards=list(LOST))
    except Exception as e:  # noqa: BLE001 — reported, then exit 1
        print(f"chip_smoke: phase {phase} FAILED: {type(e).__name__}: {e}", flush=True)
        print(json.dumps({"ok": False, "phase": phase}), flush=True)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": on_chip, "device": device}), flush=True)
    return 0 if on_chip else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--size-mib", type=int, default=1024,
                    help="volume size; smaller is for rehearsal only")
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="JAX_PLATFORMS of the server child; cpu is a rehearsal and cannot print ok:true")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: only the cross-chip mesh path, in this process")
    args = ap.parse_args()
    if args.chips == 4:
        return run_mesh(args)
    return run_one_chip(args)


if __name__ == "__main__":
    sys.exit(main())
