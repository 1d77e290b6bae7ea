"""What the bulk drivers share: the volume from the seed, the boot, the
warm-up mark, and GETs that are compared byte for byte."""

from __future__ import annotations

import http.client
import os
import random
import statistics
import time

from harness import checks, volumes
from harness.server import BenchError, say  # noqa: F401 — drivers take both from here

LOCK = "lock; {}; unlock"


def build_and_boot(run) -> None:
    """Volume from the seed, its sha256, a hard link to the original .dat
    (ec.encode deletes the volume's own), then the server over it."""
    vid = int(run.traffic.get("volume_id", 1))
    run.vid = vid
    with run.phase("volume"):
        run.ds = volumes.build(run.data_dir, vid, run.seed, run.dataset)
    run.base = os.path.join(run.data_dir, str(vid))
    run.orig_dat = os.path.join(run.work, "orig.dat")
    os.link(run.base + ".dat", run.orig_dat)
    with run.phase("dat_sha"):
        run.dat_sha = checks.file_sha(run.orig_dat)
    with run.phase("boot"):
        run.boot(vid)


def settle_disk() -> None:
    """Untimed: write back what set-up or an untimed restore left dirty, so that
    a timed command's own fsyncs do not wait for bytes it did not write."""
    os.sync()


def shell_noop_ms(run) -> None:
    """Wall of a shell child that does nothing but lock and unlock: median of 3."""
    walls = []
    for _ in range(3):
        t0 = time.monotonic()
        run.srv.shell("lock; unlock")
        walls.append(time.monotonic() - t0)
    run.facts["setup"]["shell_noop_ms"] = statistics.median(walls) * 1e3


def timed_op(run, op, known: dict) -> float:
    """Wall seconds of one timed command; the first `trace_ops` of a
    `--trace 1` run are traced, each as a stretch of its own."""
    with run.traced(known, on=len(run.timed) < int(run.traffic.get("trace_ops", 2))):
        t0 = time.monotonic()
        op(run)
        return time.monotonic() - t0


def get_round(run, picks: list[int], expect: str) -> int:
    """GET each picked needle over one connection; -> how many came back with
    another status than 200, other bytes than the seed gives, or another read
    class than `expect`."""
    host, port = run.srv.vs_url.split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=120)
    wrong = 0
    for i in picks:
        conn.request("GET", "/" + run.ds.fid(i))
        resp = conn.getresponse()
        body = resp.read()
        if (resp.status != 200 or body != run.ds.payload(i)
                or resp.getheader("X-Weedtpu-Read-Class", "") != expect):
            wrong += 1
    conn.close()
    return wrong


def sample_needles(run, n: int) -> list[int]:
    order = list(range(len(run.ds.keys)))
    random.Random(run.seed ^ 0x6E7).shuffle(order)
    return order[:n]


def flip_byte(path: str, seed: int) -> None:
    """The control's fault: one byte of a freshly written file, altered on disk."""
    size = os.path.getsize(path)
    at = random.Random(seed).randrange(size)
    with open(path, "r+b") as f:
        f.seek(at)
        b = f.read(1)
        f.seek(at)
        f.write(bytes([b[0] ^ 0x5A]))


def require(cond: bool, what: str) -> None:
    if not cond:
        raise BenchError(what)
