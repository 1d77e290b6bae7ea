"""What the bulk drivers share: the volume from the seed, the boot, the
warm-up mark, the one place a bulk rate is made, and GETs that are compared
byte for byte."""

from __future__ import annotations

import http.client
import os
import random
import statistics
import time

from harness import checks, volumes
from harness.server import BenchError, say  # noqa: F401 — drivers take both from here

LOCK = "lock; {}; unlock"
STALL = 1.5  # a timed command is stalled where its wall is over this many medians of its run


def build_and_boot(run) -> None:
    """Volume from the seed, its sha256, hard links to the original .dat and .idx
    (ec.encode deletes the volume's own), then the server over it."""
    vid = int(run.traffic.get("volume_id", 1))
    run.vid = vid
    with run.phase("volume"):
        run.ds = volumes.build(run.data_dir, vid, run.seed, run.dataset)
    run.base = os.path.join(run.data_dir, str(vid))
    run.orig_dat = os.path.join(run.work, "orig.dat")
    os.link(run.base + ".dat", run.orig_dat)
    run.orig_idx = os.path.join(run.work, "orig.idx")
    os.link(run.base + ".idx", run.orig_idx)
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


def timed_facts(walls: list[float], bytes_per_op: int) -> dict:
    """What a run's timed commands say besides its metrics, for the result line:
    how many, their median and longest wall, how many took over `STALL` medians
    (one file call of the machine's disk that stalls shows here), and the rate
    the median command alone would give."""
    if not walls:
        return {"ops": 0, "median_s": None, "max_s": None, "stalled_ops": 0, "median_rate_MBps": None}
    median = statistics.median(walls)
    return {
        "ops": len(walls), "median_s": median, "max_s": max(walls),
        "stalled_ops": sum(1 for w in walls if w > STALL * median),
        "median_rate_MBps": bytes_per_op / 1e6 / median,
    }


def bulk_rate(run, op: str, bytes_per_op: int) -> None:
    """The one place a bulk cell's end-to-end metrics are made, from the walls of
    ALL the run's timed commands (each moves `bytes_per_op`): `<op>_MBps`, all
    bytes over all walls, so a command that stalls counts with all it took; and
    `<op>_cmd_p50_s`, the median wall, the latency of the operator's command.
    `run.py` prints those of the two that BENCHMARK.json lists for the cell.
    No metric where no command was timed."""
    run.facts["timed"] = timed_facts(run.timed, bytes_per_op)
    if run.timed:
        run.metrics[op + "_MBps"] = len(run.timed) * bytes_per_op / 1e6 / sum(run.timed)
        run.metrics[op + "_cmd_p50_s"] = statistics.median(run.timed)
    say(timed_ops=len(run.timed), timed_seconds=[round(t, 4) for t in run.timed], bytes_per_op=bytes_per_op)


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
