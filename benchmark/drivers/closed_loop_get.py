"""Traffic `closed_loop_get`: N clients, each GETs a uniformly random needle of
an EC volume, compares every byte, and asks again — upstream's `weed benchmark`
read phase. The clients are `processes` worker processes of `connections`
keep-alive connections each, so that the generator's own interpreter is not
the limit. With `lost_shards` in the configuration the shards are deleted
after `ec.encode`, and a read whose record crosses a lost data shard must have
been reconstructed (class `degraded`, or `cached` once the decoded-interval
cache holds it).

A reply counts as failed when its status is not 200, its bytes differ from what
the seed gives, or it crosses a lost data shard and was served as intact."""

from __future__ import annotations

import http.client
import multiprocessing
import os
import threading
import time

import numpy as np

from drivers import common
from harness import volumes

FAULTS = ("other_seed_pool",)
CLASSES = ["", "healthy", "ec_intact", "degraded", "cached"]
RECONSTRUCTED = ("degraded", "cached")


def _client(spec: dict, arrays, pool: bytes, rng, out: list) -> None:
    """One closed-loop client on one keep-alive connection."""
    host, port, vid, size = spec["host"], spec["port"], spec["vid"], spec["size"]
    t_end, limit = spec["t_end"], spec.get("limit")
    keys, cookies, offsets, must = (arrays[k] for k in ("keys", "cookies", "offsets", "must"))
    conn = http.client.HTTPConnection(host, port, timeout=120)
    starts, lats, klass, bad = [], [], [], 0
    n = len(keys)
    while limit is None or len(lats) < limit:
        t0 = time.monotonic()
        if t0 >= t_end:
            break
        i = int(rng.integers(n))
        try:
            conn.request("GET", f"/{vid},{int(keys[i]):x}{int(cookies[i]):08x}")
            resp = conn.getresponse()
            body = resp.read()
            status, c = resp.status, resp.getheader("X-Weedtpu-Read-Class", "")
        except (http.client.HTTPException, OSError):
            # the server dropped the connection: a failed GET, then a new connection
            status, c, body = 0, "", b""
            conn.close()
            conn = http.client.HTTPConnection(host, port, timeout=120)
        lat = time.monotonic() - t0
        o = int(offsets[i])
        if status != 200 or body != pool[o:o + size] or (must[i] and c not in RECONSTRUCTED):
            bad += 1
        starts.append(t0)
        lats.append(lat)
        klass.append(CLASSES.index(c) if c in CLASSES else 0)
    conn.close()
    out.append((starts, lats, klass, bad))


def worker(w: int, spec: dict, queue) -> None:
    """One client process: `connections` threads, each a closed loop."""
    arrays = np.load(spec["arrays"])
    pool = volumes.pool(spec["pool_seed"])
    out: list = []
    threads = [
        threading.Thread(target=_client, args=(
            spec, arrays, pool, np.random.default_rng([spec["seed"], w, c]), out))
        for c in range(spec["connections"])
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    queue.put((
        np.concatenate([np.array(o[0]) for o in out]), np.concatenate([np.array(o[1]) for o in out]),
        np.concatenate([np.array(o[2], dtype=np.int8) for o in out]), sum(o[3] for o in out),
    ))


def drive(run, seconds: float, limit: int | None = None, pool_seed: int | None = None):
    """Run the clients for `seconds` (or `limit` GETs each). -> starts, latencies,
    class codes, failures."""
    host, port = run.srv.vs_url.split(":")
    spec = {
        "arrays": run.arrays_path, "host": host, "port": int(port), "vid": run.vid,
        "size": int(run.dataset["object_bytes"]), "seed": run.seed + (0 if limit is None else 1),
        "pool_seed": run.seed if pool_seed is None else pool_seed,
        "connections": int(run.traffic["connections"]), "limit": limit,
        "t_end": time.monotonic() + seconds + float(run.traffic.get("start_lag_s", 1.0)),
    }
    ctx = multiprocessing.get_context("spawn")
    queue = ctx.Queue()
    procs = [ctx.Process(target=worker, args=(w, spec, queue)) for w in range(int(run.traffic["processes"]))]
    for p in procs:
        p.start()
    got = [queue.get(timeout=seconds + 300) for _ in procs]  # drain before joining
    for p in procs:
        p.join(timeout=60)
        common.require(not p.is_alive() and p.exitcode == 0, f"a client process ended {p.exitcode}")
    return (np.concatenate([g[0] for g in got]), np.concatenate([g[1] for g in got]),
            np.concatenate([g[2] for g in got]), sum(g[3] for g in got))


def setup(run) -> None:
    run.lost = [int(s) for s in run.config.get("lost_shards", [])]
    common.build_and_boot(run)
    with run.phase("encode"):
        run.srv.shell(common.LOCK.format(f"ec.encode -volumeId {run.vid} -force"))
    if run.lost:
        run.srv.delete_shards(run.vid, run.lost)
    block = int(run.config["code"]["small_block_bytes"])
    must = volumes.crossing(run.ds, [s for s in run.lost if s < 10], block)
    run.must = must
    run.arrays_path = os.path.join(run.work, "needles.npz")
    np.savez(run.arrays_path, keys=run.ds.keys, cookies=run.ds.cookies,
             offsets=run.ds.pool_offsets, must=must)
    with run.phase("warm_gets"):
        per_client = -(-int(run.traffic["warm_gets"]) // (
            int(run.traffic["processes"]) * int(run.traffic["connections"])))
        _, _, klass, bad = drive(run, 120.0, limit=per_client)
        run.warm_wrong = int(bad)
        if run.lost:
            common.require((klass == CLASSES.index("degraded")).any(), "no warm-up GET reconstructed")


def window(run) -> None:
    # the clients start together start_lag_s from now (spawned interpreters
    # import numpy first) and stop asking at t_end; a GET that has started is
    # finished. With --trace 1 a few seconds of the steady window are traced.
    lag = float(run.traffic.get("start_lag_s", 1.0))
    result = {}

    def go():
        pool_seed = run.seed + 1 if run.fault == "other_seed_pool" else None
        result["got"] = drive(run, run.seconds, pool_seed=pool_seed)

    t = threading.Thread(target=go)
    t.start()
    if run.trace:
        time.sleep(lag + min(2.0, run.seconds / 4))
        with run.traced({}):
            time.sleep(min(float(run.traffic.get("trace_seconds", 5.0)), run.seconds / 2))
    t.join()
    starts, lats, klass, bad = result["got"]
    run.attempted = len(lats)
    run.failed = int(bad)
    first, last_done = float(starts.min()), float((starts + lats).max())
    wall = last_done - first
    run.metrics["get_ops_per_s"] = (len(lats) - bad) / wall
    run.metrics["get_p95_ms"] = float(np.percentile(lats, 95)) * 1e3
    for code, name in enumerate(CLASSES):
        if name and (klass == code).any():
            run.facts["samples"][name] = lats[klass == code].tolist()
    shares = {name: round(float((klass == code).mean()), 4) for code, name in enumerate(CLASSES) if name}
    common.say(gets=len(lats), wall_s=round(wall, 3), class_share=shares,
               p50_ms=round(float(np.median(lats)) * 1e3, 3),
               p99_ms=round(float(np.percentile(lats, 99)) * 1e3, 3),
               must_reconstruct_share=round(float(run.must.mean()), 4))
    run.reconstructed = int(np.isin(klass, [CLASSES.index(c) for c in RECONSTRUCTED]).sum())


def verify(run) -> None:
    run.check("gets_wrong", run.failed, 0)
    run.check("warm_gets_wrong", run.warm_wrong, 0)
    if run.lost:
        # at least one read in the window went through the decode on the device
        run.check("window_without_reconstruction", 0 if run.reconstructed > 0 else 1, 0)
