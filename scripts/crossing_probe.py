#!/usr/bin/env python3
"""What a staged batch's way to the device and back costs, by the shape it
crosses in: the probe behind `rs_jax.apply_matrix`'s crossing rule.

A bulk EC run stages a batch as a `(C, N)` uint8 slot (ten survivor or data
rows of N bytes), hands it to the device, runs one GF(2^8) apply and syncs
the `(R, N)` result back. The TPU stores a 2-D uint8 array four rows to a
word and eight words to a tile, so `(10, N)` is held as sixteen rows and
`(1, N)` as four; the `(C * k, N / k)` view of the same bytes, k = 32 /
gcd(C, 32), is whole tiles. This script times each piece, on whatever
device jax has (a time from a CPU run is not a device number):

  line 1  jax.device_put of the slot as staged, `(C, N)`
  line 2  of `slot.reshape(C * k, N // k)`: the exact crossing (no copy)
  line 3  of `slot.reshape(-1)`: flat, no rows at all
  line 4  the C rows as C device_puts issued from C threads at once
  line 5  np.asarray (device wait + D2H) of a device `(1, N)`, `(k, N // k)`,
          `(N,)`, `(4, N)`, `(4 * k, N // k)` and `(4 * N,)` uint8 array
  line 6  one upload (line 1's) and one download (line 5's `(4, N)`) started
          together on two threads: do they share a queue?
  line 7  the programs: `rs_jax.gf_apply` / `gf_apply_tiled` on a slot that is
          on the device already, as `(C, N)` and as `(C * k, N // k)`, R = 4
          and R = 1; device time of each run from `jax.profiler` (the
          `XLA Modules` line of the device's plane; `null` where the backend
          has none, as the CPU's), wall beside it; results byte-exact against
          `ops/gf8` (all columns of a narrow slot; the first, the last and a
          middle window of a wide one)
  line 8  a whole batch as the pipelines pay it: device_put, the donated
          program, np.asarray: as staged and in the exact crossing, R = 4 and
          R = 1, one at a time and two in flight

Lines 1-6 and 8 are host wall to `block_until_ready` / the synced array, median
of `--repeats` after `--warmup`; rates are GB/s of DATA (C x N or R x N
bytes), never of tiles. Slots come from the staging pool (`stripe._ring_for`)
as the pipelines' do. `--widths` defaults to the rebuild's and the encode's
slot; `--buckets` adds line 7 and 8 at small-read widths (the served path's
`Encoder.RECONSTRUCT_BUCKETS`), R = 1.

  python scripts/crossing_probe.py                       # the chip: both slots
  JAX_PLATFORMS=cpu python scripts/crossing_probe.py --widths 32768 \\
      --buckets 4096 --repeats 2 --warmup 1              # a smoke, seconds
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

ROWS = 10  # RS(10+4): ten rows in, four (encode, worst loss) or one out
TILE = 65536  # Encoder.BLOCK_TILE: columns of one tile of the packed program


def timed(fn, repeats: int, warmup: int, before=None) -> float:
    """Median seconds of fn(arg), arg = before() made outside the timing."""
    out = []
    for i in range(warmup + repeats):
        arg = before() if before else None
        t0 = time.perf_counter()
        fn(arg)
        if i >= warmup:
            out.append(time.perf_counter() - t0)
    return statistics.median(out)


def device_times(run, repeats: int, program: str):
    """Median device seconds of the runs of `program` (`jit_<name>`) inside
    `repeats` calls of run(), from a profiler trace; None where the trace
    has no device plane (the CPU backend)."""
    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            for _ in range(repeats):
                run()
        finally:
            jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            return None
        took = []
        for plane in ProfileData.from_file(paths[-1]).planes:
            if not re.match(r"^/device:TPU:\d+$", plane.name):
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    took += [e.duration_ns / 1e9 for e in line.events
                             if e.name.split("(")[0] == program]
    return statistics.median(took) if took else None


def reference_windows(width: int):
    """Column windows of a slot to check against ops/gf8: everything of a
    narrow one; the first, a middle (across a tile seam) and the last of a
    wide one (the numpy reference does some 40 table look-ups a byte)."""
    if width <= 4 * TILE:
        return [(0, width)]
    mid = (width // 2 // TILE) * TILE
    return [(0, TILE), (mid - TILE // 2, mid + TILE // 2), (width - TILE, width)]


def reference(m: np.ndarray, slot: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """ops/gf8's answer for columns lo..hi: one matrix, or a stack's, tile by tile."""
    from seaweedfs_tpu.ops import gf8

    if m.ndim == 2:
        return gf8.gf_mat_vec(m, slot[:, lo:hi])
    cuts = [lo] + list(range((lo // TILE + 1) * TILE, hi, TILE)) + [hi]
    return np.concatenate(
        [gf8.gf_mat_vec(m[c0 // TILE], slot[:, c0:c1]) for c0, c1 in zip(cuts, cuts[1:])], axis=1)


def probe_width(width: int, rows_out: tuple, a) -> dict:
    import jax

    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ops import rs_jax
    from seaweedfs_tpu.ops.rs_codec import Encoder

    k = rs_jax.crossing_chunks(ROWS)
    rng = np.random.default_rng(a.seed + width)
    ring = stripe._ring_for(3, (ROWS, width))
    slots = [ring.take() for _ in range(3)]
    for s in slots:
        s[:] = rng.integers(0, 256, size=s.shape, dtype=np.uint8)
    slot = slots[0]
    data_bytes = slot.size
    out: dict = {"width": width, "k": k, "slot_bytes": data_bytes}
    rate = lambda nbytes, s: round(nbytes / s / 1e9, 4)  # noqa: E731

    def up(x):
        return jax.block_until_ready(jax.device_put(x))

    views = {"1_as_staged": slot, "2_exact": slot.reshape(ROWS * k, -1), "3_flat": slot.reshape(-1)}
    for name, v in views.items():
        s = timed(lambda _: up(v), a.repeats, a.warmup)
        out[f"line{name}"] = {"shape": list(v.shape), "ms": round(s * 1e3, 4), "GBps": rate(data_bytes, s)}
    with ThreadPoolExecutor(ROWS) as pool:
        s = timed(lambda _: list(pool.map(up, list(slot))), a.repeats, a.warmup)
    out["line4_rows_from_threads"] = {"ms": round(s * 1e3, 4), "GBps": rate(data_bytes, s)}

    salt = jax.jit(lambda x, i: x ^ i)
    counter = iter(range(1, 1 << 30))

    def fresh(shape):
        base = up(np.zeros(shape, dtype=np.uint8))
        return lambda: jax.block_until_ready(salt(base, np.uint8(next(counter) % 251)))

    for name, shape in (("1xN", (1, width)), ("kxN/k", (k, width // k)), ("N_flat", (width,)),
                        ("4xN", (4, width)), ("4kxN/k", (4 * k, width // k)), ("4N_flat", (4 * width,))):
        s = timed(np.asarray, a.repeats, a.warmup, before=fresh(shape))
        nbytes = int(np.prod(shape))
        out[f"line5_down_{name}"] = {"shape": list(shape), "ms": round(s * 1e3, 4), "GBps": rate(nbytes, s)}

    make = fresh((4, width))

    def both(dev):
        t = threading.Thread(target=np.asarray, args=(dev,))
        t.start()
        up(slot)
        t.join()

    s = timed(both, a.repeats, a.warmup, before=make)
    out["line6_up_and_down_at_once"] = {
        "ms": round(s * 1e3, 4),
        "alone_ms": [out["line1_as_staged"]["ms"], out["line5_down_4xN"]["ms"]],
    }

    enc = Encoder(ROWS, 4, backend="jax")

    def decode(lost):
        return enc.reconstruction_matrix([s for s in range(14) if s not in lost][:ROWS], lost)

    # worst legal loss and one shard; the stack's second half decodes another loss
    losses = {4: ([0, 3, 11, 13], [1, 2, 4, 12]), 1: ([5], [9])}
    programs = []
    for r in rows_out:
        m, other = decode(losses[r][0]), decode(losses[r][1])
        programs.append((f"flat_{r}row", m, rs_jax.gf_apply, rs_jax._gf_apply_donated,
                         rs_jax.lifted_matrix(m), "jit_gf_apply"))
        if width % (TILE * k) == 0:
            tiles = width // TILE
            stack = np.stack([m] * (tiles // 2) + [other] * (tiles - tiles // 2))
            b = jax.numpy.asarray(np.stack([rs_jax._lifted_host(rs_jax._matrix_key(t)) for t in stack]))
            programs.append((f"tiled_{r}row", stack, rs_jax.gf_apply_tiled,
                             rs_jax._gf_apply_tiled_donated, b, "jit__gf_apply_tiled_impl"))
    windows = reference_windows(width)
    exact_ok = True
    for name, m, plain, donated, b, jit_name in programs:
        want = [reference(m, slot, lo, hi) for lo, hi in windows]
        for form, view in (("as_staged", slot), ("exact", slot.reshape(ROWS * k, -1))):
            dev = up(view)
            got = np.asarray(plain(b, dev)).reshape(-1, width)
            same = all((got[:, lo:hi] == w).all() for (lo, hi), w in zip(windows, want))
            exact_ok &= same
            run = lambda: jax.block_until_ready(plain(b, dev))  # noqa: E731
            run()
            wall = timed(lambda _: run(), a.repeats, a.warmup)
            dev_s = device_times(run, a.repeats, jit_name)
            out[f"line7_{name}_{form}"] = {
                "in": list(view.shape), "device_ms": None if dev_s is None else round(dev_s * 1e3, 4),
                "wall_ms": round(wall * 1e3, 4), "byte_exact": bool(same),
            }

            def batch(x, b=b):
                return donated(b, jax.device_put(x)) if rs_jax.donation_supported() else plain(b, x)

            sync = lambda h: np.asarray(h).reshape(-1, width)  # noqa: E731
            s1 = timed(lambda _: sync(batch(view)), a.repeats, a.warmup)
            ring_views = [s.reshape(view.shape) for s in slots]

            def two_in_flight(_):
                # the pipelines' depth 2: dispatch i + 2, then sync i
                flight = [batch(ring_views[0]), batch(ring_views[1])]
                for i in range(2, 2 + a.repeats):
                    flight.append(batch(ring_views[i % 3]))
                    sync(flight.pop(0))
                for h in flight:
                    sync(h)

            s2 = timed(two_in_flight, 1, 1) / (a.repeats + 2)
            out[f"line8_{name}_{form}"] = {
                "one_at_a_time_ms": round(s1 * 1e3, 4), "two_in_flight_ms_a_batch": round(s2 * 1e3, 4),
                "GBps_up": rate(data_bytes, s2),
            }
    out["byte_exact"] = bool(exact_ok)
    ring.give_back()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--widths", type=int, nargs="*", default=[4194304, 6553600],
                    help="slot widths N of the (10, N) slots (default: the rebuild's and the encode's)")
    ap.add_argument("--buckets", type=int, nargs="*", default=[4096, 1 << 20],
                    help="small-read widths: lines 7 and 8 only count there, one row out")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    a = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    report = {
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
        "repeats": a.repeats, "slots": [], "buckets": [],
    }
    for w in a.widths:
        report["slots"].append(probe_width(w, (4, 1), a))
    for w in a.buckets:
        report["buckets"].append(probe_width(w, (1,), a))
    report["byte_exact"] = all(r["byte_exact"] for r in report["slots"] + report["buckets"])
    text = json.dumps(report, indent=1)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(text)
    for r in report["slots"] + report["buckets"]:
        print(f"--- (10, {r['width']}) k={r['k']} ---")
        for key, v in r.items():
            if key.startswith("line"):
                print(f"{key:40s} {json.dumps(v)}")
    print(json.dumps({"device": report["device"], "byte_exact": report["byte_exact"]}))
    return 0 if report["byte_exact"] else 1


if __name__ == "__main__":
    sys.exit(main())
