"""North-star benchmark: RS(10+4) EC encode throughput, GB/s/chip, plus the
p50 shard-reconstruct latency (BASELINE.md configs 2 and 3).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Staged harness; every stage is a child process (BENCH_MODE=<stage>), so the
parent never imports jax and never holds the chip a child needs:

  device suite   first, on the default platform: compile-check the XLA
                 kernel, then sweep XLA and Pallas candidates on the chip.
                 Skipped only when the operator pinned JAX_PLATFORMS=cpu;
                 otherwise a run that finds no accelerator EXITS NON-ZERO —
                 it never falls back to the CPU and never republishes an
                 older device number.
  CPU suites     JAX_PLATFORMS=cpu children: XLA-on-CPU encode GB/s, numpy
                 golden-path GB/s, the native AVX2 library GB/s, p50/p99
                 single-needle reconstruct latency through the real
                 EcVolume degraded-read ladder, and the remote / trace /
                 ingest / xor / dp / mesh harnesses. Their numbers are
                 host numbers and are labelled `platform: "cpu"`.

Protocol per BASELINE.md: GB/s counts DATA bytes in (10 shards) / kernel
wall time with data device-resident. vs_baseline is value / 40.0 — the
fraction of the driver's 40 GB/s/chip target, since BASELINE.json.published
is empty (SURVEY.md §6: no reference numbers exist).
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

TARGET_GBPS = 40.0
WATCHDOG_SECS = int(os.environ.get("BENCH_WATCHDOG_SECS", "900"))
CPU_SUITE_SECS = int(os.environ.get("BENCH_CPU_SECS", "420"))


# ---------------------------------------------------------------------------
# child-process plumbing
# ---------------------------------------------------------------------------


def _run_child(mode: str, timeout: int, extra_env: dict | None = None):
    """Run this file with BENCH_MODE=mode; return (parsed JSON | None, err)."""
    env = dict(os.environ, BENCH_MODE=mode)
    if extra_env:
        env.update(extra_env)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            timeout=timeout,
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
        )
    except subprocess.TimeoutExpired:
        return None, f"timeout after {timeout}s"
    except Exception as e:  # noqa: BLE001
        return None, f"spawn failed: {e}"
    # stdout may carry jax warnings; the child's result is the last JSON line
    for line in reversed(proc.stdout.decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except json.JSONDecodeError:
                continue
    return None, f"exit={proc.returncode}, no JSON on stdout"


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# timing helpers (shared by cpu + device suites)
# ---------------------------------------------------------------------------


def _median_time(fn, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _measure_numpy_gbps() -> float:
    """Golden-path table-driven GF(2^8) encode on host numpy."""
    import numpy as np

    from seaweedfs_tpu.ops.rs_codec import Encoder

    enc = Encoder(10, 4, backend="numpy")
    n = 1 << 20
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(10, n), dtype=np.uint8)
    t = _median_time(lambda: enc._apply(enc.parity_matrix, data), iters=3, warmup=1)
    return 10 * n / t / 1e9


def _measure_avx2() -> tuple[float | None, bool, float | None, int]:
    """The native C++ library (AVX2 PSHUFB when the host supports it):
    (single-core GB/s, avx2?, all-cores GB/s, host core count). The MT
    split mirrors the reference codec's WithAutoGoroutines; on a 1-core
    host the two numbers coincide."""
    import numpy as np

    from seaweedfs_tpu.ops import gf8
    from seaweedfs_tpu.utils import native

    cores = os.cpu_count() or 1
    if native.load() is None:
        return None, False, None, cores
    n = 8 << 20
    rng = np.random.default_rng(0)
    bufs = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes() for _ in range(10)]
    pm = gf8.parity_matrix(10, 4)
    t = _median_time(
        lambda: native.gf_matrix_apply_native(pm, bufs, n), iters=5, warmup=1
    )
    mt_gbps = None
    if cores > 1 and native.has_mt():  # a stale pre-MT .so must not report
        t_mt = _median_time(  # a duplicate ST number as "-mt"
            lambda: native.gf_matrix_apply_native(pm, bufs, n, threads=0),
            iters=5,
            warmup=1,
        )
        mt_gbps = 10 * n / t_mt / 1e9
    return 10 * n / t / 1e9, native.has_avx2(), mt_gbps, cores


def _measure_xla_gbps(batch: int, n: int, iters: int, warmup: int) -> float:
    """Jitted bit-plane matmul encode on whatever device jax resolves."""
    import jax

    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf8, rs_jax

    parity_bits = rs_jax.lifted_matrix(gf8.parity_matrix(10, 4))

    @jax.jit
    def encode(data):
        return rs_jax.gf_apply(parity_bits, data)

    key = jax.random.PRNGKey(0)
    data = jax.block_until_ready(
        jax.random.randint(key, (batch, 10, n), 0, 256, dtype=jnp.uint8)
    )
    t = _median_time(lambda: jax.block_until_ready(encode(data)), iters, warmup)
    return batch * 10 * n / t / 1e9


def _measure_reconstruct_latency(tmpdir: str) -> dict:
    """p50/p99 single-needle degraded-read latency through the real EcVolume
    ladder (SURVEY §3.2): build a synthetic volume, stripe it, delete one
    data shard's file, then time reads that must reconstruct intervals from
    the 13 survivors. Cold = first read (builds+caches the decode matrix),
    warm = steady state."""
    import numpy as np

    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    from seaweedfs_tpu.ops.rs_codec import Encoder
    from seaweedfs_tpu.storage import idx as idx_mod
    from seaweedfs_tpu.storage import types

    enc = Encoder(10, 4, backend="numpy")
    large, small = 64 << 10, 4 << 10
    base = os.path.join(tmpdir, "bench_vol")
    rng = np.random.default_rng(7)
    offset = types.NEEDLE_PADDING_SIZE
    blobs = [b"\x03" + bytes(7)]
    records = {}
    for nid in range(1, 301):
        body = int(rng.integers(256, 4096))
        total = types.actual_size(body, version=3)
        records[nid] = (offset, body)
        blobs.append(rng.integers(0, 256, size=total, dtype=np.uint8).tobytes())
        offset += total
    with open(base + ".dat", "wb") as f:
        f.write(b"".join(blobs))
    idx_mod.write_entries(
        [(nid, types.offset_to_bytes(off), sz) for nid, (off, sz) in records.items()],
        base + ".idx",
    )
    stripe.write_ec_files(
        base, large_block_size=large, small_block_size=small, encoder=enc
    )
    stripe.write_sorted_file_from_idx(base)
    lost = 2
    os.unlink(stripe.shard_file_name(base, lost))  # lose one data shard

    recon_ms: list[float] = []
    local_ms: list[float] = []
    cold_ms = None
    with EcVolume(
        base, encoder=enc, large_block_size=large, small_block_size=small
    ) as ev:
        if ev.warm_thread is not None:
            ev.warm_thread.join(30)  # mount warmup precedes traffic (r4)
        for nid in records:
            # only reads whose intervals hit the lost shard exercise the
            # reconstruct ladder; the rest are the local-read baseline
            _, _, intervals = ev.locate_needle(nid)
            degraded = any(
                iv.to_shard_id_and_offset(large, small)[0] == lost
                for iv in intervals
            )
            t0 = time.perf_counter()
            ev.read_needle_blob(nid)
            dt = (time.perf_counter() - t0) * 1e3
            if degraded and cold_ms is None:
                cold_ms = dt  # first reconstruct builds+caches decode matrix
            elif degraded:
                recon_ms.append(dt)
            else:
                local_ms.append(dt)
    recon_ms.sort()
    local_ms.sort()

    def q(xs, p):
        return round(xs[min(len(xs) - 1, int(p * len(xs)))], 4) if xs else None

    return {
        "reconstruct_p50_ms": q(recon_ms, 0.50),
        "reconstruct_p99_ms": q(recon_ms, 0.99),
        "reconstruct_cold_ms": round(cold_ms, 4) if cold_ms is not None else None,
        "reconstruct_reads": len(recon_ms) + (cold_ms is not None),
        "local_read_p50_ms": q(local_ms, 0.50),
    }


# ---------------------------------------------------------------------------
# stage 2: CPU suite (child, JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------


def _measure_file_encode_e2e(td: str) -> dict:
    """BASELINE config-1 end-to-end: synthetic .dat file -> 14 shard files
    through write_ec_files (reads + kernel + writes + pipeline overlap),
    with the auto backend (native AVX2 on CPU, XLA bit-plane on TPU)."""
    import numpy as np

    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ops.rs_codec import new_encoder

    size = 128 << 20  # dat bytes; tmpfs-backed in most CI images
    base = os.path.join(td, "9")
    rng = np.random.default_rng(5)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    enc = new_encoder()
    t0 = time.perf_counter()
    stripe.write_ec_files(
        base,
        large_block_size=4 << 20,
        small_block_size=1 << 20,
        encoder=enc,
    )
    dt = time.perf_counter() - t0
    out = {
        "file_encode_e2e_gbps": round(size / dt / 1e9, 3),
        "file_encode_backend": enc.backend,
        "file_encode_dat_mib": size >> 20,
    }
    # pipeline-depth sweep: what the depth-N inflight pipeline buys over
    # the one-deep scheme on this host. The run above already measured the
    # configured default depth; the remaining depths are measured here
    # (skipping whichever of them the default already covered, so an env
    # override like WEEDTPU_PIPELINE_DEPTH=1 never overwrites or drops a
    # sweep point).
    sweep = {str(stripe.DEFAULT_PIPELINE_DEPTH): out["file_encode_e2e_gbps"]}
    for depth in (1, 2, 4):
        if str(depth) in sweep:
            continue
        try:
            t0 = time.perf_counter()
            stripe.write_ec_files(
                base,
                large_block_size=4 << 20,
                small_block_size=1 << 20,
                encoder=enc,
                pipeline_depth=depth,
            )
            sweep[str(depth)] = round(size / (time.perf_counter() - t0) / 1e9, 3)
        except Exception as e:  # noqa: BLE001 — one depth must not zero the sweep
            sweep[str(depth)] = f"error: {str(e)[:120]}"
    out["file_encode_depth_sweep_gbps"] = sweep
    return out


def _measure_rebuild(td: str) -> dict:
    """ec_rebuild_gbps (the north star's SECOND target: >=10x the AVX2
    baseline on a 1 TB volume set): rebuild a 4-missing-shard volume end
    to end through the pipelined `rebuild_ec_files` (slab reads + one
    fused-decode device dispatch per batch + one-deep read/compute
    overlap), vs the serial numpy golden path (one blocking reconstruct
    per chunk — the pre-pipeline shape).

    GB/s counts the volume's data footprint (DATA_SHARDS x shard bytes) /
    wall time, matching the encode protocol. Loss pattern: 2 data + 2
    parity shards — the worst loss count RS(10+4) allows."""
    import numpy as np

    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ec.constants import DATA_SHARDS_COUNT
    from seaweedfs_tpu.ops.rs_codec import Encoder, new_encoder
    from seaweedfs_tpu.utils import native as native_mod

    size = 128 << 20
    base = os.path.join(td, "rb")
    rng = np.random.default_rng(9)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    stripe.write_ec_files(
        base, large_block_size=4 << 20, small_block_size=1 << 20, encoder=new_encoder()
    )
    os.unlink(base + ".dat")
    missing = [0, 5, 11, 13]
    golden: dict[int, bytes] = {}
    for s in missing:
        with open(stripe.shard_file_name(base, s), "rb") as f:
            golden[s] = f.read()
    shard_size = len(golden[missing[0]])
    data_bytes = shard_size * DATA_SHARDS_COUNT

    def run(fn, enc, iters: int) -> tuple[float, bool]:
        """Best-of-`iters` rebuild wall time (first run swallows any XLA
        compile); outputs checked byte-identical against the survivors'
        original shard files after the last run."""
        times = []
        for _ in range(iters):
            for s in missing:
                os.unlink(stripe.shard_file_name(base, s))
            t0 = time.perf_counter()
            fn(base, encoder=enc, buffer_size=1 << 20)
            times.append(time.perf_counter() - t0)
        match = True
        for s in missing:
            with open(stripe.shard_file_name(base, s), "rb") as f:
                match = match and f.read() == golden[s]
        return data_bytes / min(times) / 1e9, match

    out: dict = {
        "dat_mib": size >> 20,
        "missing": missing,
        "protocol": "GB/s = data footprint (10 x shard bytes) / rebuild wall time",
    }
    serial, ok = run(stripe.rebuild_ec_files_serial, Encoder(10, 4, backend="numpy"), 2)
    out["numpy_serial_gbps"] = round(serial, 3)
    candidates: dict[str, float] = {}
    suite = [("numpy", Encoder(10, 4, backend="numpy"), 2)]
    if native_mod.load() is not None:
        suite.append(("native", Encoder(10, 4, backend="native"), 3))
    suite.append(("xla_cpu", Encoder(10, 4, backend="jax"), 3))
    for name, enc, iters in suite:
        try:
            gbps, match = run(stripe.rebuild_ec_files, enc, iters)
            out[f"{name}_gbps"] = round(gbps, 3)
            if not match:
                out[f"{name}_match"] = False  # a wrong rebuild is not a result
                continue
            candidates[name] = gbps
        except Exception as e:  # noqa: BLE001 — one backend must not zero the section
            out[f"{name}_error"] = str(e)[:200]
    if not ok:
        out["numpy_serial_match"] = False
    if candidates and serial > 0:
        best = max(candidates, key=candidates.get)
        out["best_backend"] = best
        out["pipelined_vs_serial"] = round(candidates[best] / serial, 2)
        # pipeline-depth sweep on the best backend: the depth-N inflight
        # rebuild pipeline vs the one-deep r5 scheme, same volume
        import functools

        enc_by_name = {name: e for name, e, _ in suite}
        sweep: dict = {}
        for depth in (1, 2, 4):
            try:
                gbps, match = run(
                    functools.partial(stripe.rebuild_ec_files, pipeline_depth=depth),
                    enc_by_name[best],
                    1,
                )
                sweep[str(depth)] = round(gbps, 3) if match else "mismatch"
            except Exception as e:  # noqa: BLE001 — one depth must not zero the sweep
                sweep[str(depth)] = f"error: {str(e)[:120]}"
        out["depth_sweep_gbps"] = sweep
    return out


def mode_cpu() -> None:
    import tempfile

    out: dict = {}
    try:
        out["xla_cpu_gbps"] = round(
            _measure_xla_gbps(batch=2, n=1 << 20, iters=5, warmup=2), 3
        )
    except Exception as e:  # noqa: BLE001
        out["xla_cpu_error"] = str(e)[:200]
    try:
        out["numpy_gbps"] = round(_measure_numpy_gbps(), 3)
    except Exception as e:  # noqa: BLE001
        out["numpy_error"] = str(e)[:200]
    try:
        gbps, avx2, mt_gbps, cores = _measure_avx2()
        out["host_cores"] = cores
        if gbps is not None:
            out["native_gbps"] = round(gbps, 3)
            out["native_avx2"] = avx2
        if mt_gbps is not None:
            out["native_mt_gbps"] = round(mt_gbps, 3)
    except Exception as e:  # noqa: BLE001
        out["native_error"] = str(e)[:200]
    try:
        with tempfile.TemporaryDirectory() as td:
            out.update(_measure_reconstruct_latency(td))
    except Exception as e:  # noqa: BLE001
        out["reconstruct_error"] = str(e)[:200]
    try:
        with tempfile.TemporaryDirectory() as td:
            out.update(_measure_file_encode_e2e(td))
    except Exception as e:  # noqa: BLE001
        out["file_encode_error"] = str(e)[:200]
    try:
        with tempfile.TemporaryDirectory() as td:
            out["ec_rebuild"] = _measure_rebuild(td)
    except Exception as e:  # noqa: BLE001
        out["ec_rebuild_error"] = str(e)[:200]
    try:
        from seaweedfs_tpu.ops.rs_codec import new_encoder

        # the factory's audited decision (evidence file, numbers, reason)
        out["auto_backend"] = new_encoder().selection
    except Exception as e:  # noqa: BLE001
        out["auto_backend_error"] = str(e)[:200]
    _emit(out)


# ---------------------------------------------------------------------------
# stage 2i: compiled XOR-schedule backend vs the native library (child)
# ---------------------------------------------------------------------------


def _min_time(fn, iters: int, warmup: int = 1) -> float:
    """min-of-iters wall time: the xorsched-vs-native gate is a SAME-RUN
    ratio on a shared noisy box, and min is the estimator least polluted
    by scheduler preemption (median still absorbs a slow neighbor)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _xor_matrix_forms(enc):
    """The four matrix shapes Encoder dispatches, as (name, matrix) —
    everything the schedule compiler must lower byte-exactly."""
    import numpy as np

    survivors = [i for i in range(14) if i not in (2, 11)][:10]
    decode = enc.reconstruction_matrix(survivors, [2, 11])
    plan = enc.repair_projection_plan(survivors, [2, 11])
    local = survivors[:5]  # a holder owning 5 of the survivors
    projection = np.stack([plan[s] for s in local], axis=1)
    delta = enc.parity_matrix[:, [3]]  # generator column: rank-1 update
    return [
        ("encode", enc.parity_matrix),
        ("decode", decode),
        ("projection", projection),
        ("delta", delta),
    ]


def mode_xor(smoke: bool = False) -> None:
    """BENCH_MODE=xor: the compiled XOR-schedule backend (ops/xorsched)
    vs the native AVX2 library, measured in the SAME run so the committed
    ratio is noise-immune (both numbers move with the box together).
    Compile and execute are reported separately — the schedule is built
    once per (matrix, tile) and cached, so steady-state cost is execute
    only. Every form is byte-verified against the gf8 numpy golden before
    any throughput number is trusted: `match` gates promotion in
    rs_codec.pick_cpu_backend. `--smoke` is the deterministic tier-1
    variant: byte-verification across tail-exercising widths, no timing
    (and no `when` stamp, so the output is stable run to run)."""
    import numpy as np

    from seaweedfs_tpu.ops import gf8, xorsched
    from seaweedfs_tpu.ops.rs_codec import Encoder, _host_fingerprint
    from seaweedfs_tpu.utils import config, native

    enc = Encoder(10, 4, backend="numpy")  # matrices only; no dispatch here
    forms = _xor_matrix_forms(enc)
    out: dict = {
        "host": _host_fingerprint(),
        "native_level": xorsched.native_level(),
        "tile_kb": config.env("WEEDTPU_XORSCHED_TILE_KB"),
    }
    if not smoke:
        out["when"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())

    # compile pass: fresh cache, per-form compile time + schedule stats
    xorsched.clear_schedule_cache()
    compile_info: dict = {}
    progs: dict = {}
    for name, m in forms:
        t0 = time.perf_counter()
        prog = xorsched.get_schedule(m)
        compile_info[f"{name}_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        compile_info[f"{name}_xors"] = prog.xor_count
        compile_info[f"{name}_raw_xors"] = prog.raw_xors
        compile_info[f"{name}_temps"] = prog.n_temps
        progs[name] = prog
    out["compile"] = compile_info

    # byte-verification: interpreter AND native executor vs the gf8 golden,
    # across widths that exercise full tiles, partial tiles, and the
    # sub-8-symbol scalar tails
    match = True
    verify: dict = {}
    widths = [1, 7, 31, 512, 4097] if smoke else [4097, 65536 + 488]
    rng = np.random.default_rng(0)
    for name, m in forms:
        ok = True
        for n in widths:
            stack = rng.integers(0, 256, size=(m.shape[1], n), dtype=np.uint8)
            golden = gf8.gf_mat_vec(m, stack)
            interp = np.stack(xorsched.apply(progs[name], list(stack)))
            ok = ok and bool((interp == golden).all())
            nat = xorsched.apply_native(progs[name], list(stack))
            if nat is not None:
                ok = ok and bool((np.stack(nat) == golden).all())
        verify[name] = ok
        match = match and ok
    out["verify"] = verify
    out["match"] = match
    out["cache"] = xorsched.schedule_cache_info()
    if smoke:
        out["ok"] = match
        _emit(out)
        return

    # throughput: xorsched native executor vs the AVX2 library, same data,
    # same run, min-of-iters (GB/s counts INPUT shard bytes / wall time,
    # matching _measure_avx2's convention)
    n = 8 << 20
    have_native_lib = native.load() is not None
    for name, m in forms:
        if name == "delta":
            continue  # 1-column rank-1 update: latency path, not bandwidth
        stack = rng.integers(0, 256, size=(m.shape[1], n), dtype=np.uint8)
        sec: dict = {}
        if xorsched.native_available():
            ins = list(stack)
            t = _min_time(lambda: xorsched.apply_native(progs[name], ins), iters=5)
            sec["xorsched_gbps"] = round(m.shape[1] * n / t / 1e9, 3)
        if have_native_lib:
            bufs = [s.tobytes() for s in stack]
            t = _min_time(
                lambda: native.gf_matrix_apply_native(m, bufs, n), iters=5
            )
            sec["native_gbps"] = round(m.shape[1] * n / t / 1e9, 3)
        if "xorsched_gbps" in sec and "native_gbps" in sec:
            sec["ratio"] = round(sec["xorsched_gbps"] / sec["native_gbps"], 2)
        out[name] = sec

    # the interpreter floor, small width + one iter: it exists as the
    # byte-exact oracle and stale-.so fallback, not as a fast path
    small = rng.integers(0, 256, size=(10, 1 << 20), dtype=np.uint8)
    ins_small = list(small)
    t = _min_time(lambda: xorsched.apply(progs["encode"], ins_small), iters=1, warmup=0)
    out["encode"]["interp_gbps"] = round(10 * (1 << 20) / t / 1e9, 3)

    enc_sec = out.get("encode", {})
    dec_sec = out.get("decode", {})
    out["gate"] = {
        "encode_2x": bool(enc_sec.get("ratio", 0) >= 2.0),
        "decode_parity": bool(dec_sec.get("ratio", 0) >= 1.0),
    }
    _emit(out)


# ---------------------------------------------------------------------------
# stage 2c: remote degraded-read ladder (child, JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------


def mode_remote() -> None:
    """Two-server remote ladder (SURVEY §3.2 end to end), run twice:

    raw            loopback as-is. On THIS 1-core host a 'remote fetch'
                   costs CPU, not network, so the degraded read's parallel
                   survivor fan-out cannot reduce wall time here — the
                   numbers quantify per-fetch framing cost.
    simulated RTT  5 ms server-side delay per VolumeEcShardRead (models
                   the network that dominates real clusters; sleeping
                   releases the GIL, so overlap IS measurable on 1 core).
                   Done-criterion home: reconstruct_remote p50 should sit
                   within ~2x plain-remote p50 when fetches overlap.
    """
    out: dict = dict(_remote_ladder(delay_ms=0, n_fids=200))
    out["simulated_rtt_5ms"] = _remote_ladder(delay_ms=5, n_fids=100)
    out["host_cores"] = os.cpu_count()
    _emit(out)


def _remote_ladder(delay_ms: int, n_fids: int) -> dict:
    """One ladder pass: master + in-process owner + SUBPROCESS peer;
    EC-encode a volume on the owner, hand shards 7-13 to the peer, then
    time reads through the owner's HTTP data path in three classes:
      local    — every interval on the owner's own shards
      remote   — >=1 interval fetched from the peer via pooled
                 VolumeEcShardRead
      reconstruct_remote — a shard deleted everywhere: the owner
                 reconstructs from survivors, >=4 of them remote
    This is the path r3 could not measure (uncached lookups + per-read
    dials would have dominated; both are fixed in r4); the peer became a
    subprocess in r5 so owner-side fetch concurrency is not serialized
    against the peer's serving threads by the GIL."""
    import socket
    import subprocess
    import tempfile
    import urllib.request

    import jax  # noqa: F401


    import numpy as np

    from seaweedfs_tpu import rpc
    from seaweedfs_tpu.cluster.client import MasterClient
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.pb import VOLUME_SERVICE
    from seaweedfs_tpu.cluster.volume_server import VolumeServer

    out: dict = {}
    large, small = 64 << 10, 4 << 10
    peer_proc = None
    with tempfile.TemporaryDirectory() as td:
        master = MasterServer(port=0, reap_interval=3600)
        master.start()
        # The OWNER runs in-process (the read path under test). The PEER is
        # a real subprocess: with both nodes in one interpreter the GIL
        # serializes the degraded read's parallel survivor fetches against
        # the peer's own serving threads, hiding exactly the concurrency
        # the ladder exists to measure.
        d0 = os.path.join(td, "srv0")
        os.makedirs(d0)
        owner_vs = VolumeServer([d0], master.address, heartbeat_interval=0.3)
        owner_vs.start()
        d1 = os.path.join(td, "srv1")
        os.makedirs(d1)

        def _free_port() -> int:
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        def _start_peer():
            """Launch the peer volume server subprocess (after the upload
            phase, so the benched volume deterministically lives on the
            in-process owner) and wait for its gRPC surface."""
            import grpc as _grpc

            peer_http, peer_grpc = _free_port(), _free_port()
            env = {**os.environ, "JAX_PLATFORMS": "cpu"}
            if delay_ms:
                env["WEEDTPU_BENCH_RPC_DELAY_MS"] = str(delay_ms)
            err_path = os.path.join(td, "peer.err")
            proc = subprocess.Popen(
                [
                    sys.executable, "-m", "seaweedfs_tpu", "volume",
                    "-port", str(peer_http), "-grpcPort", str(peer_grpc),
                    "-dir", d1, "-mserver", master.address,
                ],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                env=env,
                stdout=subprocess.DEVNULL,
                stderr=open(err_path, "wb"),
            )
            addr = f"127.0.0.1:{peer_grpc}"
            try:
                deadline0 = time.monotonic() + 60
                while True:
                    if proc.poll() is not None:  # died at startup: say why
                        with open(err_path, "rb") as ef:
                            tail = ef.read()[-500:].decode(errors="replace")
                        raise RuntimeError(
                            f"peer exited rc={proc.returncode}: {tail}"
                        )
                    if time.monotonic() > deadline0:
                        raise RuntimeError("peer not serving after 60s")
                    try:
                        with rpc.RpcClient(addr) as pc:
                            pc.call(
                                VOLUME_SERVICE, "VolumeStatus",
                                {"volume_id": 999999}, timeout=5,
                            )
                        break
                    except _grpc.RpcError as e:
                        if e.code() == _grpc.StatusCode.NOT_FOUND:
                            break  # server answered: it is up
                        time.sleep(0.5)
            except Exception:
                proc.terminate()  # never leak the subprocess on a failed start
                raise
            return proc, addr
        client = MasterClient(master.address)
        try:
            rng = np.random.default_rng(11)
            first = client.submit(rng.integers(0, 256, 8192, dtype=np.uint8).tobytes())
            vid = int(first.fid.split(",")[0])
            fids = [first.fid]
            while len(fids) < n_fids:
                a = client.assign()
                if int(a.fid.split(",")[0]) != vid:
                    continue
                size = int(rng.integers(512, 6000))
                client.upload(a.fid, rng.integers(0, 256, size, dtype=np.uint8).tobytes())
                fids.append(a.fid)
            owner = owner_vs
            assert owner.store.get_volume(vid) is not None, "volume not on owner"
            peer_proc, peer_grpc_addr = _start_peer()
            with rpc.RpcClient(owner.grpc_address) as oc:
                oc.call(VOLUME_SERVICE, "VolumeMarkReadonly", {"volume_id": vid})
                oc.call(VOLUME_SERVICE, "VolumeEcShardsGenerate",
                        {"volume_id": vid, "large_block_size": large,
                         "small_block_size": small})
            with rpc.RpcClient(peer_grpc_addr) as tc:
                tc.call(VOLUME_SERVICE, "VolumeEcShardsCopy",
                        {"volume_id": vid, "shard_ids": list(range(7, 14)),
                         "source_data_node": owner.grpc_address}, timeout=120)
            base = owner._base_path_for(vid)
            with rpc.RpcClient(owner.grpc_address) as oc:
                for s in range(7, 14):
                    os.remove(stripe.shard_file_name(base, s))
                oc.call(VOLUME_SERVICE, "VolumeDelete", {"volume_id": vid})
                oc.call(VOLUME_SERVICE, "VolumeEcShardsMount", {"volume_id": vid})
            with rpc.RpcClient(peer_grpc_addr) as pc:
                pc.call(VOLUME_SERVICE, "VolumeEcShardsMount", {"volume_id": vid})
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if len(master.topology.lookup_ec_shards(vid)) == 14:
                    break
                time.sleep(0.05)

            ev = owner.store.get_ec_volume(vid)
            lost = 3  # will be deleted everywhere for the reconstruct class

            from seaweedfs_tpu.storage.file_id import FileId

            def shard_ids_of(fid: str) -> set:
                nid = FileId.parse(fid).key
                _, _, ivs = ev.locate_needle(nid)
                return {iv.to_shard_id_and_offset(large, small)[0] for iv in ivs}

            classes: dict[str, list[str]] = {"local": [], "remote": [], "reconstruct_remote": []}
            for fid in fids:
                try:
                    sids = shard_ids_of(fid)
                except Exception:  # noqa: BLE001
                    continue
                if lost in sids:
                    classes["reconstruct_remote"].append(fid)
                elif any(s >= 7 for s in sids):
                    classes["remote"].append(fid)
                else:
                    classes["local"].append(fid)

            def read_via_owner(fid: str) -> bytes:
                with urllib.request.urlopen(
                    f"http://{owner.url}/{fid}", timeout=30
                ) as r:
                    return r.read()

            def time_class(fids_: list[str]) -> dict | None:
                if not fids_:
                    return None
                for f in fids_[:2]:
                    read_via_owner(f)  # warm compile/caches
                ms = []
                for _ in range(3):
                    for f in fids_:
                        t0 = time.perf_counter()
                        read_via_owner(f)
                        ms.append((time.perf_counter() - t0) * 1e3)
                ms.sort()
                return {
                    "p50_ms": round(ms[len(ms) // 2], 3),
                    "p99_ms": round(ms[min(len(ms) - 1, int(0.99 * len(ms)))], 3),
                    "n_reads": len(ms),
                }
            out["local"] = time_class(classes["local"])
            out["remote"] = time_class(classes["remote"])
            # now lose shard 3 everywhere: reads touching it reconstruct.
            # Owner holds 0..6 so it keeps 6 local survivors and must
            # fan out for >=4 remote ones — the parallel-fetch path.
            p = stripe.shard_file_name(owner._base_path_for(vid), lost)
            if os.path.exists(p):
                os.remove(p)
            evv = owner.store.get_ec_volume(vid)
            if evv is not None:
                evv.drop_local_shard(lost)
            out["reconstruct_remote"] = time_class(classes["reconstruct_remote"])
            out["class_sizes"] = {k: len(v) for k, v in classes.items()}
            out["peer"] = "subprocess"  # true parallelism, no shared GIL
        finally:
            client.close()
            owner_vs.stop()
            if peer_proc is not None:
                peer_proc.terminate()
                try:
                    peer_proc.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    peer_proc.kill()
            master.stop()
    return out


# ---------------------------------------------------------------------------
# stage 2e: remote-survivor distributed rebuild (child, JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------


def mode_rebuild_remote() -> None:
    """The distributed half of the >=10x rebuild target: survivors live on a
    PEER volume server and the rebuild target streams them through the
    network-overlapped pipeline (VolumeEcShardSlabRead + RemoteSlabSource
    prefetch) while decoding. Reports local-vs-remote GB/s, the overlap
    efficiency (remote wall / max(network wall, decode wall) — 1.0 is
    perfect overlap), and the speedup over a serial fetch-then-decode
    remote baseline (same windows, same parallel fetch, no overlap)."""
    import tempfile

    import jax  # noqa: F401

    with tempfile.TemporaryDirectory() as td:
        _emit(_measure_rebuild_remote(td))


def _measure_rebuild_remote(
    td: str,
    dat_bytes: int = 48 << 20,
    large: int = 4 << 20,
    small: int = 1 << 20,
    buffer_size: int = 128 << 10,
    max_batch_bytes: int = 4 << 20,
    prefetch_batches: int = 4,
    delay_ms: float | None = None,
    encoder=None,
) -> dict:
    """Two in-process volume servers + master: the peer holds data shards
    0-9, parity 10-13 is lost cluster-wide, and the (initially empty)
    rebuild target regenerates it via `VolumeEcShardsRebuild {remote:true}`.

    On this 1-core loopback host a remote fetch costs CPU, not network, so
    a server-side per-RPC sleep models the RTT real clusters pay
    (WEEDTPU_BENCH_RPC_DELAY_MS, the ladder bench's trick — sleeping
    releases the GIL, so overlap IS measurable). When `delay_ms` is None
    it is auto-tuned so the modeled network wall ~= the measured decode
    wall — the regime the repair literature says dominates at scale and
    exactly where overlap pays; the chosen value is recorded."""
    import shutil

    import numpy as np

    from seaweedfs_tpu import rpc
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ec.constants import DATA_SHARDS_COUNT
    from seaweedfs_tpu.pb import VOLUME_SERVICE

    vid = 7
    missing = [10, 11, 12, 13]
    out: dict = {
        "dat_mib": dat_bytes >> 20,
        "missing": missing,
        "protocol": (
            "GB/s = data footprint (10 x shard bytes) / rebuild wall; "
            "overlap_efficiency = remote wall / max(network wall, decode "
            "wall), 1.0 = perfect overlap; serial baseline = same windowed "
            "parallel fetch, decode blocking between windows (no overlap)"
        ),
    }
    prev_delay = os.environ.get("WEEDTPU_BENCH_RPC_DELAY_MS")

    def set_delay(ms: float) -> None:
        if ms > 0:
            os.environ["WEEDTPU_BENCH_RPC_DELAY_MS"] = str(ms)
        else:
            os.environ.pop("WEEDTPU_BENCH_RPC_DELAY_MS", None)

    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    d_target, d_peer = os.path.join(td, "target"), os.path.join(td, "peer")
    os.makedirs(d_target)
    os.makedirs(d_peer)
    set_delay(0)  # no delay during setup/copies
    target = VolumeServer(
        [d_target], master.address, heartbeat_interval=0.3, encoder=encoder
    )
    target.start()
    peer = VolumeServer([d_peer], master.address, heartbeat_interval=0.3)
    peer.start()
    try:
        # -- build the volume on the peer, lose all parity everywhere ------
        base_peer = os.path.join(d_peer, str(vid))
        rng = np.random.default_rng(13)
        with open(base_peer + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, dat_bytes, dtype=np.uint8).tobytes())
        with open(base_peer + ".idx", "wb"):
            pass
        stripe.write_ec_files(
            base_peer,
            large_block_size=large,
            small_block_size=small,
            encoder=target.store.encoder,
        )
        stripe.write_sorted_file_from_idx(base_peer)
        golden = {}
        for s in missing:
            with open(stripe.shard_file_name(base_peer, s), "rb") as f:
                golden[s] = f.read()
        shard_size = os.path.getsize(stripe.shard_file_name(base_peer, 0))
        data_bytes = shard_size * DATA_SHARDS_COUNT
        for s in missing:
            os.unlink(stripe.shard_file_name(base_peer, s))
        os.unlink(base_peer + ".dat")
        with rpc.RpcClient(peer.grpc_address) as pc:
            pc.call(VOLUME_SERVICE, "VolumeEcShardsMount", {"volume_id": vid})
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if len(master.topology.lookup_ec_shards(vid)) >= DATA_SHARDS_COUNT:
                break
            time.sleep(0.05)
        registered = len(master.topology.lookup_ec_shards(vid))
        assert registered >= DATA_SHARDS_COUNT, (
            f"only {registered} survivor shards registered at the master"
        )

        chunks_per_batch = max(1, max_batch_bytes // (DATA_SHARDS_COUNT * buffer_size))
        span = chunks_per_batch * buffer_size
        n_batches = -(-shard_size // span)
        out["n_batches"] = n_batches

        # -- decode wall: same volume, all survivors LOCAL -----------------
        base_local = os.path.join(td, "local", str(vid))
        os.makedirs(os.path.dirname(base_local))
        for s in range(DATA_SHARDS_COUNT):
            shutil.copy(stripe.shard_file_name(base_peer, s), stripe.shard_file_name(base_local, s))
        for ext in (".ecx", ".eci"):
            shutil.copy(base_peer + ext, base_local + ext)
        t0 = time.perf_counter()
        stripe.rebuild_ec_files(
            base_local,
            encoder=target.store.encoder,
            buffer_size=buffer_size,
            max_batch_bytes=max_batch_bytes,
        )
        decode_wall = time.perf_counter() - t0
        out["local_rebuild_gbps"] = round(data_bytes / decode_wall / 1e9, 3)
        out["decode_wall_s"] = round(decode_wall, 3)
        out["backend"] = target.store.encoder.backend

        # -- model the network ---------------------------------------------
        # On this 1-core loopback host a slab transfer is mostly CPU (grpc
        # serialize/deserialize + CRC) and CPU cannot overlap with decode
        # CPU — only the injected per-RPC sleep (the true network
        # component on real clusters) is overlappable. Measure the pure
        # CPU transfer wall first, then size the modeled RTT so the sleep
        # component of a window ~= its full compute cost (transfer CPU +
        # decode) — the network-comparable-to-compute regime where the
        # repair literature says rebuilds live and overlap pays.

        def fetch_windows(decode: bool) -> float:
            """Windowed survivor fetch through the real slab sources —
            parallel across shards within a window, optionally decoding
            each window BLOCKING before the next (the no-overlap serial
            baseline); without decode it is the pure network wall."""
            from concurrent.futures import ThreadPoolExecutor

            from seaweedfs_tpu.cluster.volume_server import EC_REBUILD_FETCH_WORKERS

            ex = ThreadPoolExecutor(max_workers=EC_REBUILD_FETCH_WORKERS)
            srcs = target._remote_slab_sources(vid, list(range(DATA_SHARDS_COUNT)), ex)
            staging = np.empty((DATA_SHARDS_COUNT, span), dtype=np.uint8)
            enc = target.store.encoder
            t0 = time.perf_counter()
            try:
                for off in range(0, shard_size, span):
                    valid = min(span, shard_size - off)
                    width = -(-valid // buffer_size) * buffer_size
                    for s in range(DATA_SHARDS_COUNT):
                        srcs[s].prefetch(off, width)
                    for s in range(DATA_SHARDS_COUNT):
                        srcs[s].read_into(off, staging[s, :width])
                    if decode:
                        np.asarray(
                            enc.reconstruct_lazy(
                                staging[:, :width], list(range(DATA_SHARDS_COUNT)), missing
                            )
                        )
                return time.perf_counter() - t0
            finally:
                for s in srcs.values():
                    s.close()
                ex.shutdown(wait=False, cancel_futures=True)

        set_delay(0)
        transfer_cpu_wall = fetch_windows(decode=False)
        out["transfer_cpu_wall_s"] = round(transfer_cpu_wall, 3)
        if delay_ms is None:
            # one RPC per survivor per window -> `waves` sequential sleep
            # waves per window given the fetch pool size. The 3x factor
            # puts the run in the NETWORK-DOMINATED regime ("Practical
            # Considerations in Repairing Reed-Solomon Codes": repair I/O,
            # not arithmetic, gates at scale) — and since sleeps are
            # immune to this shared vCPU's steal bursts, the ratio is set
            # by overlap arithmetic instead of CPU-noise luck
            from seaweedfs_tpu.cluster.volume_server import EC_REBUILD_FETCH_WORKERS

            waves = -(-DATA_SHARDS_COUNT // EC_REBUILD_FETCH_WORKERS)
            delay_ms = max(
                1.0,
                3e3 * (transfer_cpu_wall + decode_wall) / max(1, n_batches) / waves,
            )
        out["rpc_delay_ms"] = round(delay_ms, 2)
        set_delay(delay_ms)
        network_wall = fetch_windows(decode=False)
        out["network_wall_s"] = round(network_wall, 3)
        # best-of-2 for the gated comparison, like _measure_rebuild's
        # run(): a vCPU-steal spike during ONE phase would otherwise skew
        # the ratio either way on this shared 1-core host
        serial_wall = min(fetch_windows(decode=True) for _ in range(2))
        out["serial_fetch_then_decode_s"] = round(serial_wall, 3)
        out["serial_fetch_then_decode_gbps"] = round(data_bytes / serial_wall / 1e9, 3)

        # -- the real thing: distributed rebuild on the target -------------
        base_target = target._base_path_for(vid)
        remote_wall = float("inf")
        for _ in range(2):
            for s in missing:  # a rerun must regenerate, not no-op
                p = stripe.shard_file_name(base_target, s)
                if os.path.exists(p):
                    os.unlink(p)
            t0 = time.perf_counter()
            with rpc.RpcClient(target.grpc_address) as tc:
                resp = tc.call(
                    VOLUME_SERVICE,
                    "VolumeEcShardsRebuild",
                    {
                        "volume_id": vid,
                        "remote": True,
                        # this section measures the SLAB overlap pipeline:
                        # its baselines above model full-slab fetches, so
                        # trace projections must not silently shrink the
                        # transfer (the trace comparison is ec_rebuild_trace)
                        "trace_mode": "off",
                        # SAME window geometry as the baselines above: the
                        # comparison must count identical modeled RTTs, or
                        # "overlap" would partly measure window-size choice
                        "buffer_size": buffer_size,
                        "max_batch_bytes": max_batch_bytes,
                        "prefetch_batches": prefetch_batches,
                    },
                    timeout=600,
                )
            remote_wall = min(remote_wall, time.perf_counter() - t0)
        match = True
        for s in missing:
            with open(stripe.shard_file_name(base_target, s), "rb") as f:
                match = match and f.read() == golden[s]
        out["rebuilt_shard_ids"] = resp.get("rebuilt_shard_ids")
        out["remote_survivors"] = resp.get("remote_survivors")
        out["match"] = match
        out["remote_rebuild_wall_s"] = round(remote_wall, 3)
        out["remote_rebuild_gbps"] = round(data_bytes / remote_wall / 1e9, 3)
        out["overlap_efficiency"] = round(
            remote_wall / max(network_wall, decode_wall), 3
        )
        out["pipelined_vs_serial_fetch_then_decode"] = round(
            serial_wall / remote_wall, 2
        )
        out["ok"] = bool(match and resp.get("rebuilt_shard_ids") == missing)
    finally:
        set_delay(0)
        if prev_delay is not None:
            os.environ["WEEDTPU_BENCH_RPC_DELAY_MS"] = prev_delay
        target.stop()
        peer.stop()
        master.stop()
    return out


# ---------------------------------------------------------------------------
# stage 2f: trace-repair rebuild — wire bytes and wall vs full slabs (child)
# ---------------------------------------------------------------------------


def mode_rebuild_trace() -> None:
    """Repair-bandwidth headline: the SAME single-shard distributed rebuild
    run in trace mode (holders ship GF-projected rows for their survivor
    groups) and in slab mode (full survivor slabs), reporting the
    wire-bytes ratio — the number the repair literature prices — plus
    wall clocks under the modeled-RTT network."""
    import tempfile

    import jax  # noqa: F401

    with tempfile.TemporaryDirectory() as td:
        _emit(_measure_rebuild_trace(td))


def _measure_rebuild_trace(
    td: str,
    dat_bytes: int = 48 << 20,
    large: int = 4 << 20,
    small: int = 1 << 20,
    buffer_size: int = 128 << 10,
    max_batch_bytes: int = 4 << 20,
    prefetch_batches: int = 4,
    lost_shard: int = 3,
    delay_ms: float | None = None,
    encoder=None,
) -> dict:
    """Master + rebuild target + TWO peer holders: peer A holds shards 0-6
    (minus the lost one), peer B holds 7-13, the target holds nothing. One
    data shard is lost cluster-wide and the target rebuilds it twice over
    the RPC path — `trace_mode=on` then `trace_mode=off` — with identical
    window geometry. Wire bytes come from BOTH the EcRebuildResponse
    accounting and the weedtpu_ec_repair_network_bytes_total counter
    (in-process servers share the registry, so the counter deltas are the
    same numbers a scrape would show); rebuilt bytes are verified against
    golden both times. Trace mode's wire cost is holder-groups x repaired
    bytes — with survivors on 2 holders that is ~0.2x the 10 full slabs
    the slab path moves, and the acceptance gate is <= 0.6."""
    import shutil

    import numpy as np

    from seaweedfs_tpu import rpc, stats
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ec.constants import DATA_SHARDS_COUNT
    from seaweedfs_tpu.pb import VOLUME_SERVICE

    vid = 11
    out: dict = {
        "dat_mib": dat_bytes >> 20,
        "lost_shard": lost_shard,
        "protocol": (
            "same single-shard distributed rebuild, trace vs slab sources, "
            "identical window geometry and modeled RTT; wire_ratio = trace "
            "bytes-on-wire / slab bytes-on-wire (holder groups x repaired "
            "bytes vs 10 full survivor slabs); both runs byte-verified "
            "against golden"
        ),
    }
    prev_delay = os.environ.get("WEEDTPU_BENCH_RPC_DELAY_MS")

    def set_delay(ms: float) -> None:
        if ms > 0:
            os.environ["WEEDTPU_BENCH_RPC_DELAY_MS"] = str(ms)
        else:
            os.environ.pop("WEEDTPU_BENCH_RPC_DELAY_MS", None)

    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    dirs = [os.path.join(td, n) for n in ("target", "peer_a", "peer_b")]
    for d in dirs:
        os.makedirs(d)
    set_delay(0)  # no delay during setup
    target = VolumeServer(
        [dirs[0]], master.address, heartbeat_interval=0.3, encoder=encoder
    )
    peer_a = VolumeServer([dirs[1]], master.address, heartbeat_interval=0.3)
    peer_b = VolumeServer([dirs[2]], master.address, heartbeat_interval=0.3)
    servers = [target, peer_a, peer_b]
    for vs in servers:
        vs.start()
    try:
        # -- build on peer A, spread survivors, lose one data shard --------
        base_a = os.path.join(dirs[1], str(vid))
        rng = np.random.default_rng(29)
        with open(base_a + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, dat_bytes, dtype=np.uint8).tobytes())
        with open(base_a + ".idx", "wb"):
            pass
        stripe.write_ec_files(
            base_a,
            large_block_size=large,
            small_block_size=small,
            encoder=target.store.encoder,
        )
        stripe.write_sorted_file_from_idx(base_a)
        with open(stripe.shard_file_name(base_a, lost_shard), "rb") as f:
            golden = f.read()
        shard_size = os.path.getsize(stripe.shard_file_name(base_a, 0))
        os.unlink(stripe.shard_file_name(base_a, lost_shard))
        os.unlink(base_a + ".dat")
        base_b = os.path.join(dirs[2], str(vid))
        moved = [s for s in range(7, 14)]
        for s in moved:
            os.replace(
                stripe.shard_file_name(base_a, s), stripe.shard_file_name(base_b, s)
            )
        for ext in (".ecx", ".eci"):
            shutil.copy(base_a + ext, base_b + ext)
        for vs in (peer_a, peer_b):
            with rpc.RpcClient(vs.grpc_address) as c:
                c.call(VOLUME_SERVICE, "VolumeEcShardsMount", {"volume_id": vid})
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if len(master.topology.lookup_ec_shards(vid)) >= 13:
                break
            time.sleep(0.05)
        assert len(master.topology.lookup_ec_shards(vid)) >= DATA_SHARDS_COUNT

        out["shard_mib"] = round(shard_size / (1 << 20), 3)
        out["slab_baseline_bytes"] = DATA_SHARDS_COUNT * shard_size
        if delay_ms is None:
            # the same network-comparable-to-compute regime as the
            # rebuild_remote bench, sized off the data footprint: one
            # modeled RTT per bulk window request
            delay_ms = 2.0
        out["rpc_delay_ms"] = round(delay_ms, 2)
        base_target = target._base_path_for(vid)

        def run_once(trace_mode: str) -> tuple[dict, float, bool]:
            p = stripe.shard_file_name(base_target, lost_shard)
            if os.path.exists(p):
                os.unlink(p)  # a rerun must regenerate, not no-op
            t0 = time.perf_counter()
            with rpc.RpcClient(target.grpc_address) as tc:
                resp = tc.call(
                    VOLUME_SERVICE,
                    "VolumeEcShardsRebuild",
                    {
                        "volume_id": vid,
                        "remote": True,
                        "trace_mode": trace_mode,
                        "buffer_size": buffer_size,
                        "max_batch_bytes": max_batch_bytes,
                        "prefetch_batches": prefetch_batches,
                    },
                    timeout=600,
                )
            wall = time.perf_counter() - t0
            with open(p, "rb") as f:
                match = f.read() == golden
            return resp, wall, match

        set_delay(delay_ms)
        results: dict[str, dict] = {}
        for mode_name in ("trace", "slab"):
            counter = stats.EcRepairNetworkBytes.labels(mode_name)
            before = counter.value
            wall = float("inf")
            for _ in range(2):  # best-of-2 against vCPU steal spikes
                resp, w, match = run_once("on" if mode_name == "trace" else "off")
                wall = min(wall, w)
            results[mode_name] = {
                "wall_s": round(wall, 3),
                "wire_bytes": int(resp.get("wire_bytes") or 0),
                "counter_bytes_2_runs": int(counter.value - before),
                "mode_reported": resp.get("mode"),
                "match": bool(match),
                "rebuilt_shard_ids": resp.get("rebuilt_shard_ids"),
            }
            if mode_name == "trace":
                results[mode_name]["groups"] = resp.get("trace_groups")
                results[mode_name]["fallback"] = resp.get("trace_fallback")
        out["trace"] = results["trace"]
        out["slab"] = results["slab"]
        slab_wire = results["slab"]["wire_bytes"]
        out["wire_ratio"] = (
            round(results["trace"]["wire_bytes"] / slab_wire, 4) if slab_wire else None
        )
        out["wall_ratio"] = round(
            results["trace"]["wall_s"] / results["slab"]["wall_s"], 3
        )
        out["ok"] = bool(
            results["trace"]["match"]
            and results["slab"]["match"]
            and results["trace"]["mode_reported"] == "trace"
            and results["slab"]["mode_reported"] == "slab"
            and results["trace"]["rebuilt_shard_ids"] == [lost_shard]
            and out["wire_ratio"] is not None
            and out["wire_ratio"] <= 0.6
        )
    finally:
        set_delay(0)
        if prev_delay is not None:
            os.environ["WEEDTPU_BENCH_RPC_DELAY_MS"] = prev_delay
        for vs in servers:
            vs.stop()
        master.stop()
    return out


# ---------------------------------------------------------------------------
# stage 2g: inline-EC ingest — amortized encode-on-write + delta parity
# ---------------------------------------------------------------------------


def mode_ingest() -> None:
    """Write-heavy workload headline: a volume's bytes streamed through the
    encode-on-write stripe builder (poll per append burst) vs the warm
    batch conversion, plus the small-write delta-parity accounting — the
    < 0.5x bytes gate for <=1% stripe overwrites."""
    import tempfile

    import jax  # noqa: F401

    with tempfile.TemporaryDirectory() as td:
        _emit(_measure_ingest(td))


def mode_convert() -> None:
    """BENCH_MODE=convert: geometry conversion vs the decode->re-encode
    oracle — byte identity asserted, bytes-moved accounting gated at
    <= 0.5x the oracle's total I/O for each geometry pair."""
    import tempfile

    import jax  # noqa: F401

    with tempfile.TemporaryDirectory() as td:
        out = _measure_convert(td)
    out = {
        "when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "kind": "bench_convert",
        **out,
    }
    _emit(out)


def _measure_convert(
    td: str,
    dat_bytes: int = 192 << 20,
    large: int = 1 << 20,
    small: int = 256 << 10,
    buffer_size: int = 256 << 10,
    families: tuple = ("cauchy_12_3", "merge_20_4"),
    encoder=None,
) -> dict:
    """`ec.convert`'s engine vs the decode->re-encode oracle on the same
    volume bytes, one run per target family.

    Conversion: `convert_ec_files` streams the source shard set through
    the staging-ring pipeline into the staged target (+ journal + on-disk
    re-verify), instrumenting `bytes_read` (source bytes consumed) and
    `bytes_written` (target bytes materialized). Oracle: write_dat_file
    (decode) + write_ec_files on the target geometry — its I/O footprint
    is MEASURED from the real files (read data shards + write .dat +
    re-read .dat + write the target set) and asserted equal to the
    deterministic `reencode_oracle_bytes` formula, so the gate cannot
    drift from what the oracle actually does. Per family: staged output
    byte-compared against the oracle's shard set, and
    `bytes_written / oracle_total <= 0.5` is the committed gate."""
    import shutil

    import numpy as np

    from seaweedfs_tpu.ec import convert as convert_mod
    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ops.rs_codec import geometry_for, new_encoder

    enc = encoder or new_encoder()
    rng = np.random.default_rng(41)
    data = rng.integers(0, 256, dat_bytes, dtype=np.uint8).tobytes()
    base = os.path.join(td, "src", "7")
    os.makedirs(os.path.dirname(base))
    with open(base + ".dat", "wb") as f:
        f.write(data)
    t0 = time.perf_counter()
    stripe.write_ec_files(
        base, large_block_size=large, small_block_size=small,
        buffer_size=buffer_size, encoder=enc,
    )
    src_encode_s = time.perf_counter() - t0
    src_total = enc.total_shards
    out: dict = {
        "section": "ec_convert",
        "dat_mib": round(dat_bytes / (1 << 20), 2),
        "large_block": large,
        "small_block": small,
        "backend": enc.backend,
        "src_family": "rs_10_4",
        "src_encode_s": round(src_encode_s, 3),
        "protocol": (
            "convert = convert_ec_files (staged target + .ecc journal + "
            "on-disk re-verify), bytes_written = target bytes "
            "materialized; oracle = write_dat_file + write_ec_files on "
            "the target geometry, oracle_total = measured read data "
            "shards + write .dat + re-read .dat + write target set "
            "(asserted == the deterministic reencode_oracle_bytes "
            "formula); gate: bytes_written / oracle_total <= 0.5 AND "
            "staged output byte-identical to the oracle's"
        ),
        "pairs": {},
    }
    ok = True
    for fam in families:
        geom = geometry_for(fam)
        oracle_acct = convert_mod.reencode_oracle_bytes(base, fam)
        t0 = time.perf_counter()
        res = convert_mod.convert_ec_files(
            base, fam, encoder=enc, buffer_size=buffer_size
        )
        convert_s = time.perf_counter() - t0
        # real oracle run, I/O measured from the files it actually touches
        ob = os.path.join(td, f"oracle_{fam}", "7")
        os.makedirs(os.path.dirname(ob))
        for s in range(src_total):
            shutil.copy(
                stripe.shard_file_name(base, s), stripe.shard_file_name(ob, s)
            )
        shutil.copy(base + ".eci", ob + ".eci")
        t0 = time.perf_counter()
        stripe.write_dat_file(ob)
        decode_s = time.perf_counter() - t0
        oracle_dat = os.path.getsize(ob + ".dat")
        for s in range(src_total):
            os.unlink(stripe.shard_file_name(ob, s))
        tgt_enc = new_encoder(family=fam, backend=enc.backend)
        t0 = time.perf_counter()
        stripe.write_ec_files(
            ob, large_block_size=large, small_block_size=small,
            buffer_size=buffer_size, encoder=tgt_enc,
        )
        encode_s = time.perf_counter() - t0
        oracle_tgt = sum(
            os.path.getsize(stripe.shard_file_name(ob, s))
            for s in range(geom.total_shards)
        )
        measured_total = 3 * oracle_dat + oracle_tgt
        staged = convert_mod.stage_base(base)
        match = all(
            open(stripe.shard_file_name(staged, s), "rb").read()
            == open(stripe.shard_file_name(ob, s), "rb").read()
            for s in range(geom.total_shards)
        )
        ratio = (
            round(res["bytes_written"] / oracle_acct["total"], 4)
            if oracle_acct["total"]
            else None
        )
        pair_ok = (
            match
            and measured_total == oracle_acct["total"]
            and ratio is not None
            and ratio <= 0.5
        )
        ok = ok and pair_ok
        out["pairs"][fam] = {
            "target_shards": geom.total_shards,
            "convert_s": round(convert_s, 3),
            "oracle_s": round(decode_s + encode_s, 3),
            "bytes_read": res["bytes_read"],
            "bytes_written": res["bytes_written"],
            "reconstructed_bytes": res["reconstructed_bytes"],
            "oracle_total_bytes": oracle_acct["total"],
            "oracle_total_measured": measured_total,
            "moved_over_reencode": ratio,
            "convert_io_over_reencode": (
                round(
                    (res["bytes_read"] + res["bytes_written"])
                    / oracle_acct["total"],
                    4,
                )
                if oracle_acct["total"]
                else None
            ),
            "match": match,
            "ok": pair_ok,
        }
        convert_mod.discard_staged(base, keep_journal=False)
    out["gate"] = "bytes_written / oracle_total <= 0.5 per pair"
    out["ok"] = ok
    return out


def _measure_ingest(
    td: str,
    dat_bytes: int = 192 << 20,
    large: int = 1 << 20,
    small: int = 256 << 10,
    buffer_size: int = 256 << 10,
    append_chunk: int = 4 << 20,
    overwrite_fraction: float = 0.01,
    overwrite_count: int = 16,
    encoder=None,
) -> dict:
    """Inline-vs-warm encode on the same volume bytes.

    Inline: the .dat is appended in `append_chunk` bursts with a builder
    poll after each (the ingest write-path shape); amortized GB/s counts
    data bytes over the SUM of encode time (polls + seal), i.e. what the
    encoder actually spent, spread across ingest. Warm: one
    `write_ec_files` over the finished .dat. Output byte-identity is
    asserted, not assumed.

    Delta: `overwrite_count` random ranges totaling `overwrite_fraction`
    of the .dat are folded into a FULLY-encoded stripe via the journaled
    delta path; the gate compares deterministic BYTE counts (not
    timings): delta bytes computed/moved (changed x (2 data + 2x parity
    RMW)) must stay under 0.5x a full re-encode's dat read + 14 shard
    writes. Shards after the deltas are verified byte-identical to a
    warm encode of the mutated .dat."""
    import numpy as np

    from seaweedfs_tpu.ec import ingest, stripe
    from seaweedfs_tpu.ec.constants import TOTAL_SHARDS_COUNT
    from seaweedfs_tpu.ops.rs_codec import new_encoder

    enc = encoder or new_encoder()
    rng = np.random.default_rng(31)
    data = rng.integers(0, 256, dat_bytes, dtype=np.uint8).tobytes()
    out: dict = {
        "dat_mib": round(dat_bytes / (1 << 20), 2),
        "large_block": large,
        "small_block": small,
        "backend": enc.backend,
        "protocol": (
            "inline = append in bursts + builder poll per burst + seal; "
            "amortized GB/s = data bytes / (sum of poll secs + seal secs); "
            "warm = one write_ec_files over the finished .dat; both outputs "
            "byte-compared. delta gate compares BYTE counts: changed x "
            "(2 + 2 x parity RMW) vs dat read + 14 shard writes of a full "
            "re-encode, for <=1% overwrites"
        ),
    }

    # -- inline: stream-append + poll ---------------------------------------
    base_i = os.path.join(td, "inline", "5")
    os.makedirs(os.path.dirname(base_i))
    builder = ingest.InlineStripeBuilder(
        base_i, enc, large, small, buffer_size=buffer_size
    )
    encode_s = 0.0
    polls = 0
    with open(base_i + ".dat", "wb") as f:
        for off in range(0, dat_bytes, append_chunk):
            f.write(data[off : off + append_chunk])
            f.flush()
            t0 = time.perf_counter()
            if builder.poll():
                polls += 1
            encode_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    info = builder.seal()
    seal_s = time.perf_counter() - t0
    out["inline"] = {
        "amortized_gbps": round(dat_bytes / (encode_s + seal_s) / 1e9, 3),
        "poll_s": round(encode_s, 3),
        "seal_s": round(seal_s, 3),
        "polls_with_work": polls,
        "rows_inline": info["rows_inline"],
        "rows_total": info["rows_total"],
    }

    # -- warm reference ------------------------------------------------------
    base_w = os.path.join(td, "warm", "5")
    os.makedirs(os.path.dirname(base_w))
    with open(base_w + ".dat", "wb") as f:
        f.write(data)
    t0 = time.perf_counter()
    stripe.write_ec_files(
        base_w, large_block_size=large, small_block_size=small,
        buffer_size=buffer_size, encoder=enc,
    )
    warm_s = time.perf_counter() - t0
    out["warm"] = {"gbps": round(dat_bytes / warm_s / 1e9, 3), "wall_s": round(warm_s, 3)}
    # the ROADMAP follow-up's headline: encode-on-write efficiency relative
    # to the warm batch conversion on the same bytes, same run (shared
    # host/disk noise cancels in the ratio)
    out["amortized_over_warm"] = round(
        out["inline"]["amortized_gbps"] / out["warm"]["gbps"], 4
    ) if out["warm"]["gbps"] else None
    match = all(
        open(stripe.shard_file_name(base_i, s), "rb").read()
        == open(stripe.shard_file_name(base_w, s), "rb").read()
        for s in range(TOTAL_SHARDS_COUNT)
    ) and open(base_i + ".eci", "rb").read() == open(base_w + ".eci", "rb").read()
    out["match"] = bool(match)

    # -- delta parity updates on a fully-encoded stripe ----------------------
    base_d = os.path.join(td, "delta", "5")
    os.makedirs(os.path.dirname(base_d))
    with open(base_d + ".dat", "wb") as f:
        f.write(data)
    b2 = ingest.InlineStripeBuilder(
        base_d, enc, large, small, buffer_size=buffer_size
    )
    b2.poll()
    encoded_limit = b2.encoded_limit()
    per = max(1, int(dat_bytes * overwrite_fraction) // overwrite_count)
    mutated = bytearray(data)
    t0 = time.perf_counter()
    for i in range(overwrite_count):
        off = int(rng.integers(0, max(1, encoded_limit - per)))
        new_seg = rng.integers(0, 256, per, dtype=np.uint8).tobytes()
        old_seg = bytes(mutated[off : off + per])

        def mutate(off=off, new_seg=new_seg):
            with open(base_d + ".dat", "r+b") as f:
                f.seek(off)
                f.write(new_seg)

        b2.overwrite(off, old_seg, new_seg, mutate=mutate)
        mutated[off : off + per] = new_seg
    delta_wall = time.perf_counter() - t0
    changed = b2.delta_stats["changed_bytes"]
    delta_bytes = b2.delta_stats["accounted_bytes"]
    b2.seal()
    shard_size = os.path.getsize(stripe.shard_file_name(base_d, 0))
    reencode_bytes = dat_bytes + TOTAL_SHARDS_COUNT * shard_size
    base_m = os.path.join(td, "mut", "5")
    os.makedirs(os.path.dirname(base_m))
    with open(base_m + ".dat", "wb") as f:
        f.write(bytes(mutated))
    t0 = time.perf_counter()
    stripe.write_ec_files(
        base_m, large_block_size=large, small_block_size=small,
        buffer_size=buffer_size, encoder=enc,
    )
    reencode_wall = time.perf_counter() - t0
    delta_match = all(
        open(stripe.shard_file_name(base_d, s), "rb").read()
        == open(stripe.shard_file_name(base_m, s), "rb").read()
        for s in range(TOTAL_SHARDS_COUNT)
    )
    out["delta"] = {
        "overwrites": overwrite_count,
        "overwrite_fraction": round(changed / dat_bytes, 5),
        "changed_bytes": int(changed),
        "delta_bytes": int(delta_bytes),
        "reencode_bytes": int(reencode_bytes),
        "bytes_ratio": round(delta_bytes / reencode_bytes, 5),
        "wall_s": round(delta_wall, 3),
        "reencode_wall_s": round(reencode_wall, 3),
        "wall_ratio": round(delta_wall / reencode_wall, 4) if reencode_wall else None,
        "match": bool(delta_match),
    }
    out["ok"] = bool(
        match and delta_match and out["delta"]["bytes_ratio"] < 0.5
    )
    return out


# ---------------------------------------------------------------------------
# stage 2h: mesh backend — pod-scale encode/rebuild per mesh shape
# ---------------------------------------------------------------------------


def mode_mesh() -> None:
    """Per-mesh-shape encode + ring-vs-all_to_all rebuild GB/s through the
    REAL file pipelines (write_ec_files / rebuild_ec_files with the mesh
    backend), byte-verified against the single-device oracle — emitted in
    the MULTICHIP_r*.json artifact format the `auto` promotion reads."""
    import tempfile

    import jax  # noqa: F401

    with tempfile.TemporaryDirectory() as td:
        _emit(_measure_mesh(td))


def _measure_mesh(
    td: str,
    dat_bytes: int = 96 << 20,
    large: int = 1 << 20,
    small: int = 256 << 10,
    buffer_size: int = 256 << 10,
    max_batch_bytes: int = 32 << 20,
    shapes=None,
    lost=(0, 5, 11, 13),
) -> dict:
    """MULTICHIP-record body: for each dp x sp shape, encode the same
    volume through the mesh streaming pipeline and rebuild the worst
    allowed loss through BOTH distributed formulations; every output is
    byte-compared against the single-device oracle files. Encode GB/s
    counts data bytes in; rebuild GB/s counts rebuilt shard bytes out
    (the repaired-bytes rate the >=10x target is stated against)."""
    import jax
    import numpy as np

    from seaweedfs_tpu.ec import stripe
    from seaweedfs_tpu.ops.rs_codec import Encoder, new_encoder

    n_dev = len(jax.devices())
    d0 = jax.devices()[0]
    if shapes is None:
        shapes = [
            s
            for s in ((n_dev, 1), (n_dev // 2, 2), (n_dev // 4, 4))
            if s[0] >= 1 and s[0] * s[1] == n_dev
        ]
    out: dict = {
        "when": time.strftime("%FT%TZ", time.gmtime()),
        "kind": "multichip",
        "round": 6,
        "n_devices": n_dev,
        "platform": f"{d0.platform} ({getattr(d0, 'device_kind', '?')})",
        "protocol": (
            "per-shape encode/rebuild through the real ec/stripe file "
            "pipelines with the mesh backend; encode GB/s = data bytes / "
            "wall, rebuild GB/s = rebuilt shard bytes / wall; every shard "
            "file byte-compared vs the single-device oracle (match=false "
            "disqualifies the shape as promotion evidence)"
        ),
        "dat_mib": round(dat_bytes / (1 << 20), 2),
        "lost_shards": list(lost),
        "shapes": {},
    }
    rng = np.random.default_rng(23)
    data = rng.integers(0, 256, dat_bytes, dtype=np.uint8).tobytes()

    # single-device oracle: the auto encoder — UNLESS auto already
    # promoted to mesh (a prior on-chip evidence round landed), in which
    # case the oracle must be forced back to the per-chip path or the
    # artifact's single_device baseline would itself be the pod number
    # and no shape could ever beat it on re-measurement
    oracle_enc = new_encoder()
    if oracle_enc.backend == "mesh":
        from seaweedfs_tpu.ops.rs_codec import _cpu_backend

        single = "jax" if d0.platform != "cpu" else _cpu_backend()
        oracle_enc = Encoder(10, 4, backend=single)
    base_o = os.path.join(td, "oracle", "7")
    os.makedirs(os.path.dirname(base_o))
    with open(base_o + ".dat", "wb") as f:
        f.write(data)
    t0 = time.perf_counter()
    stripe.write_ec_files(
        base_o, large_block_size=large, small_block_size=small,
        buffer_size=buffer_size, encoder=oracle_enc,
        max_batch_bytes=max_batch_bytes,
    )
    enc_wall = time.perf_counter() - t0
    oracle = {
        s: open(stripe.shard_file_name(base_o, s), "rb").read() for s in range(14)
    }
    shard_size = len(oracle[0])
    rebuilt_bytes = len(lost) * shard_size
    for s in lost:
        os.unlink(stripe.shard_file_name(base_o, s))
    t0 = time.perf_counter()
    stripe.rebuild_ec_files(
        base_o, encoder=oracle_enc, buffer_size=buffer_size,
        max_batch_bytes=max_batch_bytes,
    )
    reb_wall = time.perf_counter() - t0
    out["single_device"] = {
        "backend": oracle_enc.backend,
        "encode_gbps": round(dat_bytes / enc_wall / 1e9, 3),
        "rebuild_gbps": round(rebuilt_bytes / reb_wall / 1e9, 3),
    }

    all_match = True
    for dp, sp in shapes:
        label = f"{dp}x{sp}"
        base_m = os.path.join(td, label, "7")
        os.makedirs(os.path.dirname(base_m))
        with open(base_m + ".dat", "wb") as f:
            f.write(data)
        rec: dict = {}
        try:
            enc = Encoder(10, 4, backend="mesh", mesh_shape=(dp, sp))
            t0 = time.perf_counter()
            stripe.write_ec_files(
                base_m, large_block_size=large, small_block_size=small,
                buffer_size=buffer_size, encoder=enc,
                max_batch_bytes=max_batch_bytes,
            )
            rec["encode_gbps"] = round(dat_bytes / (time.perf_counter() - t0) / 1e9, 3)
            match = all(
                open(stripe.shard_file_name(base_m, s), "rb").read() == oracle[s]
                for s in range(14)
            )
            for variant, key in (("ring", "rebuild_ring_gbps"),
                                 ("alltoall", "rebuild_alltoall_gbps")):
                for s in lost:
                    os.unlink(stripe.shard_file_name(base_m, s))
                enc_v = Encoder(
                    10, 4, backend="mesh", mesh_shape=(dp, sp), mesh_rebuild=variant
                )
                t0 = time.perf_counter()
                stripe.rebuild_ec_files(
                    base_m, encoder=enc_v, buffer_size=buffer_size,
                    max_batch_bytes=max_batch_bytes,
                )
                rec[key] = round(rebuilt_bytes / (time.perf_counter() - t0) / 1e9, 3)
                match = match and all(
                    open(stripe.shard_file_name(base_m, s), "rb").read() == oracle[s]
                    for s in lost
                )
            rec["match"] = bool(match)
            all_match = all_match and match
        except Exception as e:  # noqa: BLE001 — one shape must not kill the sweep
            rec["error"] = str(e)[:200]
            all_match = False
        out["shapes"][label] = rec
    out["ok"] = bool(all_match and out["shapes"])
    return out


# ---------------------------------------------------------------------------
# stage 2d: dp-scaling sweep (child, 8 virtual CPU devices)
# ---------------------------------------------------------------------------


def mode_dp() -> None:
    """Encode throughput across dp=1/2/4/8 meshes (SURVEY §2.5, VERDICT r3
    #5). On this single-core host the virtual CPU devices share one core,
    so the curve quantifies the sharding machinery's overhead (flat =
    free), not chip speedup — the real-speedup axis needs real chips."""
    import jax

    import numpy as np

    from seaweedfs_tpu.ops import gf8
    from seaweedfs_tpu.parallel import mesh as mesh_mod
    from seaweedfs_tpu.parallel import sharded

    out: dict = {
        "devices": len(jax.devices()),
        "host_cores": os.cpu_count(),
        "note": (
            "virtual CPU mesh on one host core: the curve measures "
            "sharding-machinery overhead at fixed global problem size, "
            "not parallel speedup"
        ),
    }
    b, n = 8, 1 << 20  # fixed global problem: 80 MiB of data
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, size=(b, 10, n), dtype=np.uint8)
    pm = gf8.parity_matrix(10, 4)
    sweep: dict = {}
    for dp in (1, 2, 4, 8):
        if dp > len(jax.devices()):
            break
        try:
            mesh = mesh_mod.device_mesh(("dp", "sp"), shape=(dp, 1))
            enc = sharded.make_encode_fn(mesh, pm)
            x = sharded.shard_batch(mesh, data)
            t = _median_time(lambda: jax.block_until_ready(enc(x)), iters=3, warmup=1)
            sweep[str(dp)] = round(b * 10 * n / t / 1e9, 3)
        except Exception as e:  # noqa: BLE001 — one dp point must not kill the sweep
            sweep[str(dp)] = f"error: {str(e)[:120]}"
    out["encode_gbps_by_dp"] = sweep
    base = sweep.get("1")
    if isinstance(base, float) and base > 0:
        out["efficiency_vs_dp1"] = {
            k: round(v / base, 3) for k, v in sweep.items() if isinstance(v, float)
        }
    _emit(out)


# ---------------------------------------------------------------------------
# device suite (child, default platform: the chip)
# ---------------------------------------------------------------------------


def mode_device() -> None:
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf8, rs_jax

    from seaweedfs_tpu.utils.devices import describe_devices

    dev = describe_devices()
    if dev["platform"] == "cpu":
        # a device measurement that finds no chip fails: it never measures
        # the CPU under a device metric's name
        print(f"bench device suite: jax found no accelerator ({dev})", file=sys.stderr)
        sys.exit(3)
    out: dict = {"platform": dev["platform"], "device": dev}
    parity_bits = rs_jax.lifted_matrix(gf8.parity_matrix(10, 4))

    # compile check at a tiny shape first: if the toolchain rejects the
    # kernel we still report that fact instead of dying in the sweep
    t0 = time.perf_counter()
    try:
        tiny = jnp.zeros((1, 10, 16384), dtype=jnp.uint8)
        jax.block_until_ready(rs_jax.gf_apply(parity_bits, tiny))
        out["compile_check_secs"] = round(time.perf_counter() - t0, 2)
    except Exception as e:  # noqa: BLE001 — still sweep: Pallas may lower fine
        out["compile_check_error"] = str(e)[:500]

    b, n = 8, 4 << 20
    key = jax.random.PRNGKey(0)
    data = jax.block_until_ready(
        jax.random.randint(key, (b, 10, n), 0, 256, dtype=jnp.uint8)
    )
    data_bytes = b * 10 * n

    @jax.jit
    def encode_xla(d):
        return rs_jax.gf_apply(parity_bits, d)

    def encode_pallas(d):
        from seaweedfs_tpu.ops import rs_pallas

        return rs_pallas.gf_apply_fused(parity_bits, d)

    # Two numbers per backend:
    #   per-call      — one dispatch per encode: includes the per-dispatch
    #                   floor, so it reflects the host path, not the chip.
    #   steady-state  — slope method: time lax.scan chains of K1 and K2
    #                   encodes in ONE dispatch; (t2-t1)/(K2-K1) is the true
    #                   per-encode device time. This matches production use
    #                   (a storage node streams encodes) and BASELINE.md's
    #                   device-side protocol.
    def steady_gbps(encode_fn, out_rows: int = 4):
        from seaweedfs_tpu.ops.measure import scan_chain_gbps

        return scan_chain_gbps(encode_fn, data, data_bytes, out_rows=out_rows)

    best_gbps, best_name, best_fn = 0.0, "none", None
    for name, fn in (("xla", encode_xla), ("pallas", encode_pallas)):
        try:
            t = _median_time(lambda: jax.block_until_ready(fn(data)), iters=10, warmup=3)
            gbps = data_bytes / t / 1e9
            out[f"{name}_gbps"] = round(gbps, 3)
        except Exception as e:  # noqa: BLE001 — a kernel failure must not zero the run
            out[f"{name}_error"] = str(e)[:500]
            continue
        if gbps > best_gbps:
            best_gbps, best_name, best_fn = gbps, name, fn
    # slope-measure only the per-call winner: each chain is two more XLA
    # compiles, and the device child must fit the watchdog budget even on a
    # cold compile cache
    if best_fn is not None:
        try:
            steady = steady_gbps(best_fn)
            out[f"{best_name}_steady_gbps"] = round(steady, 3)
            if steady > best_gbps:
                best_gbps = steady
        except Exception as e:  # noqa: BLE001
            out["steady_error"] = str(e)[:300]
    # rebuild decode path on-device: ONE fused survivors->missing matrix
    # (2 data + 2 parity lost — the worst allowed loss count) applied to a
    # survivor stack, the exact shape the pipelined rebuild_ec_files
    # dispatches per batch. Counts toward the >=10x-rebuild north star.
    try:
        from seaweedfs_tpu.ops.rs_codec import _reconstruction_matrix

        lost = (0, 5, 11, 13)
        surv = tuple(s for s in range(14) if s not in lost)[:10]
        dm_bits = rs_jax.lifted_matrix(
            _reconstruction_matrix("vandermonde", 10, 4, surv, lost)
        )

        @jax.jit
        def decode_xla(d):
            return rs_jax.gf_apply(dm_bits, d)

        t = _median_time(
            lambda: jax.block_until_ready(decode_xla(data)), iters=10, warmup=3
        )
        out["rebuild_xla_gbps"] = round(data_bytes / t / 1e9, 3)
        out["rebuild_xla_steady_gbps"] = round(
            steady_gbps(decode_xla, out_rows=len(lost)), 3
        )
    except Exception as e:  # noqa: BLE001 — rebuild numbers must not zero encode's
        out["rebuild_error"] = str(e)[:300]
    out["best_gbps"] = round(best_gbps, 3)
    out["best_backend"] = best_name
    try:
        from seaweedfs_tpu.ops.rs_codec import new_encoder

        # what production would ACTUALLY select on this device right now —
        # the evidence-based factory decision, next to the live numbers it
        # should eventually reflect (flips only via a committed artifact)
        out["auto_backend"] = new_encoder().selection
    except Exception as e:  # noqa: BLE001
        out["auto_backend_error"] = str(e)[:200]
    out["dispatch_floor_note"] = (
        "per-call numbers include the per-dispatch floor; steady-state "
        "(scan-chain slope) is the device-side throughput"
    )

    # jax.profiler capture of the winning kernel (SURVEY §5 tracing row):
    # only meaningful with a real device; the trace directory is committed
    # as a round artifact for offline analysis
    trace_dir = os.environ.get("BENCH_TRACE_DIR", "")
    if trace_dir and best_fn is not None and out["platform"] != "cpu":
        try:
            with jax.profiler.trace(trace_dir):
                for _ in range(3):
                    jax.block_until_ready(best_fn(data))
            out["trace_dir"] = trace_dir
        except Exception as e:  # noqa: BLE001 — tracing must not zero the run
            out["trace_error"] = str(e)[:200]
    _emit(out)


# ---------------------------------------------------------------------------
# parent orchestrator
# ---------------------------------------------------------------------------


def main() -> None:
    deadline = time.monotonic() + WATCHDOG_SECS - 30  # emit margin
    forced_cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"

    result: dict = {
        "metric": "ec_encode_gbps_10p4",
        "value": 0.0,
        "unit": "GB/s",
        "vs_baseline": 0.0,
    }

    # device suite first (with a jax.profiler capture directory), unless the
    # operator pinned the CPU. No accelerator, no result: exit non-zero.
    device = None
    if not forced_cpu:
        device, dev_err = _run_child(
            "device",
            timeout=max(60, int(deadline - time.monotonic()) // 2),
            extra_env={
                "BENCH_TRACE_DIR": os.environ.get(
                    "BENCH_TRACE_DIR",
                    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "artifacts", "jax_trace"),
                )
            },
        )
        if not device or not device.get("best_gbps"):
            print(
                "bench: the device suite produced no measurement "
                f"({dev_err or device}); set JAX_PLATFORMS=cpu to ask for "
                "this host's CPU numbers instead",
                file=sys.stderr,
            )
            sys.exit(1)
        result["device"] = device

    # CPU suite (JAX_PLATFORMS=cpu child): host numbers, labelled as such
    cpu, cpu_err = _run_child(
        "cpu",
        timeout=min(CPU_SUITE_SECS, max(30, int(deadline - time.monotonic()))),
        extra_env={"JAX_PLATFORMS": "cpu"},
    )
    if cpu:
        result["cpu"] = cpu
        if "ec_rebuild" in cpu:  # the second north-star target, surfaced
            result["ec_rebuild"] = cpu["ec_rebuild"]  # beside the encode headline
    else:
        result["cpu_error"] = cpu_err

    # stage 2c: remote degraded-read ladder (two in-process servers)
    remote, remote_err = _run_child(
        "remote",
        timeout=min(300, max(30, int(deadline - time.monotonic()))),
        extra_env={"JAX_PLATFORMS": "cpu"},
    )
    if remote:
        result["remote_ladder"] = remote
    else:
        result["remote_ladder_error"] = remote_err

    # stage 2e: distributed remote-survivor rebuild (two in-process servers)
    rr, rr_err = _run_child(
        "rebuild_remote",
        timeout=min(300, max(30, int(deadline - time.monotonic()))),
        extra_env={"JAX_PLATFORMS": "cpu"},
    )
    if rr:
        result["ec_rebuild_remote"] = rr
    else:
        result["ec_rebuild_remote_error"] = rr_err

    # stage 2f: trace-repair rebuild — wire-bytes ratio vs full slabs
    rt, rt_err = _run_child(
        "rebuild_trace",
        timeout=min(300, max(30, int(deadline - time.monotonic()))),
        extra_env={"JAX_PLATFORMS": "cpu"},
    )
    if rt:
        result["ec_rebuild_trace"] = rt
    else:
        result["ec_rebuild_trace_error"] = rt_err

    # stage 2g: inline-EC ingest — amortized encode-on-write + delta gate
    ing, ing_err = _run_child(
        "ingest",
        timeout=min(300, max(30, int(deadline - time.monotonic()))),
        extra_env={"JAX_PLATFORMS": "cpu"},
    )
    if ing:
        result["ec_ingest"] = ing
    else:
        result["ec_ingest_error"] = ing_err

    # stage 2i: compiled XOR-schedule backend vs the native library (the
    # committed section rs_codec.pick_cpu_backend promotes on: same-run
    # xorsched/native ratio, host fingerprint, byte-verification)
    xor, xor_err = _run_child(
        "xor",
        timeout=min(300, max(30, int(deadline - time.monotonic()))),
        extra_env={"JAX_PLATFORMS": "cpu"},
    )
    if xor:
        result["xor"] = xor
    else:
        result["xor_error"] = xor_err

    # stage 2d: dp-scaling sweep over the virtual 8-device CPU mesh
    if deadline - time.monotonic() > 30:
        dp, dp_err = _run_child(
            "dp",
            timeout=min(300, int(deadline - time.monotonic())),
            extra_env={
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            },
        )
        if dp:
            result["dp_scaling"] = dp
        else:
            result["dp_scaling_error"] = dp_err
    else:
        result["dp_scaling_error"] = "skipped: bench deadline exhausted"

    # stage 2h: mesh backend — per-mesh-shape encode/rebuild through the
    # real file pipelines on the forced 8-device CPU mesh (host numbers:
    # they measure the sharding machinery, never promotion evidence)
    if deadline - time.monotonic() > 60:
        mesh, mesh_err = _run_child(
            "mesh",
            timeout=min(300, int(deadline - time.monotonic())),
            extra_env={
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            },
        )
        if mesh:
            result["ec_mesh"] = mesh
        else:
            result["ec_mesh_error"] = mesh_err
    else:
        result["ec_mesh_error"] = "skipped: bench deadline exhausted"

    # headline value: the chip's, or — only when the operator pinned the
    # CPU — this host's best CPU-side measurement under its own label
    if device:
        result["value"] = device["best_gbps"]
        result["platform"] = device["platform"]
        result["backend"] = device.get("best_backend")
    else:
        fb = result.get("cpu", {})
        native_name = "native-avx2" if fb.get("native_avx2") else "native"
        candidates = {
            "xla-cpu": fb.get("xla_cpu_gbps"),
            native_name: fb.get("native_gbps"),
            native_name + "-mt": fb.get("native_mt_gbps"),
            "numpy": fb.get("numpy_gbps"),
        }
        best = max(
            ((v, k) for k, v in candidates.items() if v), default=(0.0, "none")
        )
        result["value"] = best[0]
        result["platform"] = "cpu"
        result["backend"] = best[1]
    # the evidence-based auto-backend decision for a TPU deployment, from
    # committed artifacts alone (no jax import in the parent) — what
    # new_encoder("auto") will select on-chip, and why
    try:
        from seaweedfs_tpu.ops.rs_codec import pick_device_backend

        result["auto_backend_on_tpu"] = pick_device_backend()[1]
    except Exception as e:  # noqa: BLE001
        result["auto_backend_on_tpu_error"] = str(e)[:200]
    # the CPU-side twin: what new_encoder("auto") will select on a plain
    # CPU host from committed BENCH xor evidence, and why
    try:
        from seaweedfs_tpu.ops.rs_codec import pick_cpu_backend

        result["auto_backend_on_cpu"] = pick_cpu_backend()[1]
    except Exception as e:  # noqa: BLE001
        result["auto_backend_on_cpu_error"] = str(e)[:200]
    result["vs_baseline"] = round(result["value"] / TARGET_GBPS, 4)
    _emit(result)


if __name__ == "__main__":
    mode = os.environ.get("BENCH_MODE", "")
    if mode == "cpu":
        mode_cpu()
    elif mode == "remote":
        mode_remote()
    elif mode == "rebuild_remote":
        mode_rebuild_remote()
    elif mode == "rebuild_trace":
        mode_rebuild_trace()
    elif mode == "ingest":
        mode_ingest()
    elif mode == "convert":
        mode_convert()
    elif mode == "xor":
        mode_xor(smoke="--smoke" in sys.argv)
    elif mode == "dp":
        mode_dp()
    elif mode == "mesh":
        mode_mesh()
    elif mode == "device":
        mode_device()
    else:
        main()
