"""Device-side kernel sweep: encode AND rebuild throughput of every kernel
variant (target: BASELINE.json 40 GB/s/chip, 10+4).

Variants swept (all byte-exact vs gf8 golden; the staged family —
see ops/rs_pallas.py VARIANTS):
  xla               rs_jax.gf_apply (the default device path)
  pallas[-mxu]-T    rs_pallas fused kernel, T in {8k, 16k, 32k, 64k} or
                    `auto` (VMEM-budget tile chooser); mxu one of
                      int8    shift+mask unpack, int8 MXU
                      bf16    bf16 MXU (exact: partial sums <= 80 < 256)
                      u8      shift-free mask+compare unpack
                      mplane  per-plane K=C matmuls, one accumulator —
                              never materializes the (8C, T) bit stack
                      dma     manual double-buffered HBM->VMEM chunk ring
  rebuild-*         the same kernels driven by a fused survivors->missing
                    decode matrix (worst allowed loss: 2 data + 2 parity) —
                    the shape the pipelined rebuild_ec_files dispatches.

Method: scan-chain slope (ops/measure.py) — time K=1 vs K=8 chains in one
dispatch; the slope is per-apply device time, free of the per-dispatch
floor.

INCREMENTAL HARVESTING: with `--out PATH` every config's record is appended
to PATH as one JSON line THE MOMENT it lands (write+flush per record), and
a re-run against the same PATH resumes — configs already persisted are
skipped, so a run that is cut extends the harvest instead of restarting
it. A config that crashed mid-dispatch left no record and is retried.
`--no-resume` forces a fresh sweep (PATH is truncated).

Usage: python scripts/kernel_sweep.py [--quick|--tiny|--smoke]
                                      [--out PATH] [--no-resume]
  --quick  fewer tiles
  --tiny   toy sizes, correctness + timing (off a TPU the fused variants
           are recorded as failed: only --smoke asks for the interpreter)
  --smoke  CI gate: JAX_PLATFORMS=cpu forced, toy sizes, correctness ONLY
           (no scan-chain timing) across EVERY variant in interpret mode,
           exits nonzero if ANY variant fails its byte-exactness gate —
           wired into tests so kernel refactors cannot silently break
           the sweep.
Emits one JSON line per variant + a summary line; outside --smoke it exits
nonzero only on harness failure (a variant that fails to compile is
recorded, not fatal).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, ".")

SMOKE = "--smoke" in sys.argv
if SMOKE:
    # the gate runs the Pallas interpreter on the CPU and must never take
    # a chip — pin cpu BEFORE jax resolves a backend
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from seaweedfs_tpu.ops import gf8, rs_jax, rs_pallas  # noqa: E402

if SMOKE or "--tiny" in sys.argv:  # toy sizes
    B, N = 2, 32768
else:
    B, N = 8, 4 << 20
DATA_BYTES = B * 10 * N

#: the staged kernel family, sweep order = most-promising-first so a run
#: that is cut has harvested the highest-value configs
MXUS = ("int8", "bf16", "u8", "mplane", "dma")


def _arg_value(flag: str) -> str | None:
    if flag in sys.argv:
        i = sys.argv.index(flag)
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return None


def load_done(
    path: str, platform: str | None = None, tiny: bool | None = None
) -> dict[str, dict]:
    """Variant records already persisted by a previous (interrupted) run.
    Only COMPLETE records exist in the file (each line is written after
    its config finished — success or recorded error), so presence alone
    means done; a mid-dispatch crash left no line and will be retried.

    Records from a DIFFERENT run mode never count as done: a cpu/--tiny
    sanity run landing in the harvest file must not mark configs
    harvested for the real on-chip sweep (the assembler already excludes
    such records from evidence, so skipping on them would leave the
    harvest permanently empty of usable numbers)."""
    done: dict[str, dict] = {}
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # a torn tail line from a crash mid-write
                name = rec.get("variant")
                if not name:
                    continue
                if platform is not None and rec.get("platform") != platform:
                    continue
                if tiny is not None and bool(rec.get("tiny")) != tiny:
                    continue
                done[name] = rec
    except OSError:
        pass
    return done


def open_resume_out(out_path: str, resume: bool):
    """Open the harvest file for the persist discipline. On resume, a
    crash mid-write leaves a torn tail with no newline; appending straight
    after it would glue the next record onto the fragment and corrupt
    BOTH — terminate the tail first (load_done already skips the torn
    fragment either way)."""
    out_f = open(out_path, "a" if resume else "w", encoding="utf-8")
    if resume and out_f.tell() > 0:
        with open(out_path, "rb") as chk:
            chk.seek(-1, os.SEEK_END)
            if chk.read(1) != b"\n":
                out_f.write("\n")
                out_f.flush()
    return out_f


def persist_record(out_f, rec: dict) -> None:
    """One line per config, flushed+fsynced AS IT LANDS: a hang one
    variant later must not cost the results already measured."""
    out_f.write(json.dumps(rec) + "\n")
    out_f.flush()
    os.fsync(out_f.fileno())


def _median_time(fn, iters=3, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def steady_gbps(encode_fn, data, out_rows):
    from seaweedfs_tpu.ops.measure import scan_chain_gbps

    return scan_chain_gbps(encode_fn, data, DATA_BYTES, out_rows=out_rows)


def build_variants(quick: bool):
    """-> [(name, fn, gf_matrix)] in harvest-priority order."""
    pm = gf8.parity_matrix(10, 4)
    b_bits = rs_jax.lifted_matrix(pm)

    # rebuild shape (the second north-star target): ONE fused decode
    # matrix for the worst allowed loss — 2 data + 2 parity shards gone —
    # applied to the (B, 10, N) survivor stack exactly as the pipelined
    # rebuild_ec_files dispatches it. Same kernels, different matrix.
    from seaweedfs_tpu.ops.rs_codec import _reconstruction_matrix

    lost = (0, 5, 11, 13)
    surv = tuple(s for s in range(14) if s not in lost)[:10]
    dm = _reconstruction_matrix("vandermonde", 10, 4, surv, lost)
    dm_bits = rs_jax.lifted_matrix(dm)

    def fused(bits, tile, mxu="int8"):
        # _apply_pm clamps explicit tiles to the (padded) input width, so
        # tiles larger than the golden input are safe to pass through;
        # tile=None lets auto_tile pick.
        return lambda d: rs_pallas.gf_apply_fused(
            bits, d, tile=tile, mxu=mxu, interpret=SMOKE
        )

    variants = [
        ("xla", lambda d: rs_jax.gf_apply(b_bits, d), pm),
        ("rebuild-xla", lambda d: rs_jax.gf_apply(dm_bits, d), dm),
    ]
    # auto-tiled form of every staged variant first (the production
    # configs), then the explicit-tile grid
    for mxu in MXUS:
        tag = "pallas-auto" if mxu == "int8" else f"pallas-{mxu}-auto"
        variants.append((tag, fused(b_bits, None, mxu), pm))
    variants.append(("rebuild-pallas-auto", fused(dm_bits, None), dm))
    variants.append(("rebuild-pallas-dma-auto", fused(dm_bits, None, "dma"), dm))

    if SMOKE:
        tiles = [8192]  # one explicit tile proves the tiled path; cheap
    elif quick:
        tiles = [8192, 16384]
    else:
        tiles = [8192, 16384, 32768, 65536]
    for t in tiles:
        for mxu in MXUS:
            tag = f"pallas-{t}" if mxu == "int8" else f"pallas-{mxu}-{t}"
            variants.append((tag, fused(b_bits, t, mxu), pm))
        variants.append((f"rebuild-pallas-{t}", fused(dm_bits, t), dm))
    return variants


def main():
    quick = "--quick" in sys.argv
    out_path = _arg_value("--out")
    resume = out_path is not None and "--no-resume" not in sys.argv
    platform = jax.devices()[0].platform
    print(json.dumps({"platform": platform, "smoke": SMOKE, "out": out_path}), flush=True)

    done = load_done(out_path, platform=platform, tiny=B == 2) if resume else {}
    out_f = None
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        out_f = open_resume_out(out_path, resume)

    def persist(rec: dict) -> None:
        if out_f is not None:
            persist_record(out_f, rec)

    key = jax.random.PRNGKey(0)
    data = jax.block_until_ready(
        jax.random.randint(key, (B, 10, N), 0, 256, dtype=jnp.uint8)
    )

    # golden check inputs (small) — verify each variant is byte-exact
    # against its OWN gf8 matrix product (encode variants vs the parity
    # matrix, rebuild variants vs the decode matrix)
    small = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (1, 10, 8192), 0, 256, dtype=jnp.uint8)
    )

    variants = build_variants(quick)
    results = {}
    failed = []
    skipped = []
    for name, fn, gm in variants:
        if name in done:
            skipped.append(name)
            prior = done[name]
            if isinstance(prior.get("steady_gbps"), (int, float)):
                results[name] = prior["steady_gbps"]
            print(json.dumps({"variant": name, "resumed": True}), flush=True)
            continue
        rec = {
            "variant": name,
            "platform": platform,
            "tiny": B == 2,
            "when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        try:
            golden = gf8.gf_mat_mul(gm, small[0])
            got = np.asarray(fn(jnp.asarray(small))[0, : golden.shape[0]])
            exact = bool((got == golden).all())
            rec["exact"] = exact
            if not exact:
                raise ValueError("output mismatch vs gf8 golden")
            if not SMOKE:
                t = _median_time(
                    lambda: jax.block_until_ready(fn(data)), iters=5, warmup=2
                )
                rec["per_call_gbps"] = round(DATA_BYTES / t / 1e9, 3)
                rec["steady_gbps"] = round(
                    steady_gbps(fn, data, out_rows=gm.shape[0]), 3
                )
                results[name] = rec["steady_gbps"]
        except Exception as e:  # noqa: BLE001
            rec["error"] = str(e)[:300]
            failed.append(name)
        print(json.dumps(rec), flush=True)
        persist(rec)

    if out_f is not None:
        out_f.close()
    if SMOKE:
        print(
            json.dumps(
                {
                    "smoke_ok": not failed,
                    "variants": len(variants),
                    "failed": failed,
                    "skipped": len(skipped),
                }
            ),
            flush=True,
        )
        return 1 if failed else 0
    if results:
        best = max(results, key=results.get)
        print(
            json.dumps(
                {"best": best, "steady_gbps": results[best], "skipped": len(skipped)}
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
