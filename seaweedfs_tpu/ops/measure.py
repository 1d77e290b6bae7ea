"""Scan-chain throughput measurement — the ONE implementation of the
slope method shared by bench.py and scripts/kernel_sweep.py.

Method: jit a `lax.scan` of K chained applies into a single dispatch and
time K=1 vs K=8; the slope (t8-t1)/7 is the per-apply device time, with
the per-dispatch overhead cancelled out.
The xor-chain keeps every iteration data-dependent so XLA cannot hoist
or dedupe applies, while staying byte-reversible (cheap on the VPU).

Covers BOTH north-star shapes: encode ((B, C, N) -> (B, C+R, N) parity
append) and reconstruct ((B, C, N) survivor stack -> (B, W, N) decoded
shards) — `out_rows` names how many output rows the chain folds back
into the accumulator (W for a decode matrix, parity count for encode).
"""

from __future__ import annotations

import time


def scan_chain_gbps(
    encode_fn, data, data_bytes: int, iters: int = 3, out_rows: int = 4
) -> float:
    """Steady-state effective GB/s of `encode_fn` ((B, C, N) uint8 ->
    (B, R>=out_rows, N)) on device-resident `data`. `out_rows` is how many
    of the output's shard rows feed the xor chain (4 for RS(10+4) encode
    parity; len(wanted) for a fused decode matrix). Raises ValueError when
    timing noise swamps the slope — a non-positive slope is an invalid
    measurement, never a throughput."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, _c, n = data.shape

    def make_chain(k: int):
        @jax.jit
        def chain(d):
            def body(acc, i):
                return acc ^ encode_fn(d ^ i)[:, :out_rows, :], ()

            acc, _ = lax.scan(
                body,
                jnp.zeros((b, out_rows, n), jnp.uint8),
                jnp.arange(k, dtype=jnp.uint8),
            )
            return acc

        return chain

    def best_time(fn) -> float:
        jax.block_until_ready(fn(data))  # compile + warm
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(data))
            best = min(best, time.perf_counter() - t0)
        return best

    k1, k2 = 1, 8
    t1 = best_time(make_chain(k1))
    t2 = best_time(make_chain(k2))
    per = (t2 - t1) / (k2 - k1)
    if per <= 0:
        raise ValueError(f"slope not measurable: t({k1})={t1:.4f}s t({k2})={t2:.4f}s")
    return data_bytes / per / 1e9
