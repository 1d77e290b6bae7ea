"""Ring-pipelined multi-chip rebuild — the ring-attention analog for the
EC domain (SURVEY.md §5 "long-context" row; [ref: weed/shell/
command_ec_rebuild.go, mount empty — the reference copies every survivor
shard to ONE rebuilder node]).

`make_distributed_rebuild_fn` (parallel/sharded.py) flips shard-major
survivors to byte-major with one `all_to_all`, which materializes every
chip's full survivor working set at once. This module does the same
reconstruction as a RING: each chip keeps its resident survivor-shard
block and rotates it one hop per step with `lax.ppermute`, accumulating
that block's contribution to its own byte tile before passing it on.

    step k on chip c:
      block holds the survivor shards originally resident on chip c-k
      acc ^= decode_cols(owner[block]) x block[:, :, my_byte_tile]
      block -> ppermute -> chip c+1

After P steps every chip has seen every survivor exactly once. GF(2^8)
addition is XOR, so the per-owner partial outputs combine exactly.
Peak per-chip memory is ONE resident block (vs the all_to_all's full
regrouped survivor set) and each hop's transfer overlaps the matmul of
the block in hand — the same memory/latency trade ring attention makes
for KV blocks over ICI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from seaweedfs_tpu.ops import rs_jax
from seaweedfs_tpu.parallel import shard_map
from seaweedfs_tpu.parallel.sharded import matrix_bits, pad_survivor_matrix, placed_runner


def make_ring_rebuild_fn(mesh: Mesh, recon_m: np.ndarray, donate: bool = False):
    """Ring rebuild over the 'sp' mesh axis.

    recon_m: (L, S) GF(2^8) decode matrix (survivors -> lost shards). The
    survivor axis is zero-padded to a multiple of the ring size (zero
    matrix columns contribute nothing).

    Returns run(survivors (B, S, N) uint8) -> (B, L, N) device array with
    N sharded over 'sp' — the same contract as make_distributed_rebuild_fn,
    so the two are drop-in alternatives and directly comparable.
    donate=True releases the placed survivor buffer at dispatch-consume
    time (run() owns the device_put'ed copy; caller memory is never
    donated).
    """
    n_lost, n_surv = np.asarray(recon_m).shape
    sp = mesh.shape["sp"]
    padded = pad_survivor_matrix(recon_m, sp)
    s_pad = padded.shape[1]
    b_rec = matrix_bits(padded)
    l8 = n_lost * 8
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("dp", "sp", None),),
        out_specs=P("dp", None, "sp"),
    )
    def _ring_rebuild(survivors):
        # local block: (B/dp, s_pad/sp, N) — whole shards, full byte extent
        b_local, s_local, n = survivors.shape
        tile = n // sp
        cols_per = s_local * 8
        my = jax.lax.axis_index("sp")
        acc0 = jnp.zeros((b_local, n_lost, tile), dtype=jnp.uint8)
        # the loop carry varies per device (each chip accumulates its own
        # tile) — mark the unvarying zeros init accordingly or the scan
        # carry types mismatch under shard_map's varying-axes checks
        acc0 = jax.lax.pcast(acc0, ("dp", "sp"), to="varying")

        def body(k, carry):
            block, acc = carry
            owner = (my - k) % sp  # whose shards this block holds
            cols = jax.lax.dynamic_slice(
                b_rec, (0, owner * cols_per), (l8, cols_per)
            )
            tile_block = jax.lax.dynamic_slice(
                block, (0, 0, my * tile), (b_local, s_local, tile)
            )
            acc = acc ^ rs_jax.gf_apply(cols, tile_block)
            block = jax.lax.ppermute(block, "sp", perm)
            return block, acc

        _, acc = jax.lax.fori_loop(0, sp, body, (survivors, acc0))
        return acc

    donate_argnums = (0,) if donate else ()
    rebuild = jax.jit(_ring_rebuild, donate_argnums=donate_argnums)

    return placed_runner(mesh, rebuild, n_surv, s_pad)
