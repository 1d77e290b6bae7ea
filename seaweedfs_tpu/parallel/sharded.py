"""Multi-chip EC paths: volume-batch (dp) x stripe (sp) sharding via
shard_map over a Mesh — the TPU-native analog of the reference's
shell-orchestrated fan-out of encode/rebuild over volume servers
(SURVEY.md §2.5 rows DP/TP/SP, §2.6).

Design: the coding kernel is elementwise over the volume-batch axis and over
the stripe (byte) axis, so both shard cleanly with zero communication; the
collectives are global reductions (integrity checks, progress counters)
riding ICI as psums, plus the shard-major -> byte-major layout flip in
`make_distributed_rebuild_fn` — one all_to_all over 'sp' that lets every
chip rebuild lost shards for its own byte tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from seaweedfs_tpu.ops import gf8, rs_jax
from seaweedfs_tpu.parallel import shard_map


def matrix_bits(m: np.ndarray) -> jax.Array:
    """Device int8 lift of a GF(2^8) matrix (shared by every sharded path)."""
    return jnp.asarray(gf8.gf_matrix_to_bits(np.asarray(m, dtype=np.uint8)), dtype=jnp.int8)


_bits = matrix_bits  # internal alias


def pad_survivor_matrix(recon_m: np.ndarray, sp: int) -> np.ndarray:
    """Zero-pad a (L, S) decode matrix's survivor axis to a multiple of the
    'sp' axis size (zero columns contribute nothing). Shared by the
    all_to_all and ring rebuild formulations."""
    recon_m = np.asarray(recon_m, dtype=np.uint8)
    n_lost, n_surv = recon_m.shape
    s_pad = -(-n_surv // sp) * sp
    padded = np.zeros((n_lost, s_pad), dtype=np.uint8)
    padded[:, :n_surv] = recon_m
    return padded


def place_survivors(
    mesh: Mesh, survivors: np.ndarray, n_surv: int, s_pad: int
) -> jax.Array:
    """Validate + zero-pad + device_put survivors SHARD-major for a
    distributed rebuild: B over 'dp', padded shard rows over 'sp'. The
    validation/padding contract is identical for the all_to_all and ring
    paths — one copy, so they can never drift."""
    b, s, n = survivors.shape
    if s != n_surv:
        raise ValueError(f"want {n_surv} survivor shards, got {s}")
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    if b % dp:
        raise ValueError(f"batch {b} must divide evenly over dp={dp}")
    if n % sp:
        raise ValueError(f"shard length {n} must divide evenly over sp={sp}")
    if s_pad != s:
        survivors = np.concatenate(
            [survivors, np.zeros((b, s_pad - s, n), dtype=np.uint8)], axis=1
        )
    return jax.device_put(survivors, NamedSharding(mesh, P("dp", "sp", None)))


def placed_runner(mesh: Mesh, rebuild, n_surv: int, s_pad: int):
    """The host-facing form of a distributed rebuild program:
    run(survivors) = rebuild(place(survivors)), with its two halves as
    attributes: `run.place` (`place_survivors` for this program's survivor
    count) and `run.jitted` (the jitted program), for a caller that times
    them apart (the mesh dispatcher) and for compile tests, which lower
    `jitted` for a described mesh."""

    def place(survivors: np.ndarray) -> jax.Array:
        return place_survivors(mesh, survivors, n_surv, s_pad)

    def run(survivors: np.ndarray) -> jax.Array:
        return rebuild(place(survivors))

    run.place, run.jitted = place, rebuild
    return run


def make_matrix_apply_fn(mesh: Mesh, matrix: np.ndarray, donate: bool = False):
    """Column-sharded GF(2^8) matrix apply over the FULL device set:
    (C, W) uint8 with W sharded across every mesh axis -> (R, W), zero
    communication (GF matmul is column-independent, so each chip's column
    tile is an independent matmul). This is the mesh backend's generic
    dispatch — parity encode, repair projections, and delta columns all
    ride it; W must divide evenly over the device count (the dispatcher
    zero-pads, which is exact: zero columns map to zero columns).

    donate=True releases the input's device buffer at dispatch-consume
    time (the mesh dispatcher always device_puts its own copy first, so
    the donated buffer is jax-owned, never caller memory — the same
    early-release contract as rs_jax.apply_matrix)."""
    b_bits = _bits(matrix)
    spec = P(None, tuple(mesh.axis_names))

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec)
    def apply(cols):
        return rs_jax.gf_apply(b_bits, cols)

    donate_argnums = (0,) if donate else ()
    return jax.jit(apply, donate_argnums=donate_argnums)


def make_encode_fn(mesh: Mesh, parity_m: np.ndarray):
    """Jitted sharded encode: (B, D, N) uint8 -> (B, D+P, N) uint8, with B on
    'dp' and N on 'sp' (either axis may be size 1)."""
    b_bits = _bits(parity_m)
    spec = P("dp", None, "sp")

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
    )
    def encode(data):
        parity = rs_jax.gf_apply(b_bits, data)
        return jnp.concatenate([data, parity], axis=1)

    return encode


def make_apply_fn(mesh: Mesh, matrix: np.ndarray):
    """Jitted sharded matrix application (reconstruction with a cached decode
    matrix): (B, C, N) -> (B, R, N)."""
    b_bits = _bits(matrix)
    spec = P("dp", None, "sp")

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec)
    def apply(survivors):
        return rs_jax.gf_apply(b_bits, survivors)

    return apply


def make_ec_cycle_fn(mesh: Mesh, parity_m: np.ndarray, recon_m: np.ndarray, lost_ids, survivor_ids):
    """The full-step function the driver dry-runs: encode -> lose shards ->
    reconstruct -> global integrity psum. Exercises dp x sp sharding plus an
    ICI collective, on one jit. On a mesh WITH a 'dcn' axis the batch also
    shards over it and the reduction is staged: intra-slice psum over ICI
    axes first, then one scalar psum across 'dcn' — the only thing that
    crosses DCN (SURVEY §2.6 pod↔pod).

    Returns fn(data (B, D, N)) -> (shards (B, T, N), global_mismatches ())."""
    b_enc = _bits(parity_m)
    b_rec = _bits(recon_m)
    lost_ids = tuple(lost_ids)
    survivor_ids = tuple(survivor_ids)
    has_dcn = "dcn" in mesh.axis_names
    spec = P(("dcn", "dp") if has_dcn else "dp", None, "sp")
    ici_axes = tuple(a for a in mesh.axis_names if a != "dcn")

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,),
        out_specs=(spec, P()),
    )
    def step(data):
        parity = rs_jax.gf_apply(b_enc, data)
        shards = jnp.concatenate([data, parity], axis=1)
        survivors = shards[:, survivor_ids, :]
        rebuilt = rs_jax.gf_apply(b_rec, survivors)
        want = shards[:, lost_ids, :]
        bad = jax.lax.psum(jnp.sum(rebuilt != want), ici_axes)
        if has_dcn:
            bad = jax.lax.psum(bad, "dcn")
        return shards, bad

    return step


def shard_batch(mesh: Mesh, data: np.ndarray) -> jax.Array:
    """Place a (B, C, N) host array onto the mesh with B on dp, N on sp."""
    return jax.device_put(data, NamedSharding(mesh, P("dp", None, "sp")))


def make_multislice_ec_cycle_fn(
    mesh: Mesh,
    parity_m: np.ndarray,
    recon_m: np.ndarray,
    lost_ids,
    survivor_ids,
):
    """Host-facing wrapper of make_ec_cycle_fn for a ('dcn', 'dp', 'sp')
    mesh (SURVEY §2.6 pod↔pod: jax multi-slice over DCN for rack-scale
    rebuild fan-out). Slices own disjoint volume sub-batches, heavy
    collectives ride ICI, one scalar crosses DCN — see make_ec_cycle_fn.
    On hardware, 'dcn' maps to slices (mesh_utils
    create_hybrid_device_mesh); the CPU test mesh simulates it with the
    outermost axis, exercising identical sharding/collective structure."""
    if "dcn" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'dcn' axis")
    step = make_ec_cycle_fn(mesh, parity_m, recon_m, lost_ids, survivor_ids)
    spec = P(("dcn", "dp"), None, "sp")
    batch_div = mesh.shape["dcn"] * mesh.shape["dp"]
    sp = mesh.shape["sp"]

    def run(data: np.ndarray):
        b, _c, n = data.shape
        if b % batch_div:
            raise ValueError(f"batch {b} must divide evenly over dcn*dp={batch_div}")
        if n % sp:
            raise ValueError(f"shard length {n} must divide evenly over sp={sp}")
        x = jax.device_put(data, NamedSharding(mesh, spec))
        return step(x)

    return run


def make_distributed_rebuild_fn(mesh: Mesh, recon_m: np.ndarray, donate: bool = False):
    """Multi-chip distributed rebuild — the TPU-native analog of the
    reference's `ec.rebuild` fan-out of survivor-shard copies to one
    rebuilder node ([ref: weed/shell/command_ec_rebuild.go, mount empty —
    SURVEY.md §3.3]), except every chip participates instead of one node
    doing all the work.

    Storage hands survivors over SHARD-MAJOR (a node/chip holds whole
    shards — the on-disk `.ecNN` layout); the decode matmul wants
    BYTE-MAJOR (each chip needs the same byte range of ALL survivors).
    That layout flip is exactly one `all_to_all` over the mesh's 'sp'
    axis riding ICI; after it, reconstruction of the lost shards is a
    zero-communication matmul per chip on its byte tile, and the output
    comes back byte-sharded, ready for striped writes.

    recon_m: (L, S) GF(2^8) decode matrix mapping S survivors to L lost
    shards (from rs_codec._reconstruction_matrix). The survivor axis is
    zero-padded up to a multiple of the 'sp' axis size (zero matrix
    columns contribute nothing, so correctness is unaffected).

    Returns run(survivors (B, S, N) uint8) -> (B, L, N) device array.
    B must divide evenly over 'dp' and N over 'sp'. donate=True releases
    the placed survivor buffer at dispatch-consume time (run() owns the
    device_put'ed copy, so donation never touches caller memory).
    """
    n_surv = np.asarray(recon_m).shape[1]
    padded = pad_survivor_matrix(recon_m, mesh.shape["sp"])
    s_pad = padded.shape[1]
    b_rec = _bits(padded)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("dp", "sp", None),),
        out_specs=P("dp", None, "sp"),
    )
    def _rebuild(survivors):
        # local view: (B/dp, s_pad/sp, N) whole-shard rows ->
        # (B/dp, s_pad, N/sp) full survivor set for this chip's byte tile
        # The byte axis is split as an axis of its own, (.., sp, N/sp), so
        # the collective never slices the minor (lane) dimension: splitting
        # axis 2 of the (B, S, N) block directly compiles for the v5e in
        # time LINEAR in N (~10 min at the rebuild pipeline's 4 MiB width).
        b, s_local, n = survivors.shape
        sp = mesh.shape["sp"]
        regrouped = jax.lax.all_to_all(
            survivors.reshape(b, s_local, sp, n // sp),
            "sp", split_axis=2, concat_axis=1, tiled=True,
        ).reshape(b, s_local * sp, n // sp)
        return rs_jax.gf_apply(b_rec, regrouped)

    donate_argnums = (0,) if donate else ()
    rebuild = jax.jit(_rebuild, donate_argnums=donate_argnums)

    return placed_runner(mesh, rebuild, n_surv, s_pad)
