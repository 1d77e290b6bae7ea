"""Ask the v5e's compiler, without the chip.

The TPU compiler is installed wherever JAX is; it compiles for a chip that
is described (`v5e:2x2`) and not attached. These tests compile the erasure
coding main path's XLA programs at the widths the file pipelines really
dispatch, and every (variant, shape) of the fused Pallas family, and so
refuse here what the chip's compiler would refuse there: an op the target
cannot legalize, a slice off the tiling, a kernel or a batch that does not
fit. Interpret mode and `jax.export` lowering see none of that.

Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture — never at import,
never in conftest, never in a child process — and every test of it lives in
this one file, so that under xdist exactly one worker loads libtpu.
"""

import inspect

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from seaweedfs_tpu.ec import constants, stripe
from seaweedfs_tpu.ec.stripe import DEFAULT_PIPELINE_DEPTH
from seaweedfs_tpu.ops import rs_jax, rs_pallas
from seaweedfs_tpu.ops.rs_codec import CODE_FAMILIES, Encoder

V5E_HBM_BYTES = 16 * 10**9


def _default(fn, name):
    return inspect.signature(fn).parameters[name].default


def _encode_width(k: int) -> int:
    """Flat width of write_ec_files' steady dispatch (_encode_rows): as many
    EC_BUFFER_SIZE segments per shard as fit its default max_batch_bytes —
    read from the signature, so a changed default is compiled, not assumed."""
    per = constants.EC_BUFFER_SIZE
    return max(1, _default(stripe.write_ec_files, "max_batch_bytes") // (k * per)) * per


def _rebuild_width(k: int) -> int:
    """Flat width of rebuild_ec_files' steady dispatch, likewise."""
    per = _default(stripe.rebuild_ec_files, "buffer_size")
    return max(1, _default(stripe.rebuild_ec_files, "max_batch_bytes") // (k * per)) * per


# name -> (fn, out rows, in rows, width[, "exact"]): the XLA main path as the
# smoke drives it. "exact": the slot in the exact crossing's (rows * k, width / k)
# view (`rs_jax.apply_matrix`), what a steady batch is handed as where its result
# is one row
XLA_PROGRAMS = {
    "encode_10p4_flat": (rs_jax.gf_apply, 4, 10, _encode_width(10)),
    "encode_10p4_flat_donated": (rs_jax._gf_apply_donated, 4, 10, _encode_width(10)),
    "reconstruct_4from10_rebuild": (rs_jax._gf_apply_donated, 4, 10, _rebuild_width(10)),
    "small_read_smallest_bucket": (rs_jax.gf_apply, 1, 10, Encoder.RECONSTRUCT_BUCKETS[0]),
    "small_read_largest_bucket": (rs_jax.gf_apply, 1, 10, Encoder.RECONSTRUCT_BUCKETS[-1]),
    "encode_cauchy_12_3_flat": (rs_jax._gf_apply_donated, 3, 12, _encode_width(12)),
    # many10p4.rebuild-1lost-each: a packed batch of one signature is the flat
    # program with one row out; one that holds a seam is the tiled program
    "reconstruct_1from10_rebuild": (rs_jax._gf_apply_donated, 1, 10, _rebuild_width(10)),
    "reconstruct_1from10_packed": (rs_jax._gf_apply_tiled_donated, 1, 10, _rebuild_width(10)),
    # spread10p4's seam batch: a 3-lost volume's tail beside a 4-lost volume's head
    "reconstruct_4from10_packed": (rs_jax._gf_apply_tiled_donated, 4, 10, _rebuild_width(10)),
    "reconstruct_1from10_exact": (rs_jax._gf_apply_donated, 1, 10, _rebuild_width(10), "exact"),
    "reconstruct_1from10_packed_exact": (rs_jax._gf_apply_tiled_donated, 1, 10, _rebuild_width(10), "exact"),
    "reconstruct_1from12_exact": (rs_jax._gf_apply_donated, 1, 12, _rebuild_width(12), "exact"),
    "small_read_smallest_bucket_exact": (rs_jax.gf_apply, 1, 10, Encoder.RECONSTRUCT_BUCKETS[0], "exact"),
    "small_read_largest_bucket_exact": (rs_jax.gf_apply, 1, 10, Encoder.RECONSTRUCT_BUCKETS[-1], "exact"),
}

# shape classes the storage engine hits, per fused variant (the table
# test_rs_pallas checks every variant is in): encode at the old and new
# default tiles, 4-from-10 and 10-from-10 reconstruct, the minimum tile
FUSED_SHAPES = (
    {"name": "encode_10p4_tile8192", "rows": 4, "cols": 10, "tile": 8192, "batch": 4},
    {"name": "encode_10p4_tile32768", "rows": 4, "cols": 10, "tile": 32768, "batch": 4},
    {"name": "encode_10p4_tile24576_bf16", "rows": 4, "cols": 10, "tile": 24576,
     "batch": 4, "mxu": "bf16"},
    {"name": "encode_10p4_tile32768_u8", "rows": 4, "cols": 10, "tile": 32768,
     "batch": 4, "mxu": "u8"},
    {"name": "encode_10p4_tile32768_mplane", "rows": 4, "cols": 10, "tile": 32768,
     "batch": 4, "mxu": "mplane"},
    {"name": "encode_10p4_tile65536_dma", "rows": 4, "cols": 10, "tile": 65536,
     "batch": 4, "mxu": "dma"},
    {"name": "reconstruct_4from10_tile32768_dma", "rows": 4, "cols": 10,
     "tile": 32768, "batch": 1, "mxu": "dma"},
    {"name": "reconstruct_4from10_tile8192", "rows": 4, "cols": 10, "tile": 8192, "batch": 1},
    {"name": "reconstruct_10from10_tile8192", "rows": 10, "cols": 10, "tile": 8192, "batch": 1},
    {"name": "small_read_tile128", "rows": 4, "cols": 10, "tile": 128, "batch": 1},
)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The TPU compiler works in bursts of ~4 threads; under xdist those
    # bursts disturbed the p99 gates of tests/test_slo_harness.py on the
    # other workers. Its threads are made below and inherit this thread's
    # scheduling policy and affinity: one core, and only when idle.
    policy_was, cpus_were = os.sched_getscheduler(0), os.sched_getaffinity(0)
    os.sched_setscheduler(0, os.SCHED_IDLE, os.sched_param(0))
    os.sched_setaffinity(0, {max(cpus_were)})
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        os.sched_setaffinity(0, cpus_were)
        try:
            os.sched_setscheduler(0, policy_was, os.sched_param(0))
        except PermissionError:  # then this worker stays idle-priority
            pass


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off around the
    compiles: an entry written for a described chip cannot be read back
    without one, and every later compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name", list(XLA_PROGRAMS))
def test_xla_main_path_compiles_and_fits(one_chip, name):
    """Each program compiles for the v5e, and a pipeline of it — depth
    batches in flight plus the one being staged — fits the chip's 16 GB."""
    fn, rows, cols, width, *exact = XLA_PROGRAMS[name]
    matrix = (rows * 8, cols * 8)
    if fn is rs_jax._gf_apply_tiled_donated:  # a matrix for each tile of the slot
        matrix = (width // Encoder(cols, 4, backend="jax").block_tile(width),) + matrix
    k = rs_jax.crossing_chunks(cols) if exact else 1
    compiled = fn.lower(
        _shape(matrix, jnp.int8, one_chip),
        _shape((cols * k, width // k), jnp.uint8, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    if exact:
        # the device holds the slot and the result in the bytes they have (41,943,040
        # of a rebuild slot, 4,194,304 of its one-row decode) plus the lifted matrices:
        # the (10, N) slot's 1.6 x and the (1, N) result's 4 x of padding cannot come
        # back unseen; and a one-row program keeps no temporaries, which is what the
        # crossing's rule rests on (`rs_jax._exact_chunks`)
        lifted = (matrix[0] if len(matrix) == 3 else 1) * 32 * 1024  # <= a padded (32, 96) int8 each
        assert cols * width <= mem.argument_size_in_bytes <= cols * width + lifted
        assert mem.output_size_in_bytes == rows * width
        assert mem.temp_size_in_bytes == 0
    per_batch = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    )
    assert mem.argument_size_in_bytes >= cols * width
    assert per_batch * (DEFAULT_PIPELINE_DEPTH + 1) < V5E_HBM_BYTES, (
        f"{name}: {per_batch} bytes per batch x (depth {DEFAULT_PIPELINE_DEPTH} + 1)"
    )


def _compile_fused(one_chip, rows, cols, tile, batch, mxu):
    return rs_pallas._apply_padded_jit.lower(
        _shape((rows * 8, cols * 8), jnp.int8, one_chip),
        _shape((batch, cols, 2 * tile), jnp.uint8, one_chip),
        tile=tile, interpret=False, mxu=mxu,
    ).compile()


@pytest.mark.parametrize("spec", FUSED_SHAPES, ids=lambda s: s["name"])
def test_fused_kernel_compiles(one_chip, spec):
    compiled = _compile_fused(
        one_chip, spec["rows"], spec["cols"], spec["tile"], spec["batch"],
        spec.get("mxu", "int8"),
    )
    assert "tpu_custom_call" in compiled.as_text()


def _mesh_programs(mesh):
    """The mesh backend's three programs at the file pipelines' widths, as
    MeshDispatch lays them out on a dp x sp mesh: (jitted fn, input shape,
    input spec, collective the compiled text must hold or None)."""
    from jax.sharding import PartitionSpec as P

    from seaweedfs_tpu.ops import gf8
    from seaweedfs_tpu.ops.rs_codec import _reconstruction_matrix
    from seaweedfs_tpu.parallel import ring, sharded

    lost = (0, 3, 11, 13)
    surv = tuple(i for i in range(14) if i not in lost)
    recon = _reconstruction_matrix("vandermonde", 10, 4, surv, lost)
    dp = mesh.shape["dp"]
    stack = (dp, 10, _rebuild_width(10) // dp)
    return {
        "apply_encode": (
            sharded.make_matrix_apply_fn(mesh, gf8.parity_matrix(10, 4), donate=True),
            (10, _encode_width(10)), P(None, ("dp", "sp")), None,
        ),
        "rebuild_ring": (
            ring.make_ring_rebuild_fn(mesh, recon, donate=True).jitted,
            stack, P("dp", "sp", None), "collective-permute",
        ),
        "rebuild_alltoall": (
            sharded.make_distributed_rebuild_fn(mesh, recon, donate=True).jitted,
            stack, P("dp", "sp", None), "all-to-all",
        ),
    }


@pytest.mark.parametrize("name", ["apply_encode", "rebuild_ring", "rebuild_alltoall"])
def test_mesh_program_compiles_for_four_chips(topo, one_chip, name):
    """One program across the 2x2 mesh of a four-chip v5e host, with the
    collective each rebuild variant is named for. (The all_to_all once
    split the minor axis and took ~10 min to compile at this width.)"""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "sp"))
    fn, shape, spec, collective = _mesh_programs(mesh)[name]
    compiled = fn.lower(_shape(shape, jnp.uint8, NamedSharding(mesh, spec))).compile()
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes) * (
        DEFAULT_PIPELINE_DEPTH + 1
    ) < V5E_HBM_BYTES
    if collective:
        assert collective in compiled.as_text()


@pytest.mark.parametrize("mxu", rs_pallas.VARIANTS)
def test_auto_tile_choice_compiles(one_chip, mxu):
    """auto_tile's VMEM model is only as good as the compiler's verdict:
    its pick for every registered geometry's encode, and for the default
    geometry's full reconstruct (the most rows), must be a kernel the v5e
    accepts."""
    shapes = {(g.parity_shards, g.data_shards) for g in CODE_FAMILIES.values()}
    shapes.add((10, 10))
    for rows, cols in sorted(shapes):
        tile = rs_pallas.auto_tile(cols, rows, mxu)
        _compile_fused(one_chip, rows, cols, tile, 1, mxu)
