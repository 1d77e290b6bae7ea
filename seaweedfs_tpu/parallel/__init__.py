"""Multi-chip parallel paths (dp x sp shard_map encode/rebuild, ring
rebuild)."""

import jax

from seaweedfs_tpu.utils.devices import setup_compile_cache

setup_compile_cache()

shard_map = jax.shard_map
