"""Device identification and JAX process set-up shared by every module
that imports jax."""

from __future__ import annotations

import os

#: JAX only persists programs that took at least this long to compile
#: (its own default is 1.0 s). The degraded-read programs — a 1x10 decode
#: over a 4 KiB..1 MiB bucket — compile in well under a second each, and a
#: volume server compiles one per distinct bucket and batch size, on the
#: first degraded GET that needs it: exactly the compile a restart should
#: not pay again. 0 caches them all; entries are a few hundred KiB.
_MIN_COMPILE_SECS_TO_CACHE = 0.0

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable; call right
    after `import jax`, before anything compiles. Returns the directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets no directory in code. Where it is not, the cache is
    `<checkout>/.jax_cache` — a fixed path, never a temp name, pid or time,
    because the path is part of a cache entry's key and a directory that
    moves never hits. Either way the minimum-compile-time threshold drops
    to 0 so the sub-second small-read programs are cached too (see
    `_MIN_COMPILE_SECS_TO_CACHE`)."""
    import jax

    # weedlint: ignore[env-raw-read] foreign (jax) env var, not a WEEDTPU knob
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not placed:
        placed = os.path.join(_CHECKOUT, ".jax_cache")
        if jax.config.jax_compilation_cache_dir != placed:
            jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_SECS_TO_CACHE
    )
    return placed


def is_tpu_device(d) -> bool:
    return d.platform == "tpu"


def describe_devices(devs=None) -> dict:
    """`{platform, kind, count}` of the devices this process holds, as JAX
    reports them (`devs`: an already-fetched `jax.devices()`). Raises what
    `jax.devices()` raises: a process that cannot get its device must say
    so, not carry on elsewhere."""
    if devs is None:
        import jax

        devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
