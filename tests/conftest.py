"""Test harness config: run all tests on CPU with 8 virtual devices so
multi-chip sharding paths are exercised without TPU hardware (SURVEY.md §4:
the `xla_force_host_platform_device_count` fake-backend strategy).

Must run before jax initializes, hence env mutation at import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Opt-in instrumented-lock mode (WEEDTPU_LOCK_OBSERVE=1): wrap
# threading.Lock/RLock BEFORE anything else imports, so every lock the
# package creates carries its creation site and the session records the
# actual acquisition-order graph. pytest_sessionfinish asserts the
# package's observed graph is acyclic — the dynamic half of weedlint's
# lock-discipline family.
from seaweedfs_tpu.utils import config as _weedtpu_config  # noqa: E402

_LOCK_RECORDER = None
if _weedtpu_config.env("WEEDTPU_LOCK_OBSERVE"):
    from seaweedfs_tpu.analysis import lockrec as _lockrec

    _LOCK_RECORDER = _lockrec.install()

# Opt-in filesystem-op recorder (WEEDTPU_FS_OBSERVE=<dir>): interpose the
# weedsafe recording shims over open/os.fsync/rename/unlink for paths
# under the named directory — the dynamic half of the durability family.
# The replay tests install their own scoped recorders; this session-level
# hook exists to capture traces from ad-hoc runs for offline inspection.
_FS_RECORDER = None
_fs_observe_root = _weedtpu_config.env("WEEDTPU_FS_OBSERVE")
if _fs_observe_root:
    from seaweedfs_tpu.analysis import fsrec as _fsrec

    _FS_RECORDER = _fsrec.install(_fs_observe_root)

# The library is generated output (not in git): build it once per worker
# before any test maps it. `make` compiles to a temporary name and renames,
# so xdist workers that race here each install a complete file.
from seaweedfs_tpu.utils import native as _weedtpu_native  # noqa: E402

_weedtpu_native.build()

# The suite compiles thousands of tiny CPU programs; keep them out of the
# checkout's persistent compile cache (utils/devices.setup_compile_cache).
import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')"
    )


@pytest.fixture(autouse=True)
def _reset_holder_suspicion():
    """Holder suspicion is process-wide and keyed by peer address; test
    servers reuse ephemeral ports, so suspicion leaking forward would make
    a later test's healthy peer read as wedged."""
    yield
    from seaweedfs_tpu.ec import suspicion

    suspicion.GLOBAL.reset()


@pytest.fixture(autouse=True)
def _reset_read_cache(monkeypatch):
    """The decoded-interval cache is process-wide and DEFAULT-ON in
    production; tests run it default-OFF so the hundreds of existing
    degraded-read tests keep measuring real decodes (repeat reads of one
    needle would otherwise collapse to cache hits and invalidate their
    latency/decode-count assertions). Cache-specific tests (and the
    weedload smoke) opt back in with monkeypatch.setenv; the cache itself
    is emptied after every test either way."""
    monkeypatch.setenv("WEEDTPU_READ_CACHE_MB", "0")
    yield
    from seaweedfs_tpu.ec import read_planner

    read_planner.CACHE.clear()


def pytest_sessionfinish(session, exitstatus):
    """Instrumented-lock gate: the tier-1 run's OBSERVED lock-order graph
    (package locks only — jax/stdlib internals order their own locks)
    must be acyclic, or the session fails even with every test green."""
    if _FS_RECORDER is not None:
        fs_out = _weedtpu_config.env("WEEDTPU_FS_OBSERVE_OUT")
        if fs_out:
            _FS_RECORDER.trace().dump(fs_out)
    if _LOCK_RECORDER is None:
        return
    out_path = _weedtpu_config.env("WEEDTPU_LOCK_OBSERVE_OUT")
    if out_path:
        _LOCK_RECORDER.dump(out_path)
    report = _LOCK_RECORDER.report(only_containing="seaweedfs_tpu")
    print(f"\n{report}")
    if _LOCK_RECORDER.cycles(only_containing="seaweedfs_tpu"):
        session.exitstatus = 1
