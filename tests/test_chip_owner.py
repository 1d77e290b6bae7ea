"""One process owns the chip, and nothing hides which device it got.

The volume server's encoder is the only thing that touches jax's devices:
a failure to get the device stops its start-up instead of sliding to a CPU
backend, its /status says what it runs on, tools read that instead of
asking jax themselves, the compile cache lands where it can be found again,
and the native library is built by an explicit, atomic step. The last test
is chip_smoke.py's CPU rehearsal: every phase, and never `"ok": true`."""

import json
import os
import shutil
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ops import rs_codec
from seaweedfs_tpu.utils import devices, native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_lost(*_a, **_k):
    raise RuntimeError("Unable to initialize backend 'tpu': the chip is held")


# -- no fallback that hides the device ----------------------------------------


def test_new_encoder_auto_reraises_when_jax_devices_raises(monkeypatch):
    import jax

    monkeypatch.delenv("WEEDTPU_BACKEND", raising=False)
    monkeypatch.setattr(jax, "devices", _chip_lost)
    with pytest.raises(RuntimeError, match="the chip is held"):
        rs_codec.new_encoder()


def test_volume_server_startup_stops_when_the_chip_is_lost(tmp_path, monkeypatch):
    """The chip taken from under a server: construction fails, nothing
    listens, nothing is served from a CPU backend."""
    import jax

    monkeypatch.delenv("WEEDTPU_BACKEND", raising=False)
    monkeypatch.setattr(jax, "devices", _chip_lost)
    with pytest.raises(RuntimeError, match="the chip is held"):
        VolumeServer([str(tmp_path)], "127.0.0.1:1")


def test_forced_pallas_backend_off_tpu_raises_instead_of_interpreting():
    enc = rs_codec.Encoder(10, 4, backend="pallas")
    data = [np.zeros(256, dtype=np.uint8) for _ in range(10)]
    with pytest.raises(RuntimeError, match="needs a TPU"):
        enc.encode(data)


def test_selection_names_the_device_jax_reported():
    sel = rs_codec.new_encoder(backend="jax").selection
    assert sel["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert rs_codec.new_encoder().selection["device"] == sel["device"]
    # a host backend asked for by name touches no device and reports none
    assert "device" not in rs_codec.new_encoder(backend="numpy").selection


def test_is_tpu_device_is_platform_tpu_only():
    class D:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert devices.is_tpu_device(D("tpu", "TPU v5 lite"))
    assert not devices.is_tpu_device(D("cpu", "TPU v5 lite"))
    assert not devices.is_tpu_device(D("gpu", "tpu-ish"))


# -- the compile cache can be placed from outside ------------------------------


_CACHE_PROBE = """
import json, jax
updates = []
_real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), _real(k, v))[1]
from seaweedfs_tpu.ops import rs_jax  # calls setup_compile_cache at import
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "set_in_code": "jax_compilation_cache_dir" in updates,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


@pytest.mark.parametrize("placed", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_directory_placement(tmp_path, placed):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "outside")
    p = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    if placed:
        # jax reads the variable itself; the helper sets no directory in code
        assert got["dir"] == str(tmp_path / "outside") and not got["set_in_code"]
    else:
        assert got["dir"] == os.path.join(ROOT, ".jax_cache") and got["set_in_code"]
    assert got["min_secs"] == 0.0  # the sub-second small-read programs too


# -- the native library: an explicit, atomic build ------------------------------


@pytest.fixture
def native_copy(tmp_path, monkeypatch):
    d = tmp_path / "native"
    d.mkdir()
    for name in ("Makefile", "weedtpu.cc"):
        shutil.copy(os.path.join(ROOT, "native", name), d / name)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(d))
    monkeypatch.setattr(native, "_LIB_PATH", str(d / "libweedtpu.so"))
    return d


def test_native_build_is_atomic(native_copy):
    """make compiles under a temporary name and renames: after the build
    there is exactly the library, and a rebuild replaces the inode rather
    than rewriting a file some process may have mapped."""
    assert native.build() == str(native_copy / "libweedtpu.so")
    assert sorted(os.listdir(native_copy)) == ["Makefile", "libweedtpu.so", "weedtpu.cc"]
    first = os.stat(native_copy / "libweedtpu.so").st_ino
    os.utime(native_copy / "weedtpu.cc")  # source newer than the library
    native.build()
    assert os.stat(native_copy / "libweedtpu.so").st_ino != first
    assert not [n for n in os.listdir(native_copy) if ".tmp." in n]


def test_failed_native_build_is_an_error_with_the_compilers_output(native_copy):
    native.build()
    good = (native_copy / "libweedtpu.so").read_bytes()
    (native_copy / "weedtpu.cc").write_text("this is not C++\n")
    with pytest.raises(native.NativeBuildError, match="error"):
        native.build()
    # and the library that was there is still whole
    assert (native_copy / "libweedtpu.so").read_bytes() == good


# -- the server says what it runs on; tools ask it ------------------------------


@pytest.fixture
def stack(tmp_path):
    master = MasterServer(port=0, reap_interval=3600)
    master.start()
    vs = VolumeServer([str(tmp_path / "vol")], master.address, heartbeat_interval=0.3)
    vs.start()
    yield master, vs
    vs.stop()
    master.stop()


def test_status_carries_ec_backend_device(stack):
    master, vs = stack
    with urllib.request.urlopen(f"http://{vs.url}/status", timeout=10) as r:
        st = json.loads(r.read().decode())
    sel = vs.store.encoder.selection
    assert st["ec_backend"]["backend"] == sel["backend"]
    assert st["ec_backend"]["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    # and the VolumeStatus rpc reply, through the strict proto codec too
    from seaweedfs_tpu import rpc
    from seaweedfs_tpu.pb import VOLUME_SERVICE, wire

    client = MasterClient(master.address)
    fid = client.submit(b"x" * 100).fid
    client.close()
    with rpc.RpcClient(vs.grpc_address) as c:
        reply = c.call(VOLUME_SERVICE, "VolumeStatus", {"volume_id": int(fid.split(",")[0])})
    assert reply["ec_backend"]["device"]["platform"] == "cpu"
    assert reply["ec_backend"]["backend"] == sel["backend"]
    ser, de = wire.codec().response_serdes(VOLUME_SERVICE, "VolumeStatus")
    assert de(ser(reply))["ec_backend"]["device"]["count"] == 8


_TOOL_PROBE = """
import sys
from seaweedfs_tpu.__main__ import main
rc = main(sys.argv[1:])
assert rc == 0, rc
assert "jax" not in sys.modules, "a tool imported jax: it would take the chip from the server"
"""


@pytest.mark.parametrize("tool", ["shell", "upload"])
def test_tools_never_import_jax(stack, tmp_path, tool):
    master, vs = stack
    if tool == "shell":
        argv = ["shell", "-master", master.address, "-c", "ec.backend; ec.status"]
    else:
        (tmp_path / "f.bin").write_bytes(os.urandom(4096))
        argv = ["upload", "-master", master.address, str(tmp_path / "f.bin")]
    p = subprocess.run(
        [sys.executable, "-c", _TOOL_PROBE, *argv], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stdout[-1000:] + p.stderr[-2000:]
    if tool == "shell":
        assert f"ec.backend: {vs.url}: " in p.stdout and "device=cpu:cpu" in p.stdout
        assert f"backend={vs.store.encoder.selection['backend']}(" in p.stdout  # ec.status


# -- chip_smoke.py, rehearsed on the CPU -----------------------------------------


def test_chip_smoke_cpu_rehearsal_runs_every_phase_and_cannot_say_ok(tmp_path):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--platform", "cpu", "--size-mib", "8"],
        cwd=str(tmp_path), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(l) for l in p.stdout.strip().splitlines() if l.startswith("{")]
    assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-2000:]
    assert [l["phase"] for l in lines[:-1]] == [
        "preflight", "boot", "load", "encode", "read_intact", "read_degraded",
        "rebuild", "shutdown",
    ], p.stdout[-3000:]
    assert all(l["smoke"] is True for l in lines[:-1])
    assert lines[1]["ec_backend"]["backend"] == "jax"  # the XLA path, on the CPU
    assert lines[5]["degraded"] > 0
    assert lines[-1] == {"ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    assert '"ok": true' not in p.stdout
