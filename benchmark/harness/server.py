"""The one chip-owning server child and the tool children that drive it.
Copied from `chip_smoke.py` (PR 22), which ran on the chip; kept here so that a
later change to the smoke cannot move the yardstick. The parent never imports
jax: a parent that touched jax would hold the chip its child needs."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HARNESS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS))
DEVICE_BACKENDS = ("jax", "pallas", "mesh")


class BenchError(Exception):
    pass


def say(**rec) -> None:
    """One line of the run's log on stdout; the result line comes last."""
    print(json.dumps({"bench": True, **rec}), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


class Server:
    """`python -m seaweedfs_tpu server` (master + volume server) through
    `chip_server.py`, over a data directory whose volumes are already there."""

    def __init__(self, platform: str, data_dir: str, out_dir: str, extra_env: dict | None = None):
        self.out_dir = out_dir
        self.control = os.path.join(out_dir, "control")
        os.makedirs(self.control, exist_ok=True)
        for name in os.listdir(self.control):
            os.remove(os.path.join(self.control, name))
        self.log_path = os.path.join(out_dir, "server.log")
        self.ports = {k: free_port() for k in ("master", "master_http", "volume")}
        env = dict(os.environ, **(extra_env or {}))
        # JAX_COMPILATION_CACHE_DIR is inherited untouched where it is set;
        # utils.devices puts the cache at <checkout>/.jax_cache where it is not
        env["JAX_PLATFORMS"] = platform  # "tpu": jax itself cannot slide to the CPU
        env["JAX_LOG_COMPILES"] = "1"
        env["PYTHONUNBUFFERED"] = "1"
        if platform != "tpu":
            env["WEEDTPU_BACKEND"] = "jax"  # rehearsal: the same XLA path, on the CPU
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [
                sys.executable, os.path.join(HARNESS, "chip_server.py"), self.control,
                "-dir", data_dir,
                "-masterPort", str(self.ports["master"]),
                "-masterHttpPort", str(self.ports["master_http"]),
                "-port", str(self.ports["volume"]),
            ],
            cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
        )
        self.master = f"127.0.0.1:{self.ports['master']}"
        self.master_http = f"127.0.0.1:{self.ports['master_http']}"
        self.vs_url = f"127.0.0.1:{self.ports['volume']}"
        self.vs_grpc = ""

    def log_text(self) -> str:
        self.log.flush()
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def wait_ready(self, volume_id: int, timeout: float = 600.0) -> None:
        """Up when the volume server answers `/status` and the master's
        topology, fed by its heartbeat, knows where `volume_id` lives."""
        deadline = time.monotonic() + timeout
        last = ""
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchError(f"server exited {self.proc.returncode} during start-up")
            try:
                http_json(f"http://{self.vs_url}/status", timeout=5)
                if not self.has_volume(volume_id):
                    last = f"master does not list volume {volume_id} yet"
                elif not self._grpc_from_log():
                    # the servers answer before the main thread has printed its line
                    last = "server log does not name the volume server's grpc address yet"
                else:
                    return
            except Exception as e:  # noqa: BLE001 — not up yet
                last = f"{type(e).__name__}: {e}"
            time.sleep(0.1)
        raise BenchError(f"server not ready in {timeout:.0f}s (last: {last})")

    def _grpc_from_log(self) -> str:
        for line in self.log_text().splitlines():
            if line.startswith("server: ") and " grpc " in line:
                self.vs_grpc = line.split("volume http ")[1].split(" grpc ")[1].split(",")[0]
        return self.vs_grpc

    def has_volume(self, volume_id: int) -> bool:
        """True when the master lists `volume_id` as a NORMAL volume."""
        try:
            r = http_json(f"http://{self.master_http}/dir/lookup?volumeId={volume_id}", timeout=5)
        except Exception:  # noqa: BLE001 — 404 until the heartbeat lands
            return False
        return bool(r.get("locations")) and not r.get("error")

    def wait_volume(self, volume_id: int, timeout: float = 60.0) -> float:
        t0 = time.monotonic()
        while not self.has_volume(volume_id):
            if time.monotonic() - t0 > timeout:
                raise BenchError(f"master never listed volume {volume_id} again")
            time.sleep(0.05)
        return time.monotonic() - t0

    def backend(self) -> dict:
        return http_json(f"http://{self.vs_url}/status")["ec_backend"]

    def shell(self, script: str, timeout: float = 1500.0) -> str:
        """A tool child: always JAX_PLATFORMS=cpu — only the server owns the chip."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master", self.master, "-c", script],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
        )
        if p.returncode != 0:
            raise BenchError(f"shell -c {script!r} exited {p.returncode}:\n{p.stdout}{p.stderr}")
        return p.stdout

    def delete_shards(self, volume_id: int, shard_ids: list[int]) -> None:
        from seaweedfs_tpu import rpc
        from seaweedfs_tpu.pb import VOLUME_SERVICE

        with rpc.RpcClient(self.vs_grpc) as c:
            c.call(VOLUME_SERVICE, "VolumeEcShardsDelete",
                   {"volume_id": volume_id, "collection": "", "shard_ids": list(shard_ids)},
                   timeout=60)

    def mount_volume(self, volume_id: int) -> None:
        from seaweedfs_tpu import rpc
        from seaweedfs_tpu.pb import VOLUME_SERVICE

        with rpc.RpcClient(self.vs_grpc) as c:
            c.call(VOLUME_SERVICE, "VolumeMount", {"volume_id": volume_id}, timeout=60)

    # -- the control thread of chip_server.py ---------------------------------

    def ask(self, request: str, reply: str, text: str = "", timeout: float = 120.0) -> str:
        tmp = os.path.join(self.control, request + ".tmp")
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, os.path.join(self.control, request))
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for name in (reply, "error"):
                path = os.path.join(self.control, name)
                if os.path.exists(path):
                    with open(path) as f:
                        got = f.read()
                    os.remove(path)
                    if name == "error":
                        raise BenchError(f"chip_server: {request}: {got}")
                    return got
            if self.proc.poll() is not None:
                raise BenchError(f"server exited {self.proc.returncode} while asked {request}")
            time.sleep(0.005)
        raise BenchError(f"chip_server did not answer {request} in {timeout:.0f}s")

    def start_trace(self, trace_dir: str) -> None:
        os.makedirs(trace_dir, exist_ok=True)
        self.ask("trace.start", "trace.started", trace_dir)

    def stop_trace(self) -> None:
        self.ask("trace.stop", "trace.stopped")

    def device_stats(self) -> dict:
        return json.loads(self.ask("stats", "stats.json"))

    def stop(self) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise BenchError("server did not leave within 30 s of SIGTERM") from None
        return rc

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.log.close()
