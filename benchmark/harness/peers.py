"""The cluster's other volume servers: `python -m seaweedfs_tpu volume` children
that join the chip-owning server's master, each a rack of its own. They run
with `JAX_PLATFORMS=cpu` and a host codec and never touch the chip. A peer keeps
its ports and its directory over a stop and a restart, as a server that comes
back does.

`run.py` kills only `run.srv`, so every peer dies with the parent whatever way
the parent goes: `Peers.close` (registered with `atexit`) kills what is left,
and the kernel sends SIGKILL to a peer whose parent has died (`PR_SET_PDEATHSIG`).
"""

from __future__ import annotations

import atexit
import ctypes
import os
import signal
import subprocess
import sys
import time
import urllib.request

from harness.server import ROOT, BenchError, free_port, http_json

PR_SET_PDEATHSIG = 1


def _die_with_parent() -> None:
    """In the child, before exec: SIGKILL when the parent's thread ends."""
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_PDEATHSIG, signal.SIGKILL)


def scrape(url: str) -> dict[str, float]:
    """A server's `/metrics` as {`name{labels}`: value}, the line as exposed."""
    with urllib.request.urlopen(f"http://{url}/metrics", timeout=30) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        key, _, value = line.rpartition(" ")
        if key and not line.startswith("#"):
            out[key] = float(value)
    return out


class Peer:
    def __init__(self, rack: str, data_dir: str, log_path: str, master: str):
        self.rack = rack
        self.data_dir = data_dir
        self.log_path = log_path
        self.master = master
        self.url = f"127.0.0.1:{free_port()}"
        self.grpc = f"127.0.0.1:{free_port()}"
        self.proc: subprocess.Popen | None = None
        os.makedirs(data_dir, exist_ok=True)

    def start(self) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
        for name in ("WEEDTPU_BACKEND", "WEEDBENCH_BREAK"):
            env.pop(name, None)  # the host's own codec, sound
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [
                    sys.executable, "-m", "seaweedfs_tpu", "volume",
                    "-port", self.url.split(":")[1], "-grpcPort", self.grpc.split(":")[1],
                    "-dir", self.data_dir, "-mserver", self.master, "-rack", self.rack,
                ],
                cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
                preexec_fn=_die_with_parent,
            )

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def wait_ready(self, timeout: float = 300.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.alive():
                raise BenchError(f"peer {self.rack} exited {self.proc.returncode} during start-up:\n"
                                 + self.log_text()[-2000:])
            try:
                http_json(f"http://{self.url}/status", timeout=5)
                return
            except OSError:
                time.sleep(0.05)
        raise BenchError(f"peer {self.rack} not ready in {timeout:.0f}s")

    def stop(self) -> None:
        """SIGTERM, as an operator stops a server: it leaves the master's
        topology and must exit 0."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.kill()
            raise BenchError(f"peer {self.rack} did not leave within 60 s of SIGTERM") from None
        if rc != 0:
            raise BenchError(f"peer {self.rack} exited {rc} on SIGTERM:\n" + self.log_text()[-2000:])

    def kill(self) -> None:
        if self.alive():
            self.proc.kill()
            self.proc.wait()

    def delete_shards(self, volume_id: int, shard_ids: list[int]) -> None:
        from seaweedfs_tpu import rpc
        from seaweedfs_tpu.pb import VOLUME_SERVICE

        with rpc.RpcClient(self.grpc) as c:
            c.call(VOLUME_SERVICE, "VolumeEcShardsDelete",
                   {"volume_id": volume_id, "collection": "", "shard_ids": list(shard_ids)},
                   timeout=60)

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")


class Peers:
    """One peer per rack, started together."""

    def __init__(self, racks: list[str], work: str, out_dir: str, master: str):
        self.peers = [
            Peer(rack, os.path.join(work, f"peer_{rack}"), os.path.join(out_dir, f"peer_{rack}.log"), master)
            for rack in racks
        ]
        atexit.register(self.close)

    def __iter__(self):
        return iter(self.peers)

    def by_rack(self, rack: str) -> Peer:
        return next(p for p in self.peers if p.rack == rack)

    def start(self) -> None:
        for p in self.peers:
            p.start()
        for p in self.peers:
            p.wait_ready()

    def stop_all(self) -> None:
        """The end of a run: every live peer leaves cleanly, and none of the
        logs may hold a traceback."""
        for p in self.peers:
            if p.alive():
                p.stop()
        for p in self.peers:
            if "Traceback (most recent call last)" in p.log_text():
                raise BenchError(f"peer {p.rack}'s log holds a traceback:\n" + p.log_text()[-3000:])

    def close(self) -> None:
        for p in self.peers:
            p.kill()
