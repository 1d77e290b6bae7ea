"""`mesh10p4`: a volume server that owns every chip of a four-chip host, and the
worst legal loss of two volumes rebuilt over its mesh by one flagless
`ec.rebuild`. On the CPU, four forced host devices, 24 MiB volumes of the
benchmark's own needle mix (a shard of three 1 MiB rows: the two volumes share
two (10, 4194304) batches, a volume's edge inside the first), against the plain
reference (`benchmark/harness/checks.py`: striping as arithmetic on the original
`.dat`, numpy GF(2^8) of `benchmark/reference/gf8_ref.py`).

The server of the first tests is a CHILD started as the configuration's file
says (`server_env`: `WEEDTPU_BACKEND=mesh` and nothing else; the four devices
are the child's `XLA_FLAGS`), so that `/status` has to come to the
configuration's `status` by the program's own defaults; the shell runs in this
process against the child's master. The benchmark's harness cannot do that off
the chip: `harness/server.py` sets `WEEDTPU_BACKEND=jax` for a rehearsal, which
is why the cell's rehearsal (last in this file) asserts no mesh fact."""

import hashlib
import importlib.util
import io
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

import test_ec_rebuild_cluster as cl
import test_ec_rebuild_many as many
from seaweedfs_tpu import rpc, stats
from seaweedfs_tpu.obs import trace
from seaweedfs_tpu.ops import rs_jax
from seaweedfs_tpu.pb import VOLUME_SERVICE
from seaweedfs_tpu.shell import CommandEnv, ShellError, run_script

ROOT = cl.ROOT
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(BENCH, "configs", "mesh10p4.json")) as _f:
    CONFIG = json.load(_f)
LOST = CONFIG["lost_shards"]
VIDS = (1, 2)
SIZE_MIB = 24


def _benchmarks(name: str, path: str):
    """A file of the benchmark under a name of its own (it imports `harness`,
    `drivers` and `reference` from the benchmark's directory, which is on the
    path for as long as that takes)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, path))
        module = sys.modules[name] = importlib.util.module_from_spec(spec)  # a dataclass looks its module up
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


volumes = _benchmarks("benchmark_harness_volumes", "harness/volumes.py")
checks = _benchmarks("benchmark_harness_checks", "harness/checks.py")
peers = _benchmarks("benchmark_harness_peers", "harness/peers.py")  # its scrape, free_port, http_json
_cases = _benchmarks("benchmark_tests_test_checks_fail_mesh", "tests/test_checks_fail_mesh.py")


def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.read()


def _sha(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


class MeshHost:
    """`python -m seaweedfs_tpu server` as a child over two sealed volumes, its
    environment the configuration's `server_env` on four forced host devices
    (`variant` other than the default goes in as the operator would set it)."""

    def __init__(self, tmp, variant: str):
        self.dir = str(tmp / "data")
        os.makedirs(self.dir)
        self.ds = {vid: volumes.build(self.dir, vid, 48_000 + vid, {"kind": "mixed4m", "size_mib": SIZE_MIB})
                   for vid in VIDS}
        self.orig = {}
        for vid in VIDS:
            self.orig[vid] = str(tmp / f"orig{vid}.dat")
            os.link(self.base(vid) + ".dat", self.orig[vid])
        env = {k: v for k, v in os.environ.items() if not k.startswith("WEEDTPU_")}
        env.update(CONFIG["server_env"], JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"),
                   WEEDTPU_TRACE="on", WEEDTPU_TRACE_SAMPLE="1.0", WEEDTPU_TRACE_RING="4096")
        if variant != CONFIG["status"]["mesh_rebuild"]:
            env["WEEDTPU_MESH_REBUILD"] = variant
        self.log_path = str(tmp / "server.log")
        self.env = None
        for attempt in range(3):  # a port drawn free may be taken by the time the child binds it
            try:
                self._start(env)
                break
            except AssertionError:
                self.close()
                if attempt == 2 or "Address already in use" not in self.log_text():
                    raise
            except BaseException:
                self.close()
                raise

    def _start(self, env: dict) -> None:
        ports = {k: peers.free_port() for k in ("master", "master_http", "volume")}
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu", "server", "-dir", self.dir,
             "-masterPort", str(ports["master"]), "-masterHttpPort", str(ports["master_http"]),
             "-port", str(ports["volume"])],
            cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT)
        self.master = f"127.0.0.1:{ports['master']}"
        self.master_http = f"127.0.0.1:{ports['master_http']}"
        self.url = f"127.0.0.1:{ports['volume']}"
        self.grpc = ""
        cl._wait_for(self._ready, timeout=120, msg="the mesh server is up and lists both volumes")
        self.env = CommandEnv(self.master)

    def base(self, vid: int) -> str:
        return os.path.join(self.dir, str(vid))

    def path(self, vid: int, shard: int) -> str:
        return checks.shard_path(self.base(vid), shard)

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def _ready(self) -> bool:
        assert self.proc.poll() is None, f"the server exited {self.proc.returncode}:\n{self.log_text()[-3000:]}"
        try:
            for vid in VIDS:
                if not peers.http_json(f"http://{self.master_http}/dir/lookup?volumeId={vid}").get("locations"):
                    return False
        except OSError:
            return False
        for line in self.log_text().splitlines():
            if line.startswith("server: ") and " grpc " in line:
                self.grpc = line.split("volume http ")[1].split(" grpc ")[1].split(",")[0]
        return bool(self.grpc)

    def status(self) -> dict:
        return peers.http_json(f"http://{self.url}/status")["ec_backend"]

    def metrics(self) -> dict:
        return peers.scrape(self.url)

    def listed(self, vid: int) -> dict:
        topo = peers.http_json(f"http://{self.master_http}/dir/status")["Topology"]
        return {int(s): urls for s, urls in topo.get("ec_volumes", {}).get(str(vid), {}).items() if urls}

    def shell(self, script: str):
        """-> (what the script wrote, the ShellError that ended it or None)."""
        out = io.StringIO()
        try:
            run_script(self.env, script, out)
        except ShellError as e:
            return out.getvalue(), e
        return out.getvalue(), None

    def lose(self) -> None:
        with rpc.RpcClient(self.grpc) as c:
            for vid in VIDS:
                c.call(VOLUME_SERVICE, "VolumeEcShardsDelete",
                       {"volume_id": vid, "collection": "", "shard_ids": list(LOST)}, timeout=60)
        cl._wait_for(lambda: not any(s in self.listed(vid) for vid in VIDS for s in LOST),
                     msg="the master dropped the lost shards")

    def rebuild_rpcs(self, since: float) -> list[dict]:
        """The rebuild RPCs' trees in the child's ring, begun after `since`."""
        got = peers.http_json(f"http://{self.url}/debug/traces?kind=rpc.server&limit=1000")
        return [t["root"] for t in got["traces"] if t["start"] >= since
                and t["root"]["attrs"].get("method") in many.REBUILD_RPCS]

    def close(self) -> None:
        if self.env is not None:
            self.env.close()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.log.close()


def _spans(root: dict, name: str) -> list[dict]:
    return [s for s in trace.iter_spans({"root": root}) if s["name"] == name]


def _rose(before: dict, after: dict, key: str) -> float:
    return after.get(key, 0.0) - before.get(key, 0.0)


@pytest.mark.parametrize("variant", ["ring", "alltoall"])
def test_a_four_device_mesh_server_rebuilds_four_lost_shards_of_two_volumes_in_one_batch(tmp_path, variant):
    """The configuration as it is run, at 24 MiB a volume: `/status` comes to
    the configuration's `status` from `WEEDTPU_BACKEND=mesh` alone; both volumes
    are encoded over the mesh and are the reference's; shards 0, 3, 11, 13 of
    both go; one flagless `ec.rebuild` is ONE `VolumeEcShardsRebuildBatch` whose
    every batch took the variant's program on four devices; the rebuilt shards
    are the deleted ones, byte for byte; and the mesh's spans, forms and
    counters say the same thing of the command."""
    host = MeshHost(tmp_path, variant)
    try:
        status = host.status()
        want = dict(CONFIG["status"], mesh_rebuild=variant)
        assert {k: status.get(k) for k in want} == want, status
        assert status["source"] == "env:WEEDTPU_BACKEND" and status["device"]["count"] == 4
        out, err = host.shell("lock; " + "; ".join(f"ec.encode -volumeId {v} -force" for v in VIDS) + "; unlock")
        assert err is None, out
        encoded = host.metrics()
        assert encoded['weedtpu_ec_mesh_batches_total{variant="cols",devices="4"}'] >= 2
        assert encoded["weedtpu_ec_mesh_devices"] == 4
        deleted = {(vid, s): _sha(host.path(vid, s)) for vid in VIDS for s in LOST}
        shard_bytes = os.path.getsize(host.path(1, 0))
        assert shard_bytes == os.path.getsize(host.path(2, 0)) == 3 << 20
        host.lose()
        assert not any(os.path.exists(host.path(vid, s)) for vid in VIDS for s in LOST)
        before, since = host.metrics(), time.time()

        out, err = host.shell("lock; ec.rebuild; unlock")

        assert err is None, out
        after = host.metrics()
        assert f"ec.rebuild batch on {host.url}: 2 volumes in 1 signature groups\n" in out
        calls = 'weedtpu_rpc_server_seconds_count{{method="{}"}}'
        assert {m: _rose(before, after, calls.format(m)) for m in many.REBUILD_RPCS + ("VolumeEcShardsMount",)} == {
            "VolumeEcShardsRebuildBatch": 1, "VolumeEcShardsRebuild": 0, "VolumeEcShardsMount": 0}
        for vid in VIDS:
            assert f"ec.rebuild volume {vid}: rebuilt {LOST} on {host.url}\n" in out
            for s in LOST:
                assert _sha(host.path(vid, s)) == deleted[vid, s], (vid, s)
            assert host.listed(vid) == {s: [host.url] for s in range(14)}
            got = checks.check_shards(host.base(vid), host.orig[vid], 48, 8)
            assert (got["files_missing"], got["crc_mismatches"], got["data_cells_differing"],
                    got["parity_cells_differing"], got["rows_checked"]) == (0, 0, 0, 0, 3), got
            for i in (0, len(host.ds[vid].keys) // 2, len(host.ds[vid].keys) - 1):
                assert _get(f"http://{host.url}/{host.ds[vid].fid(i)}") == host.ds[vid].payload(i)
        # -- what the mesh said of the command: the counters ...
        batches = _rose(before, after, f'weedtpu_ec_mesh_batches_total{{variant="{variant}",devices="4"}}')
        assert batches == -(-2 * shard_bytes // (4 << 20)) == 2
        assert not [k for k in after if k.startswith("weedtpu_ec_mesh_batches_total") and 'devices="4"' not in k]
        assert _rose(before, after, 'weedtpu_ec_rebuild_runs_total{backend="mesh"}') == 2
        put = _rose(before, after, 'weedtpu_ec_mesh_seconds_total{stage="put"}')
        restore = _rose(before, after, 'weedtpu_ec_mesh_seconds_total{stage="restore"}')
        assert put > 0 and restore > 0
        # ... and the spans: one dispatch a batch, each with its mesh.put, one sync a batch, each with its mesh.restore
        (root,) = host.rebuild_rpcs(since)
        (run,) = _spans(root, "rebuild.run")
        assert run["attrs"]["batch"] == 2 and run["attrs"]["signature_groups"] == 1 and run["attrs"]["batches"] == 2
        dispatches, syncs = _spans(root, "rebuild.dispatch"), _spans(root, "rebuild.sync")
        assert len(dispatches) == len(syncs) == batches
        mesh = {"mesh": "2x2", "variant": variant, "devices": 4}
        for d in dispatches:
            assert d["attrs"]["form"] == f"mesh-{variant}"
            (p,) = [c for c in d["spans"] if c["name"] == "mesh.put"]
            assert p["attrs"] == mesh
        for s in syncs:
            (r,) = [c for c in s["spans"] if c["name"] == "mesh.restore"]
            # every device's shard copied once into its columns of one result that holds the batch's rows
            a = r["attrs"]
            assert a == {**mesh, "pieces": 4, "copied": a["copied"], "kept": a["kept"]} and r["dur_ms"] <= s["dur_ms"]
            assert a["copied"] >= s["attrs"]["bytes"] and a["kept"] in (True, False)
        restored = sum(r["attrs"]["copied"] for r in _spans(root, "mesh.restore"))
        # the counter (which the checks' reads between the two scrapes moved too): a byte copied a byte of result
        assert 2 * len(LOST) * shard_bytes <= restored <= _rose(
            before, after, 'weedtpu_ec_mesh_restore_bytes_total{kind="result"}') == _rose(
            before, after, 'weedtpu_ec_mesh_restore_bytes_total{kind="copied"}')
        # nothing else ran on the mesh, and the counter's seconds enclose its spans' (it is read around them)
        spans_put = sum(p["dur_ms"] for p in _spans(root, "mesh.put")) / 1e3
        assert {p["attrs"]["variant"] for p in _spans(root, "mesh.put")} == {variant}
        assert 0 < spans_put <= put + 0.01, (spans_put, put)
        # no program was compiled by the command: the warm-up's shapes are the window's
        again = host.metrics()
        host.lose()
        out, err = host.shell("lock; ec.rebuild; unlock")
        assert err is None, out
        assert _rose(again, host.metrics(), "weedtpu_codec_programs_compiled_total") == 0
        assert "Traceback (most recent call last)" not in host.log_text()
    finally:
        host.close()


# -- the stand-in for the harness's `broken_apply`, which cannot reach the mesh programs ---------------


@pytest.fixture
def mesh_many(tmp_path, monkeypatch):
    """`test_ec_rebuild_many`'s server, in this process, its codec the mesh
    backend on the first four of the suite's eight host devices."""
    monkeypatch.setenv("WEEDTPU_TRACE", "on")
    monkeypatch.setenv("WEEDTPU_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("WEEDTPU_MESH_SHAPE", "2x2")
    made = []

    def make(variant):
        monkeypatch.setenv("WEEDTPU_MESH_REBUILD", variant)
        made.append(many.Many(tmp_path, "mesh"))
        return made[-1]

    yield make
    for c in made:
        c.close()


@pytest.mark.parametrize("variant", ["ring", "alltoall"])
def test_the_mesh_programs_answer_altered_is_refused_by_the_crc_gate(mesh_many, monkeypatch, variant):
    """`benchmark/harness/chip_server.py`'s `broken_apply` wraps
    `rs_jax.apply_matrix`; the mesh programs call `rs_jax.gf_apply` inside
    `shard_map` and never pass that name. The same fault where they do pass:
    with the first byte of every row `gf_apply` returns overwritten under the
    rebuild program (traced after the sound encode), the volume whose columns
    hold such a byte has no rebuilt shard with the CRC32 its `.eci` records, none
    of them is kept, and the command fails naming it; sound again (a new
    dispatcher: a new trace), the same loss comes back byte for byte."""
    c = mesh_many(variant)
    assert c.server.store.encoder.selection["mesh_devices"] == 4
    for vid in VIDS:
        for s in range(14):
            with open(c.path(vid, s), "rb") as f:
                assert f.read() == c.reference[vid][s], (vid, s)
    sound = rs_jax.gf_apply

    def broken(b_bits, shards):
        # overwritten, not flipped: the ring XORs an even number of these into one tile
        return sound(b_bits, shards).at[..., 0].set(0x5A)

    monkeypatch.setattr(rs_jax, "gf_apply", broken)
    c.lose({vid: LOST for vid in VIDS})
    batches0 = stats.EcMeshBatches.labels(variant, "4").value

    out, err = c.shell("lock; ec.rebuild; unlock")

    assert isinstance(err, ShellError) and "[1]" in str(err), (out, err)
    assert stats.EcMeshBatches.labels(variant, "4").value - batches0 == 1  # it ran, on the mesh
    # column 0 of the one packed batch is volume 1's (the other three devices' first columns are padding
    # at this size): its four rebuilt shards are refused and none is kept; volume 2 beside it is sound
    assert f"ec.rebuild volume 1: NOT rebuilt on {c.server.url}: " in out and "CRC mismatch" in out
    assert not any(os.path.exists(c.path(1, s)) for s in LOST) and not set(c.listed(1)) & set(LOST)
    assert f"ec.rebuild volume 2: rebuilt {LOST} on {c.server.url}\n" in out
    for s in LOST:
        with open(c.path(2, s), "rb") as f:
            assert f.read() == c.reference[2][s]
    monkeypatch.setattr(rs_jax, "gf_apply", sound)
    # the compiled, broken program is this server's dispatcher's: a new dispatcher traces anew
    c.server.store.encoder._mesh_obj = None
    out, err = c.shell("lock; ec.rebuild; unlock")
    assert err is None, out
    for vid in VIDS:
        for s in LOST:
            with open(c.path(vid, s), "rb") as f:
                assert f.read() == c.reference[vid][s], (vid, s)


# -- the benchmark's cell, rehearsed where the tier-1 command collects it -----------------

test_the_cell_is_in_the_manifest_with_its_metrics = _cases.test_the_cell_is_in_the_manifest_with_its_metrics
test_a_program_without_the_mesh_counters_gives_no_sample_and_no_metric = (
    _cases.test_a_program_without_the_mesh_counters_gives_no_sample_and_no_metric)


@pytest.mark.parametrize("fault,shows_in", _cases.CASES)
def test_the_benchmark_cell_rehearses_to_its_end_and_leaves_no_process(tmp_path, fault, shows_in):
    """`run.py --workload mesh10p4.rebuild-4lost-x4 --rehearse`: every phase on
    the CPU with 8 MiB volumes, never a result. The rehearsal runs the
    one-device backend by the harness's own line (`harness/server.py`), so the
    mesh facts are asserted only off rehearsal and not here: sound, all checks
    pass and the facts say one batch RPC a command; with the control's fault
    on disk they do not; no server outlives the run."""
    work = tmp_path / "tmp"
    work.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(work))
    result, out = _cases.rehearse(fault, 2**31 + 4800 + len(fault), env)
    _cases.see(result, out, fault, shows_in)
    assert '"correct": true' not in out
    left = subprocess.run(["pgrep", "-f", str(work)], capture_output=True, text=True).stdout.split()
    assert not left, f"processes left behind: {left}"
    shutil.rmtree(work, ignore_errors=True)
