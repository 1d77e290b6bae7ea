"""Process-level commands — master / volume / server / shell / version,
mirroring weed/command/{master,volume,server,shell}.go [VERIFY: mount
empty; SURVEY.md §2.1 "CLI entry"]. `server` runs master+volume in one
process like `weed server`."""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from seaweedfs_tpu.command import Command, register


def _version_conf(p: argparse.ArgumentParser) -> None:
    pass


def _version_run(args: argparse.Namespace) -> int:
    import seaweedfs_tpu

    print(f"seaweedfs_tpu {seaweedfs_tpu.__version__}")
    return 0


register(Command("version", "print version", _version_conf, _version_run))


def _wait_forever() -> None:
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, lambda *_: stop.set())
        except ValueError:  # not main thread (tests)
            break
    stop.wait()


def _load_guard():
    """Build a security.Guard from security.toml (None = security off).
    TLS is NOT loaded here — __main__ activates it process-wide from the
    same TOML before any command runs."""
    from seaweedfs_tpu.security import Guard
    from seaweedfs_tpu.utils.config import get_nested, load_configuration

    conf = load_configuration("security")
    key = str(get_nested(conf, "jwt.signing.key", "") or "")
    read_key = str(get_nested(conf, "jwt.signing.read.key", "") or "")
    wl = list(get_nested(conf, "guard.white_list", []) or [])
    exp = int(get_nested(conf, "jwt.signing.expires_after_seconds", 10) or 10)
    if not (key or read_key or wl):
        return None
    return Guard(
        signing_key=key.encode() or None,
        read_signing_key=read_key.encode() or None,
        white_list=wl,
        expires_seconds=exp,
    )


def _build_native() -> None:
    """Start-up of a process that owns a Store: build libweedtpu.so (it is
    not in git) once, before any thread needs it. A failure is an error
    with the compiler's output — never a quiet switch to the per-byte
    Python CRC32C over gigabyte volumes."""
    from seaweedfs_tpu.utils import native

    native.build()


def _mirror_spans_to_profiler(vs) -> None:
    """Start-up of a chip-owning server: put the program's spans on the
    device trace's clock. Every recorded span (obs/trace.py) also opens a
    `jax.profiler.TraceAnnotation`, so a profiler session on this process
    holds `rpc.server`, `encode.*`, `rebuild.*`, ... on its host threads'
    lines beside the device's operations; outside a session the annotation
    is the profiler's own no-op. Only where the store's codec runs on a
    device backend, which has imported jax already: a CPU server and the
    shell never import it for this."""
    from seaweedfs_tpu.ops.rs_codec import DEVICE_BACKENDS

    if vs.store.encoder.backend not in DEVICE_BACKENDS:
        return
    import jax

    from seaweedfs_tpu.obs import trace

    def open_annotation(name, attrs):
        annotation = jax.profiler.TraceAnnotation(name, **(attrs or {}))
        annotation.__enter__()
        return annotation

    trace.set_mirror(open_annotation)


def _maybe_metrics(port: int):
    if port:
        from seaweedfs_tpu.stats import start_metrics_server

        start_metrics_server(port)
        print(f"metrics on :{port}")


def _master_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-port", type=int, default=9333)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-volumeSizeLimitMB", type=int, default=30 * 1024)
    p.add_argument("-defaultReplication", default="000")
    p.add_argument("-peers", default="", help="comma-separated master quorum (raft HA)")
    p.add_argument("-garbageThreshold", type=float, default=0.3,
                   help="auto-vacuum volumes whose dead fraction exceeds this")
    p.add_argument("-vacuumInterval", type=float, default=900.0,
                   help="seconds between automatic vacuum sweeps")
    p.add_argument("-raftDir", default="", help="raft term/vote persistence directory")
    p.add_argument("-httpPort", type=int, default=0,
                   help="HTTP API port (/dir/assign, /dir/lookup, ...); 0 = auto")
    p.add_argument("-metricsPort", type=int, default=0)


def _master_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.cluster.master import MasterServer

    peers = [a.strip() for a in args.peers.split(",") if a.strip()]
    m = MasterServer(
        port=args.port,
        host=args.ip,
        volume_size_limit=args.volumeSizeLimitMB * 1024 * 1024,
        default_replication=args.defaultReplication,
        guard=_load_guard(),
        peers=peers or None,
        raft_dir=args.raftDir,
        garbage_threshold=args.garbageThreshold,
        vacuum_interval=args.vacuumInterval,
        http_port=args.httpPort,
    )
    m.start()
    _maybe_metrics(args.metricsPort)
    print(f"master listening on {m.address} (http :{m.http_port})")
    _wait_forever()
    m.stop()
    return 0


register(Command("master", "run a master server", _master_conf, _master_run))


def _volume_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-port", type=int, default=8080)
    p.add_argument("-grpcPort", type=int, default=0)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-dir", action="append", default=None, help="storage directory (repeatable)")
    p.add_argument("-mserver", default="127.0.0.1:9333")
    p.add_argument("-dataCenter", default="DefaultDataCenter")
    p.add_argument("-rack", default="DefaultRack")
    p.add_argument("-max", type=int, default=8, help="max volume count")
    p.add_argument("-metricsPort", type=int, default=0)
    p.add_argument(
        "-index",
        default="memory",
        choices=["memory", "sorted_file"],
        help="needle map kind: memory rebuilds the id map in RAM each "
        "mount; sorted_file binary-searches a persistent .sdx sidecar "
        "(reference -index=memory|leveldb analog)",
    )


def _volume_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.cluster.volume_server import VolumeServer

    _build_native()
    vs = VolumeServer(
        args.dir or ["./data"],
        args.mserver,
        port=args.port,
        grpc_port=args.grpcPort,
        host=args.ip,
        data_center=args.dataCenter,
        rack=args.rack,
        max_volume_count=args.max,
        guard=_load_guard(),
        needle_map_kind=args.index,
    )
    _mirror_spans_to_profiler(vs)
    vs.start()
    _maybe_metrics(args.metricsPort)
    print(f"volume server on http {vs.url} grpc {vs.grpc_address}")
    _wait_forever()
    vs.stop()
    return 0


register(Command("volume", "run a volume server", _volume_conf, _volume_run))


def _server_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-masterPort", type=int, default=9333)
    p.add_argument("-masterHttpPort", type=int, default=0,
                   help="master HTTP API port (/dir/assign, ...); 0 = auto")
    p.add_argument("-port", type=int, default=8080, help="volume server http port")
    p.add_argument("-dir", action="append", default=None)
    p.add_argument("-volumeSizeLimitMB", type=int, default=30 * 1024)
    p.add_argument("-filer", action="store_true", help="also run a filer")
    p.add_argument("-filerPort", type=int, default=8888)
    p.add_argument("-s3", action="store_true", help="also run the S3 gateway (implies -filer)")
    p.add_argument("-s3Port", type=int, default=8333)
    p.add_argument("-webdav", action="store_true", help="also run WebDAV (implies -filer)")
    p.add_argument("-webdavPort", type=int, default=7333)
    p.add_argument(
        "-allowedHosts",
        default="",
        help="comma-separated advertised host:port names accepted as the "
        "signed Host header by the S3 gateway besides the bind address",
    )


def _server_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer

    _build_native()
    m = MasterServer(
        port=args.masterPort,
        host=args.ip,
        volume_size_limit=args.volumeSizeLimitMB * 1024 * 1024,
        http_port=args.masterHttpPort,
    )
    m.start()
    vs = VolumeServer(
        args.dir or ["./data"], m.address, port=args.port, host=args.ip
    )
    _mirror_spans_to_profiler(vs)
    vs.start()
    parts = [
        f"master {m.address} (http :{m.http_port})",
        f"volume http {vs.url} grpc {vs.grpc_address}",
    ]
    extras = []
    if args.filer or args.s3 or args.webdav:
        from seaweedfs_tpu.filer import FilerServer

        f = FilerServer(m.address, port=args.filerPort, host=args.ip)
        f.start()
        extras.append(f)
        parts.append(f"filer http {f.url} grpc {f.grpc_address}")
        if args.s3:
            from seaweedfs_tpu.s3api import S3ApiServer

            s3 = S3ApiServer(
                f.url,
                f.grpc_address,
                port=args.s3Port,
                host=args.ip,
                extra_hosts={h.strip() for h in args.allowedHosts.split(",") if h.strip()},
            )
            s3.start()
            extras.append(s3)
            parts.append(f"s3 {s3.url}")
        if args.webdav:
            from seaweedfs_tpu.webdav import WebDavServer

            w = WebDavServer(f.url, f.grpc_address, port=args.webdavPort, host=args.ip)
            w.start()
            extras.append(w)
            parts.append(f"webdav {w.url}")
    print("server: " + ", ".join(parts))
    _wait_forever()
    for srv in reversed(extras):
        srv.stop()
    vs.stop()
    m.stop()
    return 0


register(Command("server", "run master + volume server in one process", _server_conf, _server_run))


def _filer_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-port", type=int, default=8888)
    p.add_argument("-grpcPort", type=int, default=0)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-store", default="memory", help="memory|sqlite|log|log3 (log3 = per-bucket store separation)")
    p.add_argument("-dir", default="", help="store/meta-log directory (sqlite/log stores)")
    p.add_argument("-collection", default="")
    p.add_argument("-defaultReplicaPlacement", default="")
    p.add_argument("-maxMB", type=int, default=4, help="chunk size in MiB")
    p.add_argument("-metricsPort", type=int, default=0)


def _filer_run(args: argparse.Namespace) -> int:
    import os

    from seaweedfs_tpu.filer import FilerServer, make_store

    # share the cluster's jwt keys so chunk deletes/reads work secured
    guard = _load_guard()
    if args.store == "sqlite":
        store_path = os.path.join(args.dir, "filer.db") if args.dir else ""
    else:  # log-structured store takes its directory
        store_path = args.dir
    f = FilerServer(
        args.master,
        store=make_store(args.store, store_path),
        port=args.port,
        grpc_port=args.grpcPort,
        host=args.ip,
        chunk_size=args.maxMB * 1024 * 1024,
        log_dir=args.dir,
        collection=args.collection,
        replication=args.defaultReplicaPlacement,
        signing_key=guard.signing_key if guard else None,
        read_signing_key=guard.read_signing_key if guard else None,
    )
    f.start()
    _maybe_metrics(args.metricsPort)
    print(f"filer on http {f.url} grpc {f.grpc_address}")
    _wait_forever()
    f.stop()
    return 0


register(Command("filer", "run a filer (namespace) server", _filer_conf, _filer_run))


def _s3_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-port", type=int, default=8333)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="127.0.0.1:8888", help="filer http host:port")
    p.add_argument("-filerGrpc", default="", help="filer grpc host:port (default: ask filer)")
    p.add_argument("-config", default="", help="identities JSON file (reference -s3.config shape)")
    p.add_argument("-metricsPort", type=int, default=0)
    p.add_argument(
        "-allowedHosts",
        default="",
        help="comma-separated advertised host:port names (DNS/LB fronts) "
        "accepted as the signed Host header besides the bind address",
    )


def _s3_run(args: argparse.Namespace) -> int:
    import json as _json

    from seaweedfs_tpu.s3api import Iam, S3ApiServer

    iam = Iam()
    if args.config:
        with open(args.config, encoding="utf-8") as f:
            iam = Iam.from_config(_json.load(f))
    grpc_addr = args.filerGrpc
    if not iam.identities and grpc_addr:
        # no static config: pick up identities the IAM API persisted in
        # the filer KV store (and _auth re-reads on unknown access keys)
        from seaweedfs_tpu.filer.client import FilerClient
        from seaweedfs_tpu.s3api.auth import load_identities

        try:
            with FilerClient(grpc_addr) as fc:
                stored = load_identities(fc)
            if stored is not None:
                iam = stored
        except Exception:  # noqa: BLE001 — filer may not be up yet
            pass
    if not grpc_addr:
        # filer grpc defaults to the http port + 10000 convention is the
        # reference's; here we require it explicitly unless colocated
        raise SystemExit("-filerGrpc is required")
    s3 = S3ApiServer(
        args.filer,
        grpc_addr,
        port=args.port,
        host=args.ip,
        iam=iam,
        extra_hosts={h.strip() for h in args.allowedHosts.split(",") if h.strip()},
    )
    s3.start()
    _maybe_metrics(args.metricsPort)
    print(f"s3 gateway on {s3.url} -> filer {args.filer}")
    _wait_forever()
    s3.stop()
    return 0


register(Command("s3", "run an S3-compatible gateway against a filer", _s3_conf, _s3_run))


def _webdav_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-port", type=int, default=7333)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-filerGrpc", default="")
    p.add_argument("-root", default="/", help="filer directory to expose")


def _webdav_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.webdav import WebDavServer

    if not args.filerGrpc:
        raise SystemExit("-filerGrpc is required")
    w = WebDavServer(
        args.filer, args.filerGrpc, port=args.port, host=args.ip, root=args.root
    )
    w.start()
    print(f"webdav on {w.url} -> filer {args.filer}")
    _wait_forever()
    w.stop()
    return 0


register(Command("webdav", "run a WebDAV gateway against a filer", _webdav_conf, _webdav_run))


def _iam_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-port", type=int, default=8111)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filerGrpc", default="", help="filer grpc host:port")
    p.add_argument(
        "-bootstrapToken",
        default="",
        help="pre-shared token allowing the first admin to be minted on a "
        "fresh cluster; without it the API stays closed until identities "
        "are seeded via config or the S3 gateway",
    )
    p.add_argument(
        "-allowedHosts",
        default="",
        help="comma-separated advertised host:port names accepted as the "
        "signed Host header besides the bind address",
    )


def _iam_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.iamapi import IamApiServer

    if not args.filerGrpc:
        raise SystemExit("-filerGrpc is required")
    srv = IamApiServer(
        args.filerGrpc,
        port=args.port,
        host=args.ip,
        bootstrap_token=args.bootstrapToken or None,
        extra_hosts={h.strip() for h in args.allowedHosts.split(",") if h.strip()},
    )
    srv.start()
    print(f"iam api on {srv.url}")
    _wait_forever()
    srv.stop()
    return 0


register(Command("iam", "run an AWS-IAM-compatible identity API", _iam_conf, _iam_run))


def _mount_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-filerGrpc", default="", help="filer grpc host:port")
    p.add_argument("-dir", default="", help="mountpoint directory")


def _mount_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.mount.fuse_adapter import fuse_available, mount_and_serve

    if not args.filerGrpc or not args.dir:
        raise SystemExit("-filerGrpc and -dir are required")
    if not fuse_available():
        print(
            "kernel FUSE unavailable (no fusepy//dev/fuse); use the WFS API "
            "(seaweedfs_tpu.mount.WFS) for in-process access",
            file=sys.stderr,
        )
        return 2
    mount_and_serve(args.filerGrpc, args.dir)
    return 0


register(Command("mount", "mount the filer as a FUSE filesystem", _mount_conf, _mount_run))


def _mq_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-port", type=int, default=17777)
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-filerGrpc", default="")


def _mq_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.mq import Broker

    if not args.filerGrpc:
        raise SystemExit("-filerGrpc is required")
    b = Broker(args.filer, args.filerGrpc, port=args.port, host=args.ip)
    b.start()
    print(f"mq broker on {b.address} -> filer {args.filer}")
    _wait_forever()
    b.stop()
    return 0


register(Command("mq.broker", "run a message-queue broker on the filer", _mq_conf, _mq_run))


def _shell_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-c", dest="script", default="", help="run `;`-separated commands and exit")


def _shell_run(args: argparse.Namespace) -> int:
    import time

    from seaweedfs_tpu import command
    from seaweedfs_tpu.obs import trace
    from seaweedfs_tpu.shell import CommandEnv, repl, run_script

    imported = time.monotonic()  # grpc and the shell's own modules are loaded (the TLS configuration began it)
    with CommandEnv(args.master) as env:
        if args.script:
            started = command.STARTED
            if started:  # a `-c` child: its script's trace begins at its birth
                started = (trace.process_birth(started[0]), started[0], imported, time.monotonic())
            run_script(env, args.script, sys.stdout, started=started)
        else:
            repl(env, sys.stdin, sys.stdout)
    return 0


register(Command("shell", "operator shell (REPL or -c script)", _shell_conf, _shell_run))


def _scaffold_conf(p: argparse.ArgumentParser) -> None:
    p.add_argument("-config", default="security", help="security|master|shell|filer")


def _scaffold_run(args: argparse.Namespace) -> int:
    from seaweedfs_tpu.utils.config import SCAFFOLDS, scaffold

    text = scaffold(args.config)
    if text is None:
        print(f"unknown config {args.config!r}; one of {sorted(SCAFFOLDS)}", file=sys.stderr)
        return 1
    print(text, end="")
    return 0


register(Command("scaffold", "print a commented TOML config template", _scaffold_conf, _scaffold_run))
