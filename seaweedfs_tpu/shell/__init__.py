"""Operator shell — mirror of weed/shell (`weed shell` REPL)
[VERIFY: mount empty; SURVEY.md §2.1 "Shell (ops)" row, §3.1/§3.3 call
stacks]. EC lifecycle orchestration lives HERE, not in the master: the
shell drives encode/rebuild/balance over gRPC while holding a
cluster-wide exclusive lock leased from the master
(wdclient/exclusive_locks analog).

Each command is a `ShellCommand(name, help, do)` where
`do(args: list[str], env: CommandEnv, writer)` mirrors the reference's
`Do(args, commandEnv, writer)` signature.
"""

from __future__ import annotations

import json
import shlex
import sys
import threading
import time
from dataclasses import dataclass
from typing import Callable, Iterable, Optional, TextIO

import grpc

from seaweedfs_tpu import rpc
from seaweedfs_tpu.cluster.client import MasterClient
from seaweedfs_tpu.obs import trace as _trace
from seaweedfs_tpu.pb import VOLUME_SERVICE

LOCK_NAME = "admin"
_RENEW_INTERVAL = 10.0
#: how long a `-c` script waits for the master to take its trace when it
#: ends; past it the trace is lost and nothing else changes
_HAND_OVER_TIMEOUT = 0.2


class ShellError(Exception):
    pass


@dataclass
class ShellCommand:
    name: str
    help: str
    do: Callable[[list[str], "CommandEnv", TextIO], None]


_REGISTRY: dict[str, ShellCommand] = {}


# Which family module registers which command. A table and not a prefix
# rule: the names do not follow the files (`collection.list` and
# `volumeServer.leave` live in command_volume.py, `lock` / `unlock` in
# command_cluster.py). A command line imports the module of the name it
# gives: a child that runs `lock; ec.rebuild; unlock` never pays for the
# filer, the S3 gateway or the broker. A name that is not here still works,
# through `commands()`; tests/test_shell.py holds the table equal to what
# the modules register.
_FAMILIES: dict[str, tuple[str, ...]] = {
    "command_cluster": ("cluster.check", "cluster.ps", "cluster.raft.ps", "lock", "unlock"),
    "command_ec": (
        "ec.backend", "ec.balance", "ec.convert", "ec.decode", "ec.encode",
        "ec.rebuild", "ec.status", "ec.trace", "ec.verify",
    ),
    "command_fs": (
        "fs.cat", "fs.cd", "fs.configure", "fs.du", "fs.ls", "fs.meta.cat",
        "fs.meta.load", "fs.meta.save", "fs.mkdir", "fs.mv", "fs.pwd", "fs.rm", "fs.tree",
    ),
    "command_mq": ("mq.broker.list", "mq.topic.configure", "mq.topic.list"),
    "command_s3": ("s3.bucket.create", "s3.bucket.delete", "s3.bucket.list", "s3.clean.uploads"),
    "command_volume": (
        "collection.delete", "collection.list", "volume.balance", "volume.check.disk",
        "volume.configure.replication", "volume.delete", "volume.deleteEmpty",
        "volume.fix.replication", "volume.fsck", "volume.grow", "volume.list",
        "volume.mark", "volume.mount", "volume.move", "volume.tier.fetch",
        "volume.tier.move", "volume.unmount", "volume.vacuum",
        "volumeServer.evacuate", "volumeServer.leave",
    ),
}
COMMAND_MODULE: dict[str, str] = {
    name: module for module, names in _FAMILIES.items() for name in names
}


def register(cmd: ShellCommand) -> ShellCommand:
    _REGISTRY[cmd.name] = cmd
    return cmd


def _load_family(module: str) -> None:
    """Import one family for its registrations. `__import__`, not
    `importlib.import_module`: `python -X importtime` logs only the former,
    and that log is how a tool child's start is read."""
    __import__(f"{__name__}.{module}")


def commands() -> dict[str, ShellCommand]:
    """Every command of every family (`help`, tests): imports all six
    modules for their registrations."""
    for module in _FAMILIES:
        _load_family(module)
    return dict(_REGISTRY)


def find_command(name: str) -> Optional[ShellCommand]:
    """The one command `name`, importing only the family COMMAND_MODULE
    gives for it; a name the table lacks is looked for in all six."""
    module = COMMAND_MODULE.get(name)
    if module is None:
        return commands().get(name)
    _load_family(module)
    return _REGISTRY.get(name)


class CommandEnv:
    """Shared command environment (commandEnv analog): master client, the
    exclusive-lock lease, and per-node gRPC helpers."""

    def __init__(self, master_address: str, client_name: str = "shell"):
        self.master_address = master_address
        self.client = MasterClient(master_address)
        self.client_name = client_name
        self.cwd = "/"  # fs.cd/fs.pwd REPL state; fs.* paths resolve against it
        self._lock_token = 0
        #: RPCs made through `master_call` and `vs_call` since the env was
        #: made; `run_command` writes a command's share on its span, and
        #: each is an `rpc.client` span under the span that queued it
        self.rpcs = 0
        self._rpcs_lock = threading.Lock()
        #: the span a call from a thread of the env's own (the lock renewer)
        #: goes under: the running command's, else the script's
        self.trace_parent = None
        self._thread = threading.get_ident()  # the one that runs the commands
        self._renew_stop: Optional[threading.Event] = None
        self._renew_thread: Optional[threading.Thread] = None

    def close(self) -> None:
        if self.is_locked:
            try:
                self.unlock()
            except Exception:  # noqa: BLE001 — master may be gone
                pass
        fc = getattr(self, "_filer_client", None)
        if fc is not None:
            fc.close()
        self.client.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- master helpers ------------------------------------------------------

    def master_call(self, method: str, req: dict, timeout: float = 30) -> dict:
        """Master RPC via MasterClient's single failover/redirect path
        (thread-safe: the lock renewer calls this concurrently)."""
        return self._rpc(
            method, self.master_address,
            lambda: self.client.master_call(method, req, timeout=timeout),
        )

    def _rpc(self, method: str, target: str, call, **attrs):
        """One RPC of a command, counted and timed: an `rpc.client` span
        under the ambient span (a pool thread's: the span that queued it,
        through `trace.attach`), `thread=` where another thread than the
        command's makes it."""
        with self._rpcs_lock:  # a command's copies call from a pool
            self.rpcs += 1
        attrs = {"method": method, "target": target, **attrs}
        if threading.get_ident() != self._thread:
            attrs["thread"] = threading.current_thread().name
        with _trace.span("rpc.client", **attrs) as sp:
            try:
                return call()
            except grpc.RpcError as e:
                if sp is not None:
                    sp.error = e.code().name
                raise

    def resolve(self, path: str) -> str:
        """Resolve an fs.* path argument against the REPL's working
        directory (fs.cd analog of the reference's shell navigation)."""
        import posixpath

        if not path.startswith("/"):
            path = posixpath.join(self.cwd, path)
        return posixpath.normpath(path)

    def filer_client(self):
        """FilerClient for a filer discovered through the master's
        cluster-node list (fs.* commands); cached per env."""
        fc = getattr(self, "_filer_client", None)
        if fc is not None:
            return fc
        filers = self.master_call("ListClusterNodes", {}).get("filers", [])
        if not filers:
            raise ShellError("no filer registered with the master")
        from seaweedfs_tpu.filer.client import FilerClient

        self._filer_client = FilerClient(filers[0]["grpc_address"])
        self._filer_http = filers[0]["http_address"]
        return self._filer_client

    def volume_list(self) -> dict:
        return self.master_call("VolumeList", {})

    def topology_nodes(self, topo: Optional[dict] = None) -> list[dict]:
        """Flatten VolumeList's dc -> rack -> node tree, annotating each
        node dict with its dc/rack. `topo`: an answer the caller has
        already (no second `VolumeList`)."""
        out = []
        if topo is None:
            topo = self.volume_list()
        for dc, racks in topo.get("data_centers", {}).items():
            for rack, nodes in racks.items():
                for nd in nodes:
                    nd = dict(nd)
                    nd["data_center"] = dc
                    nd["rack"] = rack
                    out.append(nd)
        return out

    def vs_call(self, grpc_address: str, method: str, req: dict, timeout: float = 300) -> dict:
        def call():
            with rpc.RpcClient(grpc_address) as c:
                return c.call(VOLUME_SERVICE, method, req, timeout=timeout)

        # volume=: which volume of a command's many a call belongs to
        named = {"volume": req["volume_id"]} if "volume_id" in req else {}
        return self._rpc(method, grpc_address, call, **named)

    # -- exclusive lock (SURVEY.md §3.1 "acquire cluster exclusive lock") ----

    @property
    def is_locked(self) -> bool:
        return self._lock_token != 0

    def confirm_locked(self) -> None:
        if not self.is_locked:
            raise ShellError("lock the cluster first: run `lock`")

    def lock(self) -> None:
        resp = self.master_call(
            "LeaseAdminToken",
            {
                "lock_name": LOCK_NAME,
                "previous_token": self._lock_token,
                "client_name": self.client_name,
            },
        )
        self._lock_token = int(resp["token"])
        # a second `lock` while already locked is a renewal, not a second
        # renew thread
        if self._renew_thread is None or not self._renew_thread.is_alive():
            self._renew_stop = threading.Event()
            self._renew_thread = threading.Thread(target=self._renew_loop, daemon=True)
            self._renew_thread.start()

    def unlock(self) -> None:
        if self._renew_stop is not None:
            self._renew_stop.set()
        token, self._lock_token = self._lock_token, 0
        if token:
            self.master_call(
                "ReleaseAdminToken", {"lock_name": LOCK_NAME, "previous_token": token}
            )

    def _renew_once(self) -> bool:
        """One lease renewal. Returns False — and drops the token, so the
        next confirm_locked() aborts — when the master says someone else
        holds the lock (our lease expired and was stolen)."""
        try:
            resp = self.master_call(
                "LeaseAdminToken",
                {
                    "lock_name": LOCK_NAME,
                    "previous_token": self._lock_token,
                    "client_name": self.client_name,
                },
            )
            # a freshly promoted leader may reissue the token (lock table
            # replication lags by one heartbeat): adopt it, or the next
            # renewal's stale previous_token aborts the running command
            self._lock_token = int(resp.get("token", self._lock_token))
            return True
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.FAILED_PRECONDITION:
                self._lock_token = 0  # lock lost — stop pretending we hold it
                return False
            return True  # transient failure: retry next tick (TTL is 30s)
        except Exception:  # noqa: BLE001 — transient; retry next tick
            return True

    def _renew_loop(self) -> None:
        stop = self._renew_stop
        while not stop.wait(_RENEW_INTERVAL):
            # under whatever runs now: the renewal carries the script's id
            with _trace.attach(self.trace_parent):
                if not self._lock_token or not self._renew_once():
                    return


# -- argument helpers (flag.FlagSet analog for `-name=value` style) ----------


def grpc_addr(node: dict) -> str:
    """gRPC address of a topology node dict (shared by all commands)."""
    host = node["url"].rsplit(":", 1)[0]
    return f"{host}:{node['grpc_port']}"


def iter_entries(fc, path: str, page: int = 1024):
    """Fully paged filer directory listing (exclusive start_from resume)
    — the one pagination loop every fs/s3 command shares."""
    start = ""
    while True:
        batch = fc.list(path, start_from=start, limit=page)
        if not batch:
            return
        yield from batch
        start = batch[-1].name


def parse_flags(args: Iterable[str], **defaults):
    """Parse `-name value` / `-name=value` flags with typed defaults.
    Returns an attribute namespace; unknown flags raise ShellError."""

    class NS:
        pass

    ns = NS()
    for k, v in defaults.items():
        setattr(ns, k, v)
    it = iter(list(args))
    for tok in it:
        if not tok.startswith("-"):
            raise ShellError(f"unexpected argument {tok!r}")
        body = tok.lstrip("-")
        if "=" in body:
            name, val = body.split("=", 1)
        else:
            name = body
            val = None
        key = name.replace(".", "_").replace("-", "_")
        if key not in defaults:
            raise ShellError(f"unknown flag -{name}")
        default = defaults[key]
        if isinstance(default, bool):
            setattr(ns, key, True if val is None else val.lower() in ("1", "true", "yes"))
            continue
        if val is None:
            try:
                val = next(it)
            except StopIteration:
                raise ShellError(f"flag -{name} needs a value") from None
        if isinstance(default, int):
            setattr(ns, key, int(val))
        elif isinstance(default, float):
            setattr(ns, key, float(val))
        else:
            setattr(ns, key, val)
    return ns


# -- driver ------------------------------------------------------------------


def run_command(env: CommandEnv, line: str, writer: TextIO) -> None:
    """Parse and run one command line; raises ShellError on failure."""
    parts = shlex.split(line.strip())
    if not parts or parts[0].startswith("#"):
        return
    name, args = parts[0], parts[1:]
    if name in ("help", "?"):
        cmds = commands()
        if args and args[0] in cmds:
            writer.write(f"{args[0]}\n\t{cmds[args[0]].help}\n")
        else:
            for c in sorted(cmds):
                writer.write(f"  {c:<28} {cmds[c].help.splitlines()[0]}\n")
        return
    # every RPC a command fans out carries its trace's id, so one
    # ec.rebuild/ec.convert run can be reconstructed across every server it
    # touched (ec.trace, glog grep): the script's where a `-c` script runs
    # the command, else (the REPL, an in-process caller) a root of its own
    with _trace.ensure("shell.command", klass="shell"):
        _trace.annotate(command=name)
        cmd = find_command(name)  # inside the span: a family's import is the command's time
        if cmd is None:
            raise ShellError(f"unknown command {name!r} (try `help`)")
        # modules: how much this process had loaded when the command began
        # its work (for a `-c` child's first command, what its start cost)
        _trace.annotate(modules=len(sys.modules))
        before = getattr(env, "rpcs", 0)
        outer = getattr(env, "trace_parent", None)
        if env is not None:  # (`help` of a test runs without one)
            env.trace_parent, env._thread = _trace.current(), threading.get_ident()
        try:
            cmd.do(args, env, writer)
        finally:
            if env is not None:
                env.trace_parent = outer
            # how many RPCs the command made (the lock renewer's, if one
            # fell inside it, included): a per-volume loop shows here
            _trace.annotate(rpcs=getattr(env, "rpcs", 0) - before)


class _Outbox:
    """Where a script's root lands when it ends, in the place of this
    process's ring, which dies with a `-c` child: the master keeps it."""

    done = None

    def offer(self, done) -> bool:
        self.done = done
        return True


def run_script(env: CommandEnv, script: str, writer: TextIO, started: tuple = ()) -> None:
    """Run `;`-separated commands (the `weed shell -c` path) as ONE trace:
    a `shell.script` root every command nests under, handed to the master
    when the script ends, whatever its end was. `started`: the
    `time.monotonic()` readings (birth of the process, first line of
    `__main__`, the shell's modules imported, env connected) of a process
    that IS the script; the root then begins at the birth and `shell.start`
    is its first child."""
    outbox = _Outbox()
    try:
        with _trace.start(
            "shell.script", klass="shell", ring=outbox,
            t0=started[0] if started else None, script=script[:200],
        ) as root:
            if root is not None and started:
                born, main, imported, connected = started
                _trace.record(
                    "shell.start", born, time.monotonic(),
                    interp_ms=round((main - born) * 1e3, 3),
                    import_ms=round((imported - main) * 1e3, 3),
                    connect_ms=round((connected - imported) * 1e3, 3),
                    modules=len(sys.modules),
                )
            env.trace_parent = root
            for line in script.split(";"):
                if line.strip():
                    run_command(env, line, writer)
    finally:
        env.trace_parent = None
        if outbox.done is not None:
            _hand_over(env, outbox.done)


def _hand_over(env: CommandEnv, done) -> None:
    """A finished script's tree to the master, in ONE call on the channel
    that is open (`ReportTrace`; gRPC, not HTTP: a tool child never imports
    `http.client`), so that it outlives this process. A master that does not
    take it in time, or at all, costs the trace and nothing else: then this
    process's ring has it, for as long as the process lives."""
    trace = done.to_dict()
    trace["birth_unix_ns"] = trace["unix_ns"]
    _trace.cap_spans(trace["root"])
    try:
        env.client.call_current(
            "ReportTrace", {"trace": json.dumps(trace, separators=(",", ":"))},
            timeout=_HAND_OVER_TIMEOUT,
        )
    except Exception:  # noqa: BLE001 — the command's output and exit code are not this call's
        _trace.RING.offer(done)


def repl(env: CommandEnv, stdin, writer: TextIO) -> None:
    writer.write(f"seaweedfs_tpu shell — connected to {env.master_address}\n")
    while True:
        writer.write("> ")
        writer.flush()
        line = stdin.readline()
        if not line or line.strip() in ("exit", "quit"):
            return
        try:
            run_command(env, line, writer)
        except (ShellError, rpc.RpcFault) as e:
            writer.write(f"error: {e}\n")
        except Exception as e:  # noqa: BLE001 — REPL survives command crashes
            writer.write(f"error: {type(e).__name__}: {e}\n")
