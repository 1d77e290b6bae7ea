"""CLI command registry — mirror of weed/command's Command-struct pattern
[VERIFY: mount empty; SURVEY.md §2.1 "CLI entry"]. Each command module
registers a `Command(name, help, run)`; `seaweedfs_tpu.__main__` dispatches.
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass
from typing import Callable


@dataclass
class Command:
    name: str
    help: str
    configure: Callable[[argparse.ArgumentParser], None]
    run: Callable[[argparse.Namespace], int]


_REGISTRY: dict[str, Command] = {}

#: `time.monotonic()` at the first line of `__main__`, set by `__main__` in a
#: process that was started AS the command (`python -m seaweedfs_tpu ...`) and
#: nowhere else: where the shell's `shell.start` span takes its marks from
STARTED: tuple = ()

# Every command: name -> (module under this package that registers it, its
# one-line help), in the order `-h` lists them. A table, so that a process
# imports the module of the command its command line names and not its
# neighbours': a `shell` child never pays for command/local.py (numpy, the
# stripe engine, the GF tables), a `version` not for anything. A name that
# is not here still works, through `commands()`; tests/test_cli.py holds the
# table equal to what the modules register.
COMMAND_TABLE: dict[str, tuple[str, str]] = {
    "benchmark": ("bench_tools", "write/read load generator against a cluster"),
    "upload": ("bench_tools", "upload local files, printing their fids"),
    "download": ("bench_tools", "download files by fid"),
    "encode": (
        "local",
        "EC-encode a volume: <base>.dat [+.idx] -> .ec00..13 + .ecx (TPU matmul path)",
    ),
    "rebuild": ("local", "reconstruct missing .ecNN shards from >=10 survivors"),
    "decode": ("local", "shards -> <base>.dat (+.idx from .ecx/.ecj)"),
    "verify": ("local", "re-encode data shards and compare stored parity"),
    "fix": ("local", "rebuild <base>.idx by scanning <base>.dat"),
    "compact": ("local", "vacuum a volume: rewrite live needles, drop deleted"),
    "export": ("local", "dump live needles as JSON lines"),
    "version": ("servers", "print version"),
    "master": ("servers", "run a master server"),
    "volume": ("servers", "run a volume server"),
    "server": ("servers", "run master + volume server in one process"),
    "filer": ("servers", "run a filer (namespace) server"),
    "s3": ("servers", "run an S3-compatible gateway against a filer"),
    "webdav": ("servers", "run a WebDAV gateway against a filer"),
    "iam": ("servers", "run an AWS-IAM-compatible identity API"),
    "mount": ("servers", "mount the filer as a FUSE filesystem"),
    "mq.broker": ("servers", "run a message-queue broker on the filer"),
    "shell": ("servers", "operator shell (REPL or -c script)"),
    "scaffold": ("servers", "print a commented TOML config template"),
    "filer.sync": ("sync", "continuously replicate one filer into another"),
    "filer.backup": ("sync", "apply pending filer events to a local directory"),
    "filer.meta.tail": ("sync", "stream filer metadata events as JSON lines"),
    "filer.copy": ("sync", "bulk-copy local files/directories into the filer"),
}


def register(cmd: Command) -> Command:
    _REGISTRY[cmd.name] = cmd
    return cmd


def _load(module: str) -> None:
    """Import one command module for its registrations. `__import__`, not
    `importlib.import_module`: `python -X importtime` logs only the former,
    and that log is how a child's start is read."""
    __import__(f"{__name__}.{module}")


def load_command(name: str) -> Command:
    """The one command `name` of COMMAND_TABLE, importing only its module."""
    _load(COMMAND_TABLE[name][0])
    return _REGISTRY[name]


def commands() -> dict[str, Command]:
    """Every command: imports all the modules for their registrations."""
    for module, _help in COMMAND_TABLE.values():
        _load(module)
    return dict(_REGISTRY)
