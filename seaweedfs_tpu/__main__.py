"""`python -m seaweedfs_tpu <command>` — the `weed`-style single entry point
(ref: weed/command CLI layout, SURVEY.md §2.1 [VERIFY: mount empty]).

Every command accepts -cpuprofile/-memprofile (the reference's pprof
flags, SURVEY.md §5): cProfile stats / tracemalloc snapshot written on
exit."""

from __future__ import annotations

import time

_FIRST_LINE = time.monotonic()  # before any import of the package's own: `shell.start` reads it

import argparse  # noqa: E402
import sys  # noqa: E402

from seaweedfs_tpu import command  # noqa: E402
from seaweedfs_tpu.command import COMMAND_TABLE, commands, load_command  # noqa: E402


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    # a command line that starts with a command's name loads that command's
    # module alone; anything else (no command, -h, a name the table lacks)
    # loads them all and gets the usage and the errors of the whole tree
    if argv and argv[0] in COMMAND_TABLE:
        cmds = {argv[0]: load_command(argv[0])}
    else:
        cmds = commands()
    parser = argparse.ArgumentParser(
        prog="seaweedfs_tpu",
        description="TPU-native SeaweedFS-capability framework",
    )
    sub = parser.add_subparsers(dest="command", metavar="command")
    for cmd in cmds.values():
        p = sub.add_parser(cmd.name, help=cmd.help)
        cmd.configure(p)
        p.add_argument("-cpuprofile", default="", help="write cProfile stats here on exit")
        p.add_argument("-memprofile", default="", help="write a tracemalloc snapshot here on exit")
        p.set_defaults(_run=cmd.run)
    args = parser.parse_args(argv)
    if not getattr(args, "_run", None):
        parser.print_help()
        return 2
    if __name__ == "__main__":  # this process IS the command: its birth is the command's
        command.STARTED = (_FIRST_LINE,)
    # process-wide TLS from security.toml [grpc]: activated before any
    # command binds a socket or dials a peer, so every server AND tool
    # (shell, upload, sync, ...) in this process speaks TLS uniformly
    from seaweedfs_tpu.security import tls as _tls
    from seaweedfs_tpu.utils.config import load_configuration as _load_conf

    try:
        _tls.configure_from_conf(_load_conf("security"))
    except (OSError, ValueError) as e:
        print(f"security.toml tls config error: {e}", file=sys.stderr)
        return 1
    profiler = None
    if getattr(args, "cpuprofile", ""):
        import cProfile

        profiler = cProfile.Profile()
        profiler.enable()
    if getattr(args, "memprofile", ""):
        import tracemalloc

        tracemalloc.start()
    try:
        return args._run(args)
    except (OSError, ValueError, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    finally:
        if profiler is not None:
            profiler.disable()
            profiler.dump_stats(args.cpuprofile)
        if getattr(args, "memprofile", ""):
            import tracemalloc

            snap = tracemalloc.take_snapshot()
            with open(args.memprofile, "w", encoding="utf-8") as f:
                for stat in snap.statistics("lineno")[:200]:
                    f.write(str(stat) + "\n")


if __name__ == "__main__":
    sys.exit(main())
