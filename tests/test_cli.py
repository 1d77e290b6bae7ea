"""CLI tests — the single-chip tpuec slice (SURVEY.md §7.1.3): encode,
rebuild, verify, decode, fix, compact, export on local volume files, driven
through the real argparse entry point."""

import json
import os
import subprocess
import sys

import pytest

from seaweedfs_tpu.__main__ import main
from seaweedfs_tpu.command import COMMAND_TABLE, commands, load_command
from seaweedfs_tpu.ec import stripe
from seaweedfs_tpu.ec.constants import TOTAL_SHARDS_COUNT
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

LARGE, SMALL = 4096, 512  # scaled-down stripe geometry for tests


@pytest.fixture
def vol(tmp_path):
    """A small volume with a few needles; returns its base path."""
    v = Volume(str(tmp_path), 7, "")
    needles = {}
    for i in range(1, 9):
        n = Needle(cookie=0x1000 + i, id=i, data=bytes([i]) * (100 * i))
        v.write_needle(n)
        needles[i] = n.data
    v.delete_needle(3)
    v.close()
    return str(tmp_path / "7"), needles


def run_cli(*argv):
    return main(list(argv))


def test_encode_rebuild_verify_roundtrip(vol, capsys):
    base, _ = vol
    assert run_cli("encode", base, "--large-block", str(LARGE), "--small-block", str(SMALL)) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["shards"] == TOTAL_SHARDS_COUNT

    assert run_cli("verify", base) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["verified"]

    # kill 4 shards, rebuild, verify again
    for s in (0, 5, 11, 13):
        os.remove(stripe.shard_file_name(base, s))
    assert run_cli("rebuild", base) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["rebuilt_shards"] == [
        0,
        5,
        11,
        13,
    ]
    assert run_cli("verify", base) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["verified"]


def test_decode_restores_dat(vol, capsys):
    base, needles = vol
    with open(base + ".dat", "rb") as f:
        original = f.read()
    run_cli("encode", base, "--large-block", str(LARGE), "--small-block", str(SMALL))
    os.remove(base + ".dat")
    os.remove(stripe.shard_file_name(base, 2))  # decode must tolerate a lost data shard
    assert run_cli("decode", base) == 0
    with open(base + ".dat", "rb") as f:
        assert f.read() == original
    # .idx regenerated from .ecx (+.ecj): volume must open and serve needles
    v = Volume(os.path.dirname(base), 7, "")
    assert v.read_needle(5).data == needles[5]
    with pytest.raises(KeyError):
        v.read_needle(3)  # deleted pre-encode
    v.close()


def test_fix_rebuilds_idx(vol, capsys):
    base, needles = vol
    os.remove(base + ".idx")
    assert run_cli("fix", base) == 0
    v = Volume(os.path.dirname(base), 7, "")
    assert v.read_needle(8).data == needles[8]
    with pytest.raises(KeyError):
        v.read_needle(3)  # tombstone must survive the rebuild
    v.close()


def test_compact_drops_deleted(vol, capsys):
    base, needles = vol
    assert run_cli("compact", base) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["bytes_after"] < out["bytes_before"]
    v = Volume(os.path.dirname(base), 7, "")
    assert v.read_needle(4).data == needles[4]
    v.close()


def test_export_lists_live_needles(vol, capsys):
    base, needles = vol
    assert run_cli("export", base) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    ids = {int(r["id"], 16) for r in lines}
    assert ids == {1, 2, 4, 5, 6, 7, 8}  # 3 deleted


def test_version(capsys):
    assert run_cli("version") == 0
    assert "seaweedfs_tpu" in capsys.readouterr().out


# -- the command table: a process imports the module of the command it is given ----

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_command_table_is_what_the_modules_register():
    """`__main__` imports `COMMAND_TABLE[name][0]` alone for a command line
    that starts with `name`: the table must be the registrations, name for
    name, module for module, help for help, or a command is lost to operators."""
    registered = commands()
    assert len(registered) == 26
    assert COMMAND_TABLE == {
        name: (cmd.run.__module__.rsplit(".", 1)[1], cmd.help) for name, cmd in registered.items()}
    assert all(cmd.configure.__module__ == cmd.run.__module__ for cmd in registered.values())


def _child(*argv):
    return subprocess.run([sys.executable, "-m", "seaweedfs_tpu", *argv], cwd=ROOT, timeout=120,
                          capture_output=True, text=True)


@pytest.mark.parametrize("argv", [(), ("-h",), ("--help",)], ids=["no-command", "-h", "--help"])
def test_a_new_interpreter_lists_every_command_in_the_tables_order(argv):
    """No command and `-h` load every module and print the whole tree: all 26
    names with their help lines, in the table's order (the registration order of
    a process that imports all four modules); 2 for a command line without a
    command, 0 for a question."""
    done = _child(*argv)
    assert done.returncode == (0 if argv else 2) and not done.stderr
    assert done.stdout.startswith("usage: seaweedfs_tpu [-h] command ...\n")
    listing = " ".join(done.stdout.split())
    at = [listing.find(f" {name} {help_} ") for name, (_, help_) in COMMAND_TABLE.items()]
    assert -1 not in at and at == sorted(at), at


@pytest.mark.parametrize("argv", [("nosuch",), ("nosuch", "-h"), ("-x",), ("shel",)],
                         ids=["unknown", "unknown-h", "option-first", "prefix-of-a-name"])
def test_a_command_line_that_names_no_command_fails_as_the_whole_tree_fails_it(argv, capsys):
    with pytest.raises(SystemExit) as e:
        main(list(argv))
    err = capsys.readouterr().err
    assert e.value.code == 2 and err.startswith("usage: seaweedfs_tpu [-h] command ...\n")
    if argv[0].startswith("-"):
        assert err.endswith(f"error: unrecognized arguments: {argv[0]}\n")
    else:
        assert f"argument command: invalid choice: {argv[0]!r} (choose from " in err
        assert all(name in err for name in COMMAND_TABLE)


@pytest.mark.parametrize("name", sorted(COMMAND_TABLE))
def test_every_command_parses_alone(name, capsys):
    """The parser `__main__` builds for a command line that starts with
    `name` holds that one command: its own usage, with the profile flags."""
    assert load_command(name) is commands()[name]
    with pytest.raises(SystemExit) as e:
        main([name, "-h"])
    out = capsys.readouterr().out
    assert e.value.code == 0 and out.startswith(f"usage: seaweedfs_tpu {name} [-h]")
    assert "-cpuprofile CPUPROFILE" in out and "-memprofile MEMPROFILE" in out


def test_a_new_interpreter_runs_a_command_with_its_module_alone():
    """`version` is in `command/servers.py`: a new interpreter that runs it
    has imported neither the offline tools (numpy, the stripe engine) nor the
    filer-sync and load tools, and an argument error is the command's own."""
    code = ("import sys; from seaweedfs_tpu.__main__ import main; rc = main(['version']); "
            "print(*sorted(m for m in sys.modules if m.startswith('seaweedfs_tpu.command.'))); sys.exit(rc)")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=120, capture_output=True, text=True)
    assert done.returncode == 0 and done.stdout.splitlines()[-1] == "seaweedfs_tpu.command.servers"
    bad = _child("shell", "-nosuchflag")
    assert bad.returncode == 2 and bad.stderr == (
        "usage: seaweedfs_tpu [-h] command ...\nseaweedfs_tpu: error: unrecognized arguments: -nosuchflag\n")


def test_fix_preserves_live_empty_needle(tmp_path, capsys):
    """A live needle with empty data must survive an index rebuild — its
    on-disk record (size 5: DataSize+flags) is distinct from a delete
    marker (size 0)."""
    v = Volume(str(tmp_path), 9, "")
    v.write_needle(Needle(cookie=0xAA, id=1, data=b""))
    v.write_needle(Needle(cookie=0xBB, id=2, data=b"live"))
    v.delete_needle(2)
    v.close()
    base = str(tmp_path / "9")
    os.remove(base + ".idx")
    assert run_cli("fix", base) == 0
    v = Volume(str(tmp_path), 9, "")
    assert v.read_needle(1).data == b""
    with pytest.raises(KeyError):
        v.read_needle(2)
    v.close()


def test_compact_refuses_empty_index_with_data(vol, capsys):
    """compact on a volume whose .idx was lost must not wipe the data."""
    base, _ = vol
    os.remove(base + ".idx")
    # constructing Volume now self-heals by scan; simulate the dangerous
    # state directly: empty map + populated .dat
    v = Volume.__new__(Volume)
    import threading

    from seaweedfs_tpu.storage.needle_map import CompactMap

    v.dir, v.id, v.collection = os.path.dirname(base), 7, ""
    v.read_only = False
    v.tiered = False
    v._lock = threading.RLock()
    v.nm = CompactMap()
    v.base_path, v.dat_path, v.idx_path = base, base + ".dat", base + ".idx"
    v._dat = open(v.dat_path, "r+b")
    from seaweedfs_tpu.storage.super_block import SuperBlock

    v._dat.seek(0)
    v.super_block = SuperBlock.from_bytes(v._dat.read(8))
    v._idx = open(v.idx_path, "ab")
    with pytest.raises(IOError):
        v.compact()
    v.close()
    with open(base + ".dat", "rb") as f:
        assert len(f.read()) > 8  # data untouched


def test_volume_self_heals_missing_idx(vol):
    base, needles = vol
    os.remove(base + ".idx")
    v = Volume(os.path.dirname(base), 7, "")
    assert v.read_needle(5).data == needles[5]
    v.close()


def test_scan_detects_midfile_corruption(vol):
    """A corrupted size field mid-file must raise CorruptVolume (valid
    records follow), never silently truncate the index — silent truncation
    plus compact would destroy everything after the bad record."""
    from seaweedfs_tpu.storage import scan as scan_mod
    from seaweedfs_tpu.storage import types as t

    base, _ = vol
    # find the offset of needle id=2's record via a clean scan
    records = list(scan_mod.scan_volume_file(base + ".dat"))
    off2 = next(off for off, n in records if n.id == 2)
    with open(base + ".dat", "r+b") as f:
        f.seek(off2 + 12)  # size field of the header
        f.write((0x7FFF0000).to_bytes(4, "big"))
    with pytest.raises(scan_mod.CorruptVolume):
        list(scan_mod.scan_volume_file(base + ".dat"))
    with pytest.raises(scan_mod.CorruptVolume):
        scan_mod.rebuild_idx(base)
    assert not os.path.exists(base + ".idx.tmp")  # no litter on failure


def test_scan_tolerates_truncated_tail(vol):
    base, _ = vol
    full = list(scan_mod_records(base))
    with open(base + ".dat", "r+b") as f:
        f.seek(0, os.SEEK_END)
        f.truncate(f.tell() - 37)  # chop mid-record
    partial = list(scan_mod_records(base))
    assert len(partial) == len(full) - 1


def scan_mod_records(base):
    from seaweedfs_tpu.storage import scan as scan_mod

    return scan_mod.scan_volume_file(base + ".dat")


def test_compact_fully_deleted_volume_reclaims(tmp_path):
    """All-needles-deleted is a legitimate empty state (tombstones in .idx)
    — compact must reclaim it, not confuse it with a lost index."""
    v = Volume(str(tmp_path), 11, "")
    for i in (1, 2, 3):
        v.write_needle(Needle(cookie=i, id=i, data=b"z" * 500))
    for i in (1, 2, 3):
        v.delete_needle(i)
    before, after = v.compact()
    assert after < before and after == 8  # superblock only
    v.close()


def test_ttl_rejects_out_of_range():
    from seaweedfs_tpu.storage.super_block import TTL

    for bad in ("300m", "-3m", "256h"):
        with pytest.raises(ValueError):
            TTL.parse(bad)
    assert TTL.parse("255m").count == 255
