"""The check must be able to fail. Each case drives a whole run of a cell at
the rehearsal's size on the CPU (the look for a chip skipped by `--rehearse`),
sound or with one fault, and reads `checks_ok` from the result line:

  flip_shard_byte  the control: a guarantee the configuration states (each
                   shard's CRC32 equals .eci's; parity equals the reference's;
                   rebuilt shards are byte-identical) broken on disk after the
                   program wrote the file
  flip_first_encode  the same fault in a shard of the window's FIRST encode, whose
                   files are gone by the end: only the CRC32s kept of each encode show it
  broken_apply     the timed path broken underneath: the device's GF(2^8)
                   apply alters one byte of each output row where it is produced

`correct` is false in every rehearsal, whatever the checks say."""

import json
import os
import subprocess
import sys

import pytest

from harness.manifest import BENCH_DIR, ROOT

CASES = [
    ("warm10p4.encode-cycle", "", True),
    ("warm10p4.encode-cycle", "flip_shard_byte", False),
    ("warm10p4.encode-cycle", "flip_first_encode", False),
    ("warm10p4.encode-cycle", "broken_apply", False),
    ("warm10p4.rebuild-4lost", "", True),
    ("warm10p4.rebuild-4lost", "flip_shard_byte", False),
    ("warm10p4.rebuild-4lost", "broken_apply", False),
    ("spread10p4.rebuild-serverlost", "", True),
]


@pytest.mark.parametrize("workload,fault,sound", CASES)
def test_checks_come_out(workload, fault, sound):
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", workload,
           "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0", "--rehearse"]
    if fault:
        cmd += ["--fault", fault]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearse"] is True
    assert result["checks_ok"] is sound, p.stdout[-3000:]
    assert result["attempted"] >= 1
    # every number compared comes last in the line, beside its limit, and again on stderr
    assert list(result)[-1] == "checks" and result["checks"]["failed_ops"] == {"value": result["failed"], "limit": 0}
    assert all(f"check {name}: value {c['value']} limit {c['limit']}" in p.stderr
               for name, c in result["checks"].items())
    assert (all(c["value"] == 0 for c in result["checks"].values())) is sound
    timed = result["timed"]
    assert set(timed) == {"ops", "median_s", "max_s", "stalled_ops", "median_rate_MBps"}
    if sound:
        # a sound run timed every command it attempted; its rate is all of them, not the median one
        assert timed["ops"] == result["attempted"] and 0 < timed["median_s"] <= timed["max_s"]
        (rate,) = [m["value"] for name, m in result["metrics"].items() if name.endswith("_MBps")]
        assert rate > 0
        if timed["ops"] == 1:  # one command: the rate is that command's
            assert rate == pytest.approx(timed["median_rate_MBps"])
        if workload == "warm10p4.encode-cycle":
            assert result["metrics"]["encode_cmd_p50_s"] == {"value": timed["median_s"], "unit": "s"}
        else:
            assert not [name for name in result["metrics"] if name.endswith("_cmd_p50_s")]
