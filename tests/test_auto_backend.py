"""Evidence-based `auto` backend + incremental sweep harvesting.

The r6 contract: `new_encoder("auto")` on TPU flips to the fused Pallas
kernel ONLY when a committed on-chip measurement artifact shows a fused
variant beating the XLA steady-state — fabricated evidence files (fused
faster / slower / absent / stale / off-chip) must each select the
expected backend. The sweep that produces the evidence persists one JSON
line per config as it lands and resumes past configs an interrupted run
already harvested.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from seaweedfs_tpu.ops import rs_codec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_evidence(dirpath, meas, name="DEVICE_MEASUREMENT_r91.json"):
    with open(os.path.join(dirpath, name), "w", encoding="utf-8") as f:
        json.dump(meas, f)


def _fresh_when():
    import datetime

    return datetime.datetime.utcnow().strftime("%Y-%m-%dT%H:%MZ")


# -- pick_device_backend: the decision table ---------------------------------


def test_fused_faster_flips_to_pallas_with_variant_config(tmp_path):
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu (TPU v5 lite)",
        "xla_steady_gbps": 31.0, "pallas_bf16_steady_gbps": 44.5,
    })
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "pallas"
    assert dec["pallas_mxu"] == "bf16" and dec["pallas_tile"] is None
    assert "beats" in dec["reason"]
    assert dec["evidence_file"] == "DEVICE_MEASUREMENT_r91.json"


def test_fused_slower_keeps_xla(tmp_path):
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu (TPU v5 lite)",
        "xla_steady_gbps": 31.0, "pallas_auto_steady_gbps": 18.7,
    })
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "jax"
    assert "no fused number beats" in dec["reason"]


def test_absent_evidence_keeps_xla(tmp_path):
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "jax"
    assert "no committed" in dec["reason"]


def test_stale_evidence_keeps_xla_even_when_fused_wins(tmp_path):
    _write_evidence(tmp_path, {
        "when": "2024-01-01T00:00Z", "platform": "tpu (TPU v5 lite)",
        "xla_steady_gbps": 31.0, "pallas_bf16_steady_gbps": 44.5,
    })
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "jax"
    assert "stale" in dec["reason"]


def test_off_chip_evidence_never_flips(tmp_path):
    # a cpu-platform artifact (e.g. someone committed a sanity run) is
    # not on-chip evidence, no matter what its numbers say
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "cpu",
        "xla_steady_gbps": 0.04, "pallas_auto_steady_gbps": 1.0,
    })
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "jax"
    assert "not an on-chip" in dec["reason"]


def test_newest_round_wins_and_unreadable_newest_falls_back(tmp_path):
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu",
        "xla_steady_gbps": 31.0, "pallas_auto_steady_gbps": 18.0,
    }, name="DEVICE_MEASUREMENT_r04.json")
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu",
        "xla_steady_gbps": 31.0, "pallas_dma_steady_gbps": 50.0,
    }, name="DEVICE_MEASUREMENT_r06.json")
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "pallas" and dec["pallas_mxu"] == "dma"
    assert dec["evidence_file"] == "DEVICE_MEASUREMENT_r06.json"
    # corrupt the newest: the older readable round must serve
    with open(os.path.join(tmp_path, "DEVICE_MEASUREMENT_r06.json"), "w") as f:
        f.write("{torn")
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "jax"
    assert dec["evidence_file"] == "DEVICE_MEASUREMENT_r04.json"


def test_sweep_section_counts_as_evidence(tmp_path):
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu (TPU v5 lite)",
        "xla_steady_gbps": 31.0,
        "sweep": {"encode": {"pallas-mplane-32768": 47.2, "xla": 31.0},
                  "rebuild": {"rebuild-pallas-auto": 40.0}},
    })
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "pallas"
    assert dec["pallas_mxu"] == "mplane" and dec["pallas_tile"] == 32768


def test_sweep_only_artifact_flips_without_stage1_keys(tmp_path):
    """The short-window case the harvest exists for: the watch-fired
    sweep landed (with its own xla anchor) but the window worker never
    wrote stage-1 scan-chain keys. The sweep table alone must decide."""
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu (TPU v5 lite)",
        "sweep": {"encode": {"xla": 31.2, "pallas-dma-65536": 45.0}},
    })
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "pallas"
    assert dec["xla_steady_gbps"] == 31.2
    assert dec["pallas_mxu"] == "dma" and dec["pallas_tile"] == 65536
    # and a sweep whose fused numbers LOSE to its own xla anchor stays jax
    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu (TPU v5 lite)",
        "sweep": {"encode": {"xla": 31.2, "pallas-auto": 19.0}},
    })
    backend, dec = rs_codec.pick_device_backend(art_dir=str(tmp_path))
    assert backend == "jax" and "no fused number beats" in dec["reason"]


def test_sweep_resume_ignores_other_mode_records(tmp_path):
    """A cpu/--tiny sanity run landing in the harvest file must NOT mark
    configs done for the on-chip sweep (the assembler excludes those
    records from evidence, so skipping on them would leave the harvest
    permanently without usable numbers)."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        import kernel_sweep as ks
    finally:
        sys.path.pop(0)
    p = tmp_path / "SWEEP.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"variant": "pallas-auto", "platform": "cpu",
                            "tiny": True, "exact": True}) + "\n")
        f.write(json.dumps({"variant": "pallas-dma-auto", "platform": "tpu",
                            "tiny": False, "steady_gbps": 50.0}) + "\n")
    done = ks.load_done(str(p), platform="tpu", tiny=False)
    assert "pallas-dma-auto" in done and "pallas-auto" not in done
    # a cpu sanity re-run, conversely, resumes only its own records
    done = ks.load_done(str(p), platform="cpu", tiny=True)
    assert "pallas-auto" in done and "pallas-dma-auto" not in done


def test_variant_label_parsing():
    cases = {
        "pallas_steady_gbps": ("int8", None),
        "pallas_auto_steady_gbps": ("int8", None),
        "pallas_bf16_steady_gbps": ("bf16", None),
        "pallas_tile8192_steady_gbps": ("int8", 8192),
        "pallas-u8-16384": ("u8", 16384),
        "pallas-dma-auto": ("dma", None),
        "pallas-65536": ("int8", 65536),
    }
    for label, want in cases.items():
        assert rs_codec.parse_fused_variant(label) == want, label


# -- new_encoder integration --------------------------------------------------


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_new_encoder_flips_on_winning_evidence(tmp_path, monkeypatch):
    import jax

    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu (TPU v5 lite)",
        "xla_steady_gbps": 31.0, "pallas_dma_steady_gbps": 52.0,
    })
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeTpu()])
    monkeypatch.setattr(rs_codec, "_artifacts_dir", lambda: str(tmp_path))
    enc = rs_codec.new_encoder()
    assert enc.backend == "pallas"
    assert enc.pallas_mxu == "dma" and enc.pallas_tile is None
    assert enc.selection["source"] == "on-chip-evidence"
    assert enc.selection["backend"] == "pallas"


def test_new_encoder_keeps_xla_on_losing_evidence(tmp_path, monkeypatch):
    import jax

    _write_evidence(tmp_path, {
        "when": _fresh_when(), "platform": "tpu (TPU v5 lite)",
        "xla_steady_gbps": 31.0, "pallas_auto_steady_gbps": 18.7,
    })
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeTpu()])
    monkeypatch.setattr(rs_codec, "_artifacts_dir", lambda: str(tmp_path))
    enc = rs_codec.new_encoder()
    assert enc.backend == "jax"
    assert enc.selection["source"] == "on-chip-evidence"


def test_weedtpu_backend_env_overrides_auto(monkeypatch):
    monkeypatch.setenv("WEEDTPU_BACKEND", "numpy")
    enc = rs_codec.new_encoder()
    assert enc.backend == "numpy"
    assert enc.selection["source"] == "env:WEEDTPU_BACKEND"
    # explicit callers are never overridden
    enc = rs_codec.new_encoder(backend="jax")
    assert enc.backend == "jax"
    assert enc.selection["source"] == "explicit"
    monkeypatch.setenv("WEEDTPU_BACKEND", "bogus")
    with pytest.raises(ValueError, match="WEEDTPU_BACKEND"):
        rs_codec.new_encoder()


def test_selection_exported_through_stats(monkeypatch):
    from seaweedfs_tpu import stats

    monkeypatch.setenv("WEEDTPU_BACKEND", "numpy")
    rs_codec.new_encoder()
    lines = "\n".join(stats.EcBackendSelected.collect())
    assert (
        'weedtpu_ec_backend_selected{backend="numpy",source="env:WEEDTPU_BACKEND"} 1.0'
        in lines
    )
    # a later different selection zeroes the previous one
    monkeypatch.delenv("WEEDTPU_BACKEND")
    enc = rs_codec.new_encoder()
    lines = "\n".join(stats.EcBackendSelected.collect())
    assert (
        'weedtpu_ec_backend_selected{backend="numpy",source="env:WEEDTPU_BACKEND"} 0.0'
        in lines
    )
    src = enc.selection["source"]  # platform, or cpu-bench-evidence when
    assert f'backend="{enc.backend}",source="{src}"}} 1.0' in lines  # promoted


def test_pallas_encoder_honors_variant_config():
    """An evidence-selected variant config must actually reach the kernel
    dispatch and stay byte-exact vs the numpy golden."""
    import numpy as np

    rng = np.random.default_rng(5)
    gold = rs_codec.Encoder(10, 4, backend="numpy")
    data = [rng.integers(0, 256, 700, dtype=np.uint8) for _ in range(10)]
    want = gold.encode([d.copy() for d in data])
    for mxu, tile in (("dma", None), ("mplane", 8192), ("u8", None)):
        enc = rs_codec.Encoder(
            10, 4, backend="pallas", pallas_mxu=mxu, pallas_tile=tile,
            pallas_interpret=True,
        )
        got = enc.encode([d.copy() for d in data])
        for a, b in zip(want, got):
            assert np.array_equal(a, b), (mxu, tile)


# -- interrupted-sweep resume + watch->assembler round-trip -------------------


def test_interrupted_sweep_resume_skips_persisted_configs(tmp_path):
    """Simulate the r5 failure mode: a sweep dies mid-run (here: its
    harvest file is truncated to a prefix + one torn line). The re-run
    must skip every persisted config, re-measure only the missing ones,
    and leave a complete harvest."""
    out = tmp_path / "SWEEP.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run1 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "kernel_sweep.py"),
         "--smoke", "--out", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=560,
    )
    assert run1.returncode == 0, run1.stdout + run1.stderr
    lines = out.read_text().strip().splitlines()
    all_names = [json.loads(l)["variant"] for l in lines]
    assert len(all_names) >= 10
    # interrupt: keep a prefix, add a torn line (crash mid-write)
    keep = lines[:-3]
    out.write_text("\n".join(keep) + "\n" + '{"variant": "pallas-')
    run2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "kernel_sweep.py"),
         "--smoke", "--out", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=560,
    )
    assert run2.returncode == 0, run2.stdout + run2.stderr
    resumed = [
        json.loads(l)["variant"]
        for l in run2.stdout.splitlines()
        if '"resumed": true' in l
    ]
    assert sorted(resumed) == sorted(json.loads(l)["variant"] for l in keep)
    # every config exactly once in the final harvest (the torn fragment
    # is terminated, never glued onto an appended record)
    final = []
    for l in out.read_text().strip().splitlines():
        try:
            final.append(json.loads(l)["variant"])
        except ValueError:
            pass  # the terminated torn fragment
    assert sorted(final) == sorted(all_names)
