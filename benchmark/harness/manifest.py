"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric sits in a file of its
own, found by the name in BENCHMARK.json:

  benchmark/configs/<config>.json          the deployment as it is run
  benchmark/traffic/<traffic>.json         names its driver and its parameters
  benchmark/drivers/<driver>.py            one per kind of traffic
  benchmark/layer_metrics/<metric>.json    source, reducer and its arguments

so a later PR adds a cell or a metric with new files and one list entry."""

from __future__ import annotations

import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what} {value!r} is not a name (letters, digits, _ . -; at most 64)")
    return value


def _load_json(path: str) -> dict:
    if not os.path.exists(path):
        raise ManifestError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, path: str | None = None, bench_dir: str = BENCH_DIR):
        self.bench_dir = bench_dir
        self.doc = _load_json(path or os.path.join(ROOT, "BENCHMARK.json"))
        self.run_seconds = int(self.doc["run_seconds"])
        self.configs = {_name(c["name"], "config"): c for c in self.doc["configs"]}
        self.workloads = {_name(w["name"], "workload"): w for w in self.doc["workloads"]}
        self.end_to_end = {}
        self.per_layer = {}
        for kind, table in (("end_to_end", self.end_to_end), ("per_layer", self.per_layer)):
            for m in self.doc[kind]:
                _name(m["name"], "metric")
                if not isinstance(m.get("unit"), str) or not UNIT_RE.match(m["unit"]):
                    raise ManifestError(f"metric {m['name']}: unit {m.get('unit')!r} is not allowed")
                if m.get("better") not in ("lower", "higher"):
                    raise ManifestError(f"metric {m['name']}: better must be lower or higher")
                if m.get("source") not in SOURCES:
                    raise ManifestError(f"metric {m['name']}: unknown source {m.get('source')!r}")
                if m["name"] in self.end_to_end or m["name"] in self.per_layer:
                    raise ManifestError(f"metric {m['name']} appears twice")
                table[m["name"]] = m
        for m in self.per_layer.values():
            if m.get("moves") not in self.end_to_end:
                raise ManifestError(f"metric {m['name']} moves unknown {m.get('moves')!r}")
        for w in self.workloads.values():
            _name(w["traffic"], "traffic")
            if w["config"] not in self.configs:
                raise ManifestError(f"workload {w['name']}: unknown config {w['config']!r}")

    def cell(self, workload: str) -> dict:
        """The cell's entry with its configuration's and traffic's files loaded."""
        if workload not in self.workloads:
            raise ManifestError(f"unknown workload {workload!r}; have {sorted(self.workloads)}")
        w = self.workloads[workload]
        config = _load_json(os.path.join(self.bench_dir, "configs", w["config"] + ".json"))
        traffic = _load_json(os.path.join(self.bench_dir, "traffic", w["traffic"] + ".json"))
        driver = _name(traffic.get("driver"), "driver")
        if not os.path.exists(os.path.join(self.bench_dir, "drivers", driver + ".py")):
            raise ManifestError(f"traffic {w['traffic']}: missing driver drivers/{driver}.py")
        return {"workload": w, "config": config, "traffic": traffic, "driver": driver}

    def metrics_of(self, kind: str, workload: str) -> list[dict]:
        """The metrics of `kind` that list this cell (or list none at all)."""
        table = self.end_to_end if kind == "end_to_end" else self.per_layer
        return [m for m in table.values() if workload in m.get("workloads", [workload])]

    def layer_metric_spec(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "layer_metrics", name + ".json"))
