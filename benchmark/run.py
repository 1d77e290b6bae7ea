#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

  python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's volumes from the seed, boots the ONE chip-owning server as a
child (this parent never imports jax), warms the cell's own shapes, measures
for `--seconds`, checks what the window produced against the plain reference,
prints one JSON line last, and exits. It fails where the server's `/status`
does not report a TPU device and a device backend; it never falls back.

  --rehearse   every phase on the CPU at the tiny size the traffic file names;
               prints "correct": false, exits 1, and is never a result
  --fault <f>  (tests and the control runs only) break what the driver's
               `FAULTS` names, so that the check is seen to fail: a guarantee
               broken on disk after the program wrote it (the control), or
               `broken_apply`, the device's answer altered where it is produced
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)  # seaweedfs_tpu: the system under test

from harness import manifest as manifest_mod  # noqa: E402
from harness import reducers  # noqa: E402
from harness.server import DEVICE_BACKENDS, BenchError, Server, say  # noqa: E402

GAP_LABELS = ("window start -> first device op", "between device ops",
              "last device op -> window end")


class Run:
    """What a driver gets: the cell, the server, and where to put what it
    measured. A driver has `setup(run)`, `window(run)` and `verify(run)`."""

    def __init__(self, args, cell: dict):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.fault = args.fault
        self.config = cell["config"]
        self.traffic = dict(cell["traffic"])
        self.dataset = dict(self.config["dataset"])
        if self.rehearse:
            self.traffic.update(self.traffic.get("rehearse", {}))
            self.dataset.update(self.config.get("rehearse_dataset", {}))
        self.workload = cell["workload"]["name"]
        self.chips = int(cell["workload"]["chips"])
        self.out_dir = os.path.join(
            ROOT, "chiprun_out", "benchmark", self.workload, f"seed{self.seed}-trace{int(self.trace)}")
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        self.work = tempfile.mkdtemp(prefix="weedbench_")
        self.data_dir = os.path.join(self.work, "data")
        self.srv: Server | None = None
        self.checks: list[dict] = []
        self.attempted = 0
        self.failed = 0
        self.metrics: dict[str, float] = {}
        self.facts = {"setup": {}, "samples": {}, "traces": [], "traced": [], "device_kind": None}
        self.trace_dirs: list[str] = []
        self.warm_mark = 0
        self.window_mark = 0
        self.backend: dict = {}

    # -- set-up ---------------------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase of set-up; a `setup_value` reader can read it."""
        t0 = time.monotonic()
        yield
        seconds = time.monotonic() - t0
        self.facts["setup"][name + "_s"] = seconds
        say(phase=name, seconds=round(seconds, 3))

    def boot(self, volume_id: int) -> None:
        platform = "cpu" if self.rehearse else "tpu"
        broken = {"WEEDBENCH_BREAK": "apply_matrix"} if self.fault == "broken_apply" else None
        self.srv = Server(platform, self.data_dir, self.out_dir, extra_env=broken)
        self.srv.wait_ready(volume_id)
        self.backend = self.srv.backend()
        device = self.backend.get("device") or {}
        self.facts["device_kind"] = device.get("kind")
        on_chip = device.get("platform") == "tpu" and self.backend.get("backend") in DEVICE_BACKENDS
        say(phase="backend", ec_backend=self.backend)
        if not self.rehearse and not on_chip:
            raise BenchError(f"the server does not run the codec on a TPU: {self.backend}")
        if not self.rehearse and int(device.get("count") or 0) < self.chips:
            raise BenchError(f"the cell asks for {self.chips} chip(s), the server holds {device}")

    def check(self, name: str, value, limit) -> None:
        """One number compared, beside its limit; exact comparisons have limit 0."""
        ok = value is not None and 0 <= value <= limit
        self.checks.append({"check": name, "value": value, "limit": limit, "ok": ok})
        say(check=name, value=value, limit=limit, ok=ok)

    # -- the traced stretches -------------------------------------------------

    @contextlib.contextmanager
    def traced(self, known: dict, on: bool = True):
        """Trace what runs inside, in a `--trace 1` run and where `on`; `known`
        is what the driver knows of the stretch (the bytes it moves)."""
        if not (self.trace and on):
            yield
            return
        d = os.path.join(self.out_dir, f"trace{len(self.trace_dirs)}")
        self.srv.start_trace(d)
        self.trace_dirs.append(d)
        self.facts["traced"].append(known)
        try:
            yield
        finally:
            self.srv.stop_trace()


def reduce_traces(run: Run) -> None:
    if not run.trace_dirs:
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "harness", "trace_reduce.py"), *run.trace_dirs],
        env=env, capture_output=True, text=True, timeout=300,
    )
    if p.returncode != 0:
        raise BenchError(f"trace_reduce failed:\n{p.stdout[-2000:]}{p.stderr[-2000:]}")
    run.facts["traces"] = json.loads(p.stdout.strip().splitlines()[-1])
    with open(os.path.join(run.out_dir, "trace_summary.json"), "w") as f:
        json.dump(run.facts["traces"], f)
    if not run.args.keep_trace:
        for d in run.trace_dirs:
            shutil.rmtree(d, ignore_errors=True)


def breakdown(run: Run) -> dict:
    ops: dict[str, float] = {}
    idle = []
    for t in run.facts["traces"]:
        for chip in t.get("chips", []):
            for name, seconds in chip["top_ops"]:
                ops[name] = ops.get(name, 0.0) + seconds
            runs = [(s, s + d) for _, s, d in chip["modules"]]
            first = min((s for s, _ in runs), default=0.0)
            last = max((e for _, e in runs), default=chip["window_s"])
            for s, d in chip["gaps"]:
                label = GAP_LABELS[0] if s + d <= first + 1e-6 else (
                    GAP_LABELS[2] if s >= last - 1e-6 else GAP_LABELS[1])
                idle.append([label, d])
    return {
        "device_ops": sorted(([n, s] for n, s in ops.items()), key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(idle, key=lambda x: -x[1])[:10],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fault", default="")
    ap.add_argument("--keep-trace", action="store_true", help="leave the raw .xplane.pb in the output directory")
    args = ap.parse_args(argv)

    man = manifest_mod.Manifest()
    cell = man.cell(args.workload)
    driver = importlib.import_module(f"drivers.{cell['driver']}")
    if args.fault and args.fault not in getattr(driver, "FAULTS", ()):
        print(f"benchmark: driver {cell['driver']} knows no fault {args.fault!r}", flush=True)
        return 2
    run = Run(args, cell)
    phase = "preflight"
    try:
        t_setup = time.monotonic()
        from seaweedfs_tpu.utils import native

        with run.phase("native_build"):
            native.build()
        phase = "setup"
        driver.setup(run)
        run.warm_mark = len(run.srv.log_text())
        setup_s = time.monotonic() - t_setup
        say(phase="setup_done", setup_s=round(setup_s, 3),
            cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache"))

        phase = "window"
        t0 = time.monotonic()
        driver.window(run)
        say(phase="window", wall_seconds=round(time.monotonic() - t0, 3),
            attempted=run.attempted, failed=run.failed)
        run.window_mark = len(run.srv.log_text())

        phase = "verify"
        t0 = time.monotonic()
        driver.verify(run)
        say(phase="verify", seconds=round(time.monotonic() - t0, 3))

        phase = "shutdown"
        stats = run.srv.device_stats()
        backend_end = run.srv.backend()
        if backend_end != run.backend:
            raise BenchError(f"the server's backend changed under the run: {run.backend} -> {backend_end}")
        rc = run.srv.stop()
        log = run.srv.log_text()
        if rc != 0:
            raise BenchError(f"server exited {rc} on SIGTERM")
        if "Traceback (most recent call last)" in log:
            raise BenchError("the server's log holds a traceback")
        in_window = log[run.warm_mark:run.window_mark]
        compiles = in_window.count("Finished XLA compilation of ") - in_window.count(
            "Persistent compilation cache hit")
        programs = log.count("Finished XLA compilation of ")
        say(phase="compiles", in_window=compiles, programs=programs,
            cache_hits=log.count("Persistent compilation cache hit"))

        phase = "reduce"
        reduce_traces(run)
        run.metrics["setup_s"] = setup_s
        kind = "per_layer" if run.trace else "end_to_end"
        out = {}
        for m in man.metrics_of(kind, run.workload):
            if kind == "end_to_end":
                value = run.metrics.get(m["name"])
            else:
                spec = man.layer_metric_spec(m["name"])
                value = reducers.READERS[spec["reader"]](run.facts, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
    except Exception as e:  # noqa: BLE001 — every failure is reported, none passed over
        print(f"benchmark: phase {phase} FAILED: {type(e).__name__}: {e}", flush=True)
        if run.srv is not None:
            print("---- last 40 lines of the server log ----", flush=True)
            print("\n".join(run.srv.log_text().splitlines()[-40:]), flush=True)
        return 1
    finally:
        if run.srv is not None:
            run.srv.kill()
        shutil.rmtree(run.work, ignore_errors=True)
    if "jax" in sys.modules:
        print("benchmark: the parent imported jax: it would have held the chip", flush=True)
        return 1
    device = run.backend.get("device") or {}
    checks_ok = bool(run.checks) and all(c["ok"] for c in run.checks) and run.failed == 0 \
        and run.attempted > 0
    result = {
        "correct": checks_ok and not run.rehearse,
        "attempted": run.attempted,
        "failed": run.failed,
        # a fact, not a metric: the timed commands' count, median, longest, stalled (`common.timed_facts`)
        "timed": run.facts.get("timed"),
        "metrics": out,
        "device": {
            "platform": device.get("platform"), "kind": device.get("kind"),
            "count": device.get("count"), "memory_peak_bytes": stats["memory_peak_bytes"],
        },
    }
    if run.trace:
        chips = [c for t in run.facts["traces"] for c in t.get("chips", [])]
        n_chips = max(1, len({c["chip"] for c in chips}))
        result["device"]["busy_s"] = sum(c["busy_s"] for c in chips) / n_chips
        result["device"]["window_s"] = sum(c["window_s"] for c in chips) / n_chips
        result["breakdown"] = breakdown(run)
    if run.rehearse:
        result["rehearse"] = True
        result["checks_ok"] = checks_ok
    # every number compared beside its limit: last in the line, and last on stderr
    result["checks"] = {c["check"]: {"value": c["value"], "limit": c["limit"]} for c in run.checks}
    result["checks"]["failed_ops"] = {"value": run.failed, "limit": 0}
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
