"""Reduction of the HOST side of a profiler trace: the program's own spans.

The chip-owning server mirrors every span it records (`seaweedfs_tpu/obs/
trace.py`: `rpc.server`, `encode.*`, `rebuild.*`, ...) into the profiler as a
`TraceAnnotation` (`command/servers.py`), so a `--trace 1` run's `.xplane.pb`
holds them on the lines of `/host:CPU`, one line per thread, on the clock of
the device's `XLA Modules` / `XLA Ops`. This file reads them:

  python benchmark/harness/span_reduce.py [--workload <cell>] <trace_dir> [...]

prints one JSON object: per traced stretch the spans by name (count, union and
self seconds), every device idle gap attributed to the spans that cover it, the
two cross-checks against the device trace, and, with `--workload`, the values
of the span metrics that `parked/span-metrics.json` defines for that cell. Keep
the raw trace with `run.py --trace 1 --keep-trace` and point this at
`chiprun_out/benchmark/<cell>/seed<n>-trace1/trace*`.

The pure functions take plain lists, as `trace_reduce.py`'s do, and are what
`benchmark/tests/test_span_reduce.py` checks on hand-made lists and on a small
recorded trace. An event is `(name, start_s, duration_s)`, times relative to
the `bench.window` annotation's start (the reference of `trace_reduce`'s
summaries); a thread is a list of events; a stretch has a list of threads.
A trace without spans (the parent commit's, a CPU server's) gives empty lists,
and every reader below then returns None.

What the profiler does with an annotation's keyword arguments (looked at by
hand, PR 25): on the CPU and on the v5e they arrive as the event's stats and
the event's name is the bare span name; `split_name` also takes the other
form the profiler knows (`name#key=value,...#`)."""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HARNESS_DIR)
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:  # run as a script: `harness` is this file's own package
    sys.path.insert(0, BENCH_DIR)

from harness import trace_reduce  # noqa: E402

WINDOW_EVENT = trace_reduce.WINDOW_EVENT
HOST_PLANE = "/host:CPU"
UNATTRIBUTED = "unattributed"
GAP_POSITIONS = ("window start -> first device op", "between device ops",
                 "last device op -> window end")
STAGES = ("read", "write", "crc", "dispatch", "sync")


def _is_stage(name: str) -> bool:
    return name.rsplit(".", 1)[-1] in STAGES


def split_name(event_name: str) -> tuple[str, dict]:
    """`encode.read#bytes=65536000#` -> (`encode.read`, {"bytes": "65536000"})."""
    if not event_name.endswith("#") or "#" not in event_name[:-1]:
        return event_name, {}
    name, _, rest = event_name[:-1].partition("#")
    attrs = dict(kv.split("=", 1) for kv in rest.split(",") if "=" in kv)
    return name, attrs


def nest(thread: list) -> list[tuple[str, float, float, int, float]]:
    """One thread's events, which nest and never cross, to
    (name, start, duration, depth, self seconds) in time order: self is the
    duration less what the event's direct children cover."""
    order = sorted(thread, key=lambda e: (e[1], -e[2]))
    out: list[list] = []
    stack: list[list] = []  # open events: [name, start, dur, depth, self]
    for name, start, dur, *_ in order:
        while stack and stack[-1][1] + stack[-1][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            covered = min(start + dur, parent[1] + parent[2]) - start
            parent[4] = max(0.0, parent[4] - max(0.0, covered))
        row = [name, start, dur, len(stack), dur]
        out.append(row)
        stack.append(row)
    return [tuple(r) for r in out]


def by_name(threads: list) -> dict[str, dict]:
    """Per span name over all threads: how many, the union of their intervals
    and the sum of their self seconds."""
    found: dict[str, dict] = {}
    for thread in threads:
        for name, start, dur, _depth, self_s in nest(thread):
            row = found.setdefault(name, {"count": 0, "self_s": 0.0, "intervals": []})
            row["count"] += 1
            row["self_s"] += self_s
            row["intervals"].append((start, start + dur))
    return {name: {"count": r["count"], "union_s": trace_reduce.union_seconds(r["intervals"]),
                   "self_s": r["self_s"]} for name, r in found.items()}


def attribute_gap(gap: tuple[float, float], threads: list) -> dict:
    """One device idle gap, (start, length), shared out among the spans that
    cover it: at each instant the deepest open span has it (the later started
    one where two threads are as deep), and where none is open it is
    `unattributed`. -> {"spans": [[name, seconds], ...] deepest first,
    "unattributed": seconds}; the seconds sum to the gap's length."""
    g0, g1 = gap[0], gap[0] + gap[1]
    open_in_gap = []  # (start, end, depth, name), clipped to the gap
    for thread in threads:
        for name, start, dur, depth, _ in nest(thread):
            s, e = max(start, g0), min(start + dur, g1)
            if e > s:
                open_in_gap.append((s, e, depth, name))
    cuts = sorted({g0, g1, *(t for s, e, _, _ in open_in_gap for t in (s, e))})
    seconds: dict[str, float] = {}
    depth_of: dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        holders = [(depth, s, name) for s, e, depth, name in open_in_gap if s <= a and e >= b]
        name = max(holders)[2] if holders else UNATTRIBUTED
        seconds[name] = seconds.get(name, 0.0) + (b - a)
        if holders:
            depth_of[name] = max(depth_of.get(name, 0), max(holders)[0])
    unattributed = seconds.pop(UNATTRIBUTED, 0.0)
    spans = sorted(seconds.items(), key=lambda kv: (-depth_of[kv[0]], -kv[1]))
    return {"spans": [[n, s] for n, s in spans], "unattributed": unattributed}


def gap_position(gap: tuple[float, float], runs: list[tuple[float, float]], window_s: float) -> str:
    """Where a gap lies among the device's program runs, (start, end) each:
    `run.py`'s three labels, by its rule."""
    first = min((s for s, _ in runs), default=0.0)
    last = max((e for _, e in runs), default=window_s)
    if gap[0] + gap[1] <= first + 1e-6:
        return GAP_POSITIONS[0]
    return GAP_POSITIONS[2] if gap[0] >= last - 1e-6 else GAP_POSITIONS[1]


def gap_label(position: str, attributed: dict) -> str:
    """`between device ops: encode.sync`: the position, then whatever holds
    most of the gap."""
    shares = attributed["spans"] + [[UNATTRIBUTED, attributed["unattributed"]]]
    return f"{position}: {max(shares, key=lambda x: x[1])[0]}"


# -- readers: (facts, **arguments) -> value or None, as harness/reducers.py's ----
# facts["spans"]  one {"threads": [...]} per traced stretch, beside
# facts["traces"] trace_reduce's summaries of the same stretches


def _stretches(facts: dict):
    return [s for s in facts.get("spans") or [] if s.get("threads")]


def span_self_ms(facts: dict, span: str):
    """Self milliseconds of every span of that name in one traced stretch (one
    timed operation: summed over its batches); median over the stretches."""
    found = [by_name(s["threads"]).get(span) for s in _stretches(facts)]
    found = [r["self_s"] for r in found if r]
    return statistics.median(found) * 1e3 if found else None


def first_span_start_ms(facts: dict, span: str):
    """Window start to the start of the first span of that name; median."""
    found = []
    for s in _stretches(facts):
        starts = [e[1] for t in s["threads"] for e in t if e[0] == span]
        if starts:
            found.append(min(starts))
    return statistics.median(found) * 1e3 if found else None


def idle_attributed_pct(facts: dict):
    """Share of the device's idle seconds that some span of the program covers."""
    idle = named = 0.0
    for trace, s in zip(facts.get("traces") or [], facts.get("spans") or []):
        if not s.get("threads"):
            continue
        for chip in trace.get("chips", []):
            for gap in chip["gaps"]:
                idle += gap[1]
                named += gap[1] - attribute_gap(tuple(gap), s["threads"])["unattributed"]
    return 100.0 * named / idle if idle > 0 else None


READERS = {f.__name__: f for f in (span_self_ms, first_span_start_ms, idle_attributed_pct)}


# -- the two comparisons with the device trace ------------------------------------


def cross_check(trace: dict, spans: dict, program: str = "^jit__gf_apply_impl$") -> dict:
    """One stretch: the stages' self seconds, whole and clipped to it, against
    the pipeline phase the device shows (first start to last end of the
    program's runs), and whether one `rpc.server` span encloses every one of
    those runs."""
    pat = re.compile(program)
    runs = [(s, s + d) for chip in trace.get("chips", []) for n, s, d in chip["modules"]
            if pat.search(n)]
    out: dict = {"device_runs": len(runs)}
    if not runs or not spans.get("threads"):
        return out
    first, last = min(s for s, _ in runs), max(e for _, e in runs)
    names = by_name(spans["threads"])
    stages = {n: r["self_s"] for n, r in names.items() if _is_stage(n)}
    # the stages also run before the first device run (the first batch's fill)
    # and after the last (the last drains): what of them lies inside the phase
    phase = attribute_gap((first, last - first), spans["threads"])
    in_phase = sum(s for n, s in phase["spans"] if _is_stage(n))
    out.update(pipeline_phase_s=last - first, stages_self_s=sum(stages.values()),
               stages_in_phase_s=in_phase, stages=stages)
    if last > first:
        out["stages_over_phase"] = out["stages_self_s"] / (last - first)
        out["stages_in_phase_over_phase"] = in_phase / (last - first)
    best = None
    for thread in spans["threads"]:
        for name, start, dur, attrs in thread:
            if name != "rpc.server":
                continue
            n_inside = sum(1 for s, e in runs if s >= start and e <= start + dur)
            if best is None or n_inside > best["runs_inside"]:
                best = {"method": attrs.get("method"), "runs_inside": n_inside,
                        "lead_s": first - start, "tail_s": start + dur - last}
    if best:
        best["encloses_all"] = best["runs_inside"] == len(runs)
        out["rpc_server"] = best
    return out


# -- reading the trace ------------------------------------------------------------


def program_span_names() -> set[str]:
    """The program's closed catalog of span names (it imports nothing of jax)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from seaweedfs_tpu.obs.trace import SPAN_NAMES

    return set(SPAN_NAMES)


def reduce_spans(trace_dir: str, names: set[str] | None = None) -> dict:
    """The host plane's events whose names are the program's span names, by
    thread, relative to `bench.window`'s start (the first span's, where the
    window is missing): {"window_found", "threads": [[[name, start_s,
    duration_s, attrs], ...], ...]}."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"error": f"no .xplane.pb under {trace_dir}", "threads": []}
    names = program_span_names() if names is None else names
    window_ns = None
    threads = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            found = []
            for e in line.events:
                name, attrs = split_name(e.name)
                if name == WINDOW_EVENT and window_ns is None:
                    window_ns = e.start_ns
                if name in names:
                    attrs.update((str(k), v) for k, v in e.stats)
                    found.append((name, e.start_ns, e.duration_ns, attrs))
            if found:
                threads.append(found)
    t0 = window_ns
    if t0 is None:
        t0 = min((e[1] for t in threads for e in t), default=0.0)
    return {"window_found": window_ns is not None,
            "threads": [[[n, (s - t0) / 1e9, d / 1e9, a] for n, s, d, a in sorted(t, key=lambda e: e[1])]
                        for t in threads]}


def report(facts: dict) -> dict:
    """Everything this file computes of the traced stretches, for a person."""
    stretches = []
    for trace, spans in zip(facts["traces"], facts["spans"]):
        gaps = []
        for chip in trace.get("chips", []):
            runs = [(s, s + d) for _, s, d in chip["modules"]]
            for gap in chip["gaps"]:
                a = attribute_gap(tuple(gap), spans["threads"])
                gaps.append({"label": gap_label(gap_position(tuple(gap), runs, chip["window_s"]), a),
                             "start_s": gap[0], "seconds": gap[1], **a})
        stretches.append({
            "spans": by_name(spans["threads"]),
            "idle_gaps": sorted(gaps, key=lambda g: -g["seconds"])[:10],
            "cross_check": cross_check(trace, spans),
        })
    return {"stretches": stretches, "idle_attributed_pct": idle_attributed_pct(facts)}


def parked_metrics(facts: dict, workload: str) -> dict:
    """The span metrics that `parked/span-metrics.json` defines for the cell."""
    with open(os.path.join(BENCH_DIR, "parked", "span-metrics.json")) as f:
        parked = json.load(f)
    out = {}
    for m in parked["per_layer"]:
        if workload in m["workloads"]:
            spec = parked["layer_metrics"][m["name"]]
            value = READERS[spec["reader"]](facts, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list[str]) -> int:
    workload = None
    if argv[:1] == ["--workload"]:
        workload, argv = argv[1], argv[2:]
    names = program_span_names()
    facts = {"traces": [trace_reduce.reduce_trace(d) for d in argv],
             "spans": [reduce_spans(d, names) for d in argv]}
    out = report(facts)
    if workload:
        out["metrics"] = parked_metrics(facts, workload)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
