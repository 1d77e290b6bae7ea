"""Reduction of a profiler trace (`*.xplane.pb`) to what the per-layer metrics
read. Run as a short-lived child pinned to JAX_PLATFORMS=cpu after the server
has left (it imports jax only for `jax.profiler.ProfileData`):

  python benchmark/harness/trace_reduce.py <trace_dir> [<trace_dir> ...]

prints one JSON object: a list with one summary per trace directory. The pure
functions below (`union_seconds`, `gaps`, `summarize_events`) take plain lists
and are what `benchmark/tests/` checks on a small recorded trace.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Modules` has one event per run of a compiled
program (named `jit_<function>(<fingerprint>)`) and whose line `XLA Ops` has
one event per HLO operation inside it; host threads are lines of `/host:CPU`.
Times are nanoseconds on one clock for all planes. The window is the
`bench.window` annotation that `chip_server.py` holds open between start and
stop; where it is missing, the extent of all device events."""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW_EVENT = "bench.window"
TOP = 10


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list[tuple[float, float]], window: tuple[float, float]) -> list[tuple[float, float]]:
    """The idle stretches of `window` not covered by any interval, as
    (start, length), in time order."""
    out, at = [], window[0]
    for s, e in sorted(intervals):
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        if s > at:
            out.append((at, s - at))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1] - at))
    return out


def program_name(event_name: str) -> str:
    """`jit_encode(1234567890)` -> `jit_encode`: stable across compiles."""
    return event_name.split("(")[0]


def summarize_events(modules: list[tuple[str, float, float]], ops: list[tuple[str, float, float]],
                     window: tuple[float, float] | None) -> dict:
    """One chip's events, (name, start_s, duration_s) each, to a summary whose
    times are relative to the window's start."""
    busy_src = ops or modules
    if window is None:
        if not busy_src:
            return {"window_s": 0.0, "busy_s": 0.0, "modules": [], "top_ops": [], "gaps": []}
        window = (min(s for _, s, _ in busy_src), max(s + d for _, s, d in busy_src))
    w0, w1 = window
    inside = [(max(s, w0), min(s + d, w1)) for _, s, d in busy_src if s + d > w0 and s < w1]
    by_op: dict[str, float] = {}
    for name, s, d in busy_src:
        if s + d > w0 and s < w1:
            by_op[name] = by_op.get(name, 0.0) + d
    return {
        "window_s": w1 - w0,
        "busy_s": union_seconds(inside),
        "modules": [[program_name(n), s - w0, d] for n, s, d in sorted(modules, key=lambda m: m[1])
                    if s + d > w0 and s < w1],
        "top_ops": sorted(([n, t] for n, t in by_op.items()), key=lambda x: -x[1])[:TOP],
        "gaps": [[s - w0, d] for s, d in gaps(inside, window)],
    }


def reduce_trace(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"error": f"no .xplane.pb under {trace_dir}", "chips": []}
    pd = ProfileData.from_file(paths[-1])
    window = None
    chips = {}
    plane_names = []
    for plane in pd.planes:
        plane_names.append(plane.name)
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is None:
                if window is None:
                    for e in line.events:
                        if e.name == WINDOW_EVENT:
                            window = (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                            break
                continue
            chip = chips.setdefault(int(m.group(1)), {"modules": [], "ops": [], "lines": []})
            chip["lines"].append(line.name)
            if line.name == MODULE_LINE:
                chip["modules"] = [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9) for e in line.events]
            elif line.name == OP_LINE:
                chip["ops"] = [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9) for e in line.events]
    out = {"planes": plane_names, "xplane_bytes": os.path.getsize(paths[-1]),
           "window_found": window is not None, "chips": []}
    for n in sorted(chips):
        s = summarize_events(chips[n]["modules"], chips[n]["ops"], window)
        s["chip"] = n
        s["lines"] = chips[n]["lines"]
        out["chips"].append(s)
    return out


def main(argv: list[str]) -> int:
    print(json.dumps([reduce_trace(d) for d in argv]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
