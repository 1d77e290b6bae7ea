"""Reduction of ONE COMMAND as its own process saw it: the shell child's half of
a traced stretch, beside the server's half and the device's, from the same
`.xplane.pb`.

A `shell -c` child hands its finished `shell.script` trace to the master when it
ends (`ReportTrace`, PR 42). The benchmark's chip server is master and volume
server in one process and mirrors its spans into the profiler, so the receipt
leaves ONE short annotation, `shell.trace`, whose attributes carry the child's
tree flat (`names`, `what`, `t_ns` after `birth_unix_ns`, `dur_ns`, `depth`,
`thread`, `start_ms`); every mirrored root (`rpc.server`) carries `unix_ns`, the
wall clock at its start, and `trace_id`. One root therefore gives the offset
between the profiler's clock and the wall clock, and the child's spans map onto
the clock of `XLA Modules` / `XLA Ops`. Nothing else is asked of anybody:

  python benchmark/harness/command_reduce.py [--workload <cell>] <trace_dir> [...]

prints one JSON object: per traced stretch the command's timeline from its birth
(start: interpreter / imports / connect; each command; the plan and its RPCs; every
RPC the command's thread waited for), the first dispatch and the last sync on the
server, each EC RPC's self time, the clock check, and, with `--workload`, the
values of the metrics that `parked/command-metrics.json` defines for that cell.
Keep the raw trace with `run.py --trace 1 --keep-trace` and point this at
`chiprun_out/benchmark/<cell>/seed<n>-trace1/trace*`.

The pure functions take plain lists, as `span_reduce.py`'s do (an event is
`(name, start_s, duration_s, attrs)`, seconds after `bench.window`'s start; a
thread is a list of events; a stretch has a list of threads), and are what
`benchmark/tests/test_command_reduce.py` checks on hand-made lists and on a small
recorded trace. A trace without a `shell.trace` annotation (the parent commit's, a
`--trace 1` run before PR 42) gives no command, and every reader returns None.

The clock check is part of the reading: after the mapping every `rpc.server` root
of the command's id has to lie inside the `rpc.client` span of the same method;
`clock_check` reports the largest violation in microseconds."""

from __future__ import annotations

import json
import os
import statistics
import sys

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HARNESS_DIR)
if BENCH_DIR not in sys.path:  # run as a script: `harness` is this file's own package
    sys.path.insert(0, BENCH_DIR)

from harness import span_reduce, trace_reduce  # noqa: E402

RECEIPT = "shell.trace"
SCRIPT = "shell.script"
#: the RPCs whose server side must name what it does (ISSUE 42): self time of
#: their `rpc.server` roots is reported by `rpc_self_times`
EC_RPCS = ("VolumeEcShardsGenerate", "VolumeEcShardsGenerateBatch", "VolumeEcShardsRebuild",
           "VolumeEcShardsRebuildBatch", "VolumeEcShardsMount", "VolumeEcShardsDelete", "VolumeDelete")


def _ints(joined) -> list[int]:
    return [int(x) for x in str(joined).split(";")] if str(joined) != "" else []


def _events(stretch: dict):
    for thread in stretch.get("threads") or []:
        yield from thread


def clock_offset(stretch: dict, base_ns: int):
    """Seconds to add to `(unix_ns - base_ns) / 1e9` to land on the stretch's
    own clock (seconds after `bench.window`): the median over the mirrored
    roots, each of which says the wall clock at its start. None without one."""
    found = [start - (int(attrs["unix_ns"]) - base_ns) / 1e9
             for _name, start, _dur, attrs in _events(stretch) if "unix_ns" in attrs]
    return statistics.median(found) if found else None


def commands(stretch: dict) -> list[dict]:
    """Every `shell.trace` receipt of a stretch as the command it carries:
    {"trace_id", "birth_s", "start_ms": [interp, import, connect], "spans":
    [(name, what, start_s, duration_s, depth, thread), ...] pre-order}, times on
    the stretch's clock. A receipt whose lists disagree in length, or a stretch
    without a mirrored root to take the clock from, gives nothing."""
    out = []
    for name, _start, _dur, attrs in _events(stretch):
        if name != RECEIPT or "names" not in attrs:
            continue
        names, what = str(attrs["names"]).split(";"), str(attrs.get("what", "")).split(";")
        t_ns, dur_ns = _ints(attrs.get("t_ns", "")), _ints(attrs.get("dur_ns", ""))
        depth, thread = _ints(attrs.get("depth", "")), _ints(attrs.get("thread", ""))
        if len({len(names), len(what), len(t_ns), len(dur_ns), len(depth), len(thread)}) != 1:
            continue
        birth_ns = int(attrs["birth_unix_ns"])
        offset = clock_offset(stretch, birth_ns)
        if offset is None:
            continue
        out.append({
            "trace_id": str(attrs.get("trace_id", "")),
            "birth_s": offset,
            "start_ms": [float(x) for x in str(attrs.get("start_ms", "")).split(";") if x],
            "spans": [(n, w, offset + t / 1e9, d / 1e9, dp, th)
                      for n, w, t, d, dp, th in zip(names, what, t_ns, dur_ns, depth, thread)],
        })
    return sorted(out, key=lambda c: c["birth_s"])


def the_command(stretch: dict, command: str):
    """(script, the `shell.command` span of `command`) of the first script of
    the stretch that ran it, or None."""
    for script in commands(stretch):
        for span in script["spans"]:
            if span[0] == "shell.command" and span[1] == command:
                return script, span
    return None


def _under(script: dict, parent: tuple) -> list[tuple]:
    """The spans of a script that nest under `parent` (pre-order: what follows
    it, deeper, until the next span as shallow)."""
    spans = script["spans"]
    at = spans.index(parent)
    out = []
    for span in spans[at + 1:]:
        if span[4] <= parent[4]:
            break
        out.append(span)
    return out


def child_threads(script: dict) -> list[list]:
    """A script's spans as threads of events, for `span_reduce`'s functions."""
    by_thread: dict[int, list] = {}
    for name, what, start, dur, _depth, thread in script["spans"]:
        by_thread.setdefault(thread, []).append((name, start, dur, {"what": what}))
    return [sorted(t, key=lambda e: e[1]) for _, t in sorted(by_thread.items())]


def clock_check(stretch: dict, script: dict) -> dict:
    """After the mapping every mirrored `rpc.server` root of the script's id
    must lie inside an `rpc.client` span of the same method: {"roots", "matched",
    "largest_violation_us", "no_client"}: by how much the worst root sticks
    out of the client span that fits it best, and the methods of the roots no
    span of the script sent (a server's own calls under the script's id: a
    rebuilder asking the master where a volume's shards are)."""
    clients: dict[str, list] = {}
    for name, what, start, dur, _depth, _thread in script["spans"]:
        if name == "rpc.client":
            clients.setdefault(what, []).append((start, start + dur))
    roots = matched = 0
    worst = 0.0
    no_client: set[str] = set()
    for name, start, dur, attrs in _events(stretch):
        if name != "rpc.server" or "unix_ns" not in attrs or str(attrs.get("trace_id", "")) != script["trace_id"]:
            continue
        roots += 1
        fits = [max(a - start, (start + dur) - b, 0.0) for a, b in clients.get(str(attrs.get("method")), [])]
        if fits:
            matched += 1
            worst = max(worst, min(fits))
        else:
            no_client.add(str(attrs.get("method")))
    return {"roots": roots, "matched": matched, "largest_violation_us": worst * 1e6, "no_client": sorted(no_client)}


def rpc_self_times(stretch: dict, methods=EC_RPCS) -> list[dict]:
    """Per `rpc.server` root of the named methods: its wall and what of it no
    span under it names (self time, on its own thread), in milliseconds."""
    out = []
    for thread in stretch.get("threads") or []:
        by_start = {(e[0], e[1]): e[3] for e in thread}
        for name, start, dur, depth, self_s in span_reduce.nest(thread):
            method = by_start[(name, start)].get("method")
            if name == "rpc.server" and depth == 0 and method in methods:
                out.append({"method": method, "wall_ms": dur * 1e3, "self_ms": self_s * 1e3,
                            "self_pct": 100.0 * self_s / dur if dur > 0 else 0.0})
    return sorted(out, key=lambda r: -r["self_ms"])


# -- readers: (facts, **arguments) -> value or None, as harness/reducers.py's ----
# facts["spans"]   span_reduce.reduce_spans' stretches (PR 25's edit fills it)
# facts["traces"]  trace_reduce's summaries of the same stretches


def _found(facts: dict, command: str):
    for i, stretch in enumerate(facts.get("spans") or []):
        got = the_command(stretch, command) if stretch.get("threads") else None
        if got is not None:
            yield i, stretch, got[0], got[1]


def _median_ms(values: list):
    return statistics.median(values) * 1e3 if values else None


def command_span_ms(facts: dict, command: str, span: str):
    """Duration of the span `span` of the traced command's script: `shell.start`
    (under the script) or a span under the `shell.command` of `command`
    (`shell.plan`); median over the traced commands."""
    found = []
    for _i, _stretch, script, cmd in _found(facts, command):
        pool = script["spans"] if span == "shell.start" else _under(script, cmd)
        got = [s[3] for s in pool if s[0] == span]
        if got:
            found.append(sum(got))
    return _median_ms(found)


def command_head_ms(facts: dict, command: str, dispatch: str):
    """Birth of the child to the start of the first `dispatch` span on the
    server after it: the spans' reading of `window_start -> first_device_op`."""
    found = []
    for _i, stretch, script, _cmd in _found(facts, command):
        starts = [e[1] for e in _events(stretch) if e[0] == dispatch and e[1] >= script["birth_s"]]
        if starts:
            found.append(min(starts) - script["birth_s"])
    return _median_ms(found)


def command_tail_ms(facts: dict, command: str, sync: str):
    """End of the last `sync` span on the server inside the script's life to the
    end of `shell.script`."""
    found = []
    for _i, stretch, script, _cmd in _found(facts, command):
        root = script["spans"][0]
        end = root[2] + root[3]
        ends = [e[1] + e[2] for e in _events(stretch) if e[0] == sync and script["birth_s"] <= e[1] + e[2] <= end]
        if ends:
            found.append(end - max(ends))
    return _median_ms(found)


def _is_root(event) -> bool:
    return event[0] in (SCRIPT, RECEIPT) or "unix_ns" in event[3]


def head_attributed_pct(facts: dict, command: str):
    """Share of the device's idle gap before its first operation that lies under
    some span of the server or of the child other than a root's own time (a
    root says that a process was busy, not with what)."""
    idle = named = 0.0
    for i, stretch, script, _cmd in _found(facts, command):
        traces = facts.get("traces") or []
        if i >= len(traces):
            continue
        threads = [[e for e in t if not _is_root(e)] for t in (stretch["threads"] + child_threads(script))]
        for chip in traces[i].get("chips", []):
            runs = [(s, s + d) for _, s, d in chip["modules"]]
            for gap in chip["gaps"]:
                if span_reduce.gap_position(tuple(gap), runs, chip["window_s"]) != span_reduce.GAP_POSITIONS[0]:
                    continue
                idle += gap[1]
                named += gap[1] - span_reduce.attribute_gap(tuple(gap), threads)["unattributed"]
    return 100.0 * named / idle if idle > 0 else None


READERS = {f.__name__: f for f in (command_span_ms, command_head_ms, command_tail_ms, head_attributed_pct)}


# -- for a person -----------------------------------------------------------------


def timeline(script: dict, max_depth: int = 3) -> list[dict]:
    """A script's spans down to `max_depth`, milliseconds after its birth."""
    return [{"name": n, "what": w, "t_ms": (s - script["birth_s"]) * 1e3, "dur_ms": d * 1e3,
             "depth": dp, "thread": th}
            for n, w, s, d, dp, th in script["spans"] if dp <= max_depth]


def report(facts: dict) -> dict:
    stretches = []
    for i, stretch in enumerate(facts["spans"]):
        scripts = commands(stretch) if stretch.get("threads") else []
        row: dict = {"scripts": []}
        for script in scripts:
            root = script["spans"][0]
            end = root[2] + root[3]
            first = {n: min((e[1] for e in _events(stretch) if e[0] == n and e[1] >= script["birth_s"]), default=None)
                     for n in ("encode.dispatch", "rebuild.dispatch")}
            last = {n: max((e[1] + e[2] for e in _events(stretch)
                            if e[0] == n and script["birth_s"] <= e[1] + e[2] <= end), default=None)
                    for n in ("encode.sync", "rebuild.sync")}
            row["scripts"].append({
                "trace_id": script["trace_id"], "birth_s": script["birth_s"], "wall_ms": root[3] * 1e3,
                "start_ms": script["start_ms"], "timeline": timeline(script),
                "first_dispatch_ms": {n: (t - script["birth_s"]) * 1e3 for n, t in first.items() if t is not None},
                "last_sync_to_end_ms": {n: (end - t) * 1e3 for n, t in last.items() if t is not None},
                "clock_check": clock_check(stretch, script),
            })
        row["rpc_self_times"] = rpc_self_times(stretch) if stretch.get("threads") else []
        if i < len(facts["traces"]):
            row["first_device_op_s"] = [min((s for _, s, _ in chip["modules"]), default=None)
                                        for chip in facts["traces"][i].get("chips", [])]
        stretches.append(row)
    return {"stretches": stretches}


def parked_metrics(facts: dict, workload: str) -> dict:
    """The command metrics that `parked/command-metrics.json` defines for the cell."""
    with open(os.path.join(BENCH_DIR, "parked", "command-metrics.json")) as f:
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
    names = span_reduce.program_span_names()
    facts = {"traces": [trace_reduce.reduce_trace(d) for d in argv],
             "spans": [span_reduce.reduce_spans(d, names) for d in argv]}
    out = report(facts)
    if workload:
        out["metrics"] = parked_metrics(facts, workload)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
