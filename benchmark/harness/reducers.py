"""The readers of per-layer metrics. A file `layer_metrics/<metric>.json` names
one of these with its arguments; a reader that finds nothing to read returns
None and the harness leaves the metric out of the line.

Every reader takes (`facts`, **arguments). `facts` holds what a run gathered:
  facts["setup"]    values measured in set-up, by name
  facts["samples"]  client-clock samples by class: {class: [seconds, ...]}
  facts["traces"]   trace_reduce's summaries, one per traced stretch
  facts["traced"]   what the driver knows of each traced stretch (bytes moved)
  facts["device_kind"]
"""

from __future__ import annotations

import re
import statistics

from harness import peaks


def setup_value(facts: dict, key: str):
    return facts["setup"].get(key)


def class_p50_ms(facts: dict, read_class: str):
    xs = facts["samples"].get(read_class) or []
    return statistics.median(xs) * 1e3 if xs else None


def _chips(facts: dict):
    for t, known in zip(facts["traces"], facts["traced"]):
        for chip in t.get("chips", []):
            yield chip, known


def idle_pct(facts: dict):
    window = sum(c["window_s"] for c, _ in _chips(facts))
    busy = sum(c["busy_s"] for c, _ in _chips(facts))
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)


def program_gap_median_ms(facts: dict, program: str):
    """Median gap between the end of one run of the program and the start of
    the next, inside one traced stretch."""
    pat = re.compile(program)
    found = []
    for chip, _ in _chips(facts):
        runs = [(s, d) for n, s, d in chip["modules"] if pat.search(n)]
        found += [b[0] - (a[0] + a[1]) for a, b in zip(runs, runs[1:])]
    return statistics.median(found) * 1e3 if found else None


def program_roofline_pct(facts: dict, program: str, rows_in: int, rows_out: int):
    """Least HBM time for the bytes the traced stretches had to move (from
    shapes the driver knows: `width` columns of rows_in + rows_out rows) over
    the device time of the program's runs."""
    pat = re.compile(program)
    least = spent = 0.0
    for chip, known in _chips(facts):
        t = sum(d for n, _, d in chip["modules"] if pat.search(n))
        if t <= 0 or not known.get("width"):
            continue
        spent += t
        least += peaks.rs_apply_min_seconds(facts["device_kind"], rows_in, rows_out, known["width"])
    return 100.0 * least / spent if spent > 0 else None


READERS = {f.__name__: f for f in
           (setup_value, class_p50_ms, idle_pct, program_gap_median_ms, program_roofline_pct)}
