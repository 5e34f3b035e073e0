"""Profiler statistics/reporting tables.

Parity: `python/paddle/profiler/profiler_statistic.py:1` (SortedKeys,
the Overview / Operator Summary tables printed by `Profiler.summary`)
— built from the host-event recorder plus (optionally) the device
xplane trace, whose per-op times are the ground truth for device time.
"""
from __future__ import annotations

import collections
from enum import Enum


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4     # name parity; device == TPU here
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


_SORT_FIELD = {
    SortedKeys.CPUTotal: "total", SortedKeys.GPUTotal: "total",
    SortedKeys.CPUAvg: "avg", SortedKeys.GPUAvg: "avg",
    SortedKeys.CPUMax: "max", SortedKeys.GPUMax: "max",
    SortedKeys.CPUMin: "min", SortedKeys.GPUMin: "min",
}


def _aggregate(events):
    """events: [{name, dur(us), ...}] -> {name: stats dict}."""
    by_name = {}
    for e in events:
        st = by_name.setdefault(e["name"], {
            "calls": 0, "total": 0.0, "max": 0.0, "min": float("inf")})
        d = e["dur"] / 1e3  # us -> ms
        st["calls"] += 1
        st["total"] += d
        st["max"] = max(st["max"], d)
        st["min"] = min(st["min"], d)
    for st in by_name.values():
        st["avg"] = st["total"] / max(st["calls"], 1)
    return by_name


def _table(title, headers, rows, widths):
    sep = "-" * (sum(widths) + len(widths) * 2)
    lines = [sep, title, sep,
             "  ".join(h.ljust(w) if i == 0 else h.rjust(w)
                       for i, (h, w) in enumerate(zip(headers, widths)))]
    for row in rows:
        lines.append("  ".join(
            str(c)[:widths[0]].ljust(widths[0]) if i == 0
            else str(c).rjust(w)
            for i, (c, w) in enumerate(zip(row, widths))))
    lines.append(sep)
    return "\n".join(lines)


def host_statistic_table(events, sorted_by=SortedKeys.CPUTotal,
                         time_unit="ms", top_k=0):
    """The Operator-Summary-style table over recorded host spans."""
    stats = _aggregate(events)
    field = _SORT_FIELD.get(sorted_by, "total")
    items = sorted(stats.items(), key=lambda kv: -kv[1][field])
    if top_k:
        items = items[:top_k]
    gtotal = sum(st["total"] for _, st in stats.items()) or 1.0
    rows = [(name, st["calls"], f"{st['total']:.3f}",
             f"{st['avg']:.3f}", f"{st['max']:.3f}",
             f"{st['min'] if st['min'] != float('inf') else 0:.3f}",
             f"{100 * st['total'] / gtotal:.2f}%")
            for name, st in items]
    return _table(
        f"Host Event Summary (sorted by {field}, {time_unit})",
        ["Name", "Calls", "Total", "Avg", "Max", "Min", "Ratio"],
        rows, [44, 7, 11, 9, 9, 9, 8])


def device_statistic_table(trace_dir, top_k=30, n_steps=1):
    """Device-op table from the newest xplane trace under trace_dir."""
    from .xplane import load_xplane, device_op_times
    times = device_op_times(load_xplane(trace_dir))
    total = sum(times.values()) or 1
    rows = []
    for name, ns in times.most_common(top_k):
        short = name.split(" = ")[0].lstrip("%")
        rows.append((short, f"{ns / 1e6 / n_steps:.3f}",
                     f"{100 * ns / total:.2f}%"))
    return _table(
        f"Device (TPU) Op Summary — {sum(times.values()) / 1e6 / n_steps:.2f}"
        f" ms/step over {len(times)} ops",
        ["HLO op", "ms", "Ratio"], rows, [64, 11, 8])


def statistic_report(events, trace_dir=None, sorted_by=SortedKeys.CPUTotal,
                     top_k=30, n_steps=1):
    """Full report: host table + device table when a trace exists."""
    parts = [host_statistic_table(events, sorted_by, top_k=top_k)]
    if trace_dir is not None:
        try:
            parts.append(device_statistic_table(trace_dir, top_k=top_k,
                                                n_steps=n_steps))
        except Exception as e:  # no trace captured (CPU test mesh)
            parts.append(f"(no device trace: {e})")
    return "\n\n".join(parts)
