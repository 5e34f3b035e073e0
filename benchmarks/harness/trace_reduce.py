"""From a profiler trace to numbers: device busy union, idle share,
time by operation name, and idle gaps named by what the host was doing.

Two halves. `read_profile` turns an `.xplane.pb` (read with
`jax.profiler.ProfileData`, nothing but jax) into plain lists of
`(name, start_ns, duration_ns)`. `reduce_events` is pure arithmetic on
such lists, so `tests/test_trace_reduce.py` checks it on a hand-made
trace with known answers.

What a v5e trace looks like (first traces of PR 24, read by hand): one
plane per chip, `/device:TPU:<n>`, whose line `XLA Ops` holds one event
per executed HLO op or Mosaic kernel (nested: a `while` spans the ops
of its body) and whose line `XLA Modules` holds one event per executed
program, named `jit_<name>(<fingerprint>)`. Host threads are lines of
`/host:CPU`; `jax.profiler.TraceAnnotation`s appear there under their
own names. All planes share one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEFAULT_GAP = "host_between_dispatches"


@dataclasses.dataclass
class Reduced:
    chips: int
    window_s: float            # first device op start -> last op end
    busy_s: float              # union of op intervals, mean over chips
    ops: dict                  # name -> [self seconds, calls], all chips
    modules: dict              # name -> [seconds, calls]
    gaps: list                 # (start_s, seconds, label), longest first

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def seconds_of(self, fragment):
        """Total self time of the ops whose name contains `fragment`,
        per chip."""
        return sum(v[0] for k, v in self.ops.items()
                   if fragment in k) / max(self.chips, 1)

    def calls_of(self, fragment, table="ops"):
        return sum(v[1] for k, v in getattr(self, table).items()
                   if fragment in k) / max(self.chips, 1)

    def idle_by_label(self):
        out = {}
        for _, dur, label in self.gaps:
            out[label] = out.get(label, 0.0) + dur
        return out

    def breakdown(self, top=10):
        """The result line's `breakdown`: the device operations that
        took most time, and idle time by what the host was doing."""
        ops = sorted(((k, v[0] / max(self.chips, 1))
                      for k, v in self.ops.items()),
                     key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_label().items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, s] for k, s in ops],
                "idle_gaps": [[k, s / max(self.chips, 1)]
                              for k, s in idle]}


def op_name(text):
    """The name of a device event. On the chip an event of `XLA Ops` is
    named by its whole HLO line, `%fusion.227 = bf16[64,3,16,128]{...}
    fusion(...)`: keep `fusion.227`, the instruction's name, which is
    one place in the program (a scanned layer's op runs once per layer
    under one name). A Mosaic kernel is `<kernel name>.<n>`."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _union(intervals):
    """Total length of the union of (start, end) intervals."""
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


def _gaps(intervals):
    """(start, end) of every hole in the union of the intervals."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def _self_times(events):
    """(name, self_ns) per event: its duration less what events nested
    inside it on the same line cover (a `while` keeps only what its
    body's ops leave)."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = [[ev[0], float(ev[2])] for ev in order]
    stack = []      # indices into order of the open ancestors
    for i, (_, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] \
                <= start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= dur
        stack.append(i)
    return [(n, max(s, 0.0)) for n, s in out]


def reduce_events(device, host=(), labels=(), default_gap=DEFAULT_GAP,
                  min_gap_ns=0.0):
    """`device`: {plane: {"ops": [(name, start_ns, dur_ns)],
    "modules": [...]}} for the chips used. `host`: annotation events
    `(name, start_ns, dur_ns)`; a gap takes the name of the annotation
    in `labels` that overlaps it most, and `default_gap` where none
    does."""
    chips = len(device)
    ops, modules, gaps = {}, {}, []
    busy = window = 0.0
    notes = [(n, s, s + d) for n, s, d in host if n in set(labels)]
    for plane in device.values():
        evs = plane.get("ops", [])
        if not evs:
            continue
        spans = [(s, s + d) for _, s, d in evs]
        t0 = min(s for s, _ in spans)
        t1 = max(e for _, e in spans)
        window += (t1 - t0) / 1e9
        busy += _union(spans) / 1e9
        for name, self_ns in _self_times(evs):
            slot = ops.setdefault(op_name(name), [0.0, 0])
            slot[0] += self_ns / 1e9
            slot[1] += 1
        for name, _, dur in plane.get("modules", []):
            slot = modules.setdefault(name.split("(")[0], [0.0, 0])
            slot[0] += dur / 1e9
            slot[1] += 1
        for gs, ge in _gaps(spans):
            if ge - gs < min_gap_ns:
                continue
            best, best_overlap = default_gap, 0.0
            for name, ns, ne in notes:
                overlap = min(ge, ne) - max(gs, ns)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
            gaps.append((gs / 1e9, (ge - gs) / 1e9, best))
    gaps.sort(key=lambda g: -g[1])
    n = max(chips, 1)
    return Reduced(chips=chips, window_s=window / n, busy_s=busy / n,
                   ops=ops, modules=modules, gaps=gaps)


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_profile(path, rehearse=False):
    """(device, host) event lists of an `.xplane.pb`.

    On the chip the device planes are `/device:TPU:<n>`. A CPU
    rehearsal has none: there the events of `/host:CPU` that carry an
    `hlo_op` stat stand in for device ops, so that the reduction's
    code path runs — its numbers are not device numbers."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    key = "ops" if line.name == OPS_LINE else "modules"
                    lines[key] = [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events]
            if lines.get("ops"):
                device[plane.name] = lines
        elif plane.name == "/host:CPU":
            fake = {"ops": [], "modules": []}
            for line in plane.lines:
                for e in line.events:
                    if rehearse and any(
                            k == "hlo_op" for k, _ in e.stats):
                        fake["ops"].append(
                            (e.name, e.start_ns, e.duration_ns))
                    else:
                        host.append((e.name, e.start_ns, e.duration_ns))
            if rehearse and fake["ops"]:
                device["/host:CPU(rehearsal)"] = fake
    return device, host
