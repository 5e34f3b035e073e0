"""Device-trace (xplane) parsing for profiler statistics.

The jax.profiler trace directory holds `*.xplane.pb` protos; the TPU
device plane's "XLA Ops" line is ground truth for per-op device time
(a host clock around one async dispatch is not). Read with
`jax.profiler.ProfileData`, so nothing but jax is needed.

Parity: the role of `paddle/fluid/platform/profiler/chrometracing_logger.cc`
+ `python/paddle/profiler/profiler_statistic.py`'s device-side tables.
"""
from __future__ import annotations

import collections
import glob
import os
import re

_HLO_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$')
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_HLO_CALLS = re.compile(r'(?:calls|to_apply|body)=%?([\w.\-]+)')
_HLO_COMPUTATION = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$')
_HLO_NAME = re.compile(r'%([\w.\-]+)')


def hlo_op_names(text):
    """{instruction name: its `op_name`} of an HLO module's text
    (`compiled.as_text()`). A v5e trace names a device event after its
    instruction and carries no `op_name`; the compiled module's
    metadata does, and with it the `jax.named_scope`s the operation was
    traced under."""
    out = {}
    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        name = m and _HLO_OP_NAME.search(m.group(2))
        if name:
            out[m.group(1)] = name.group(1)
    return out


def hlo_op_scopes(text, scope_of, none):
    """{instruction name: scope} of an HLO module's text: `scope_of(its
    op_name)` (`none` where that names no scope). An instruction the
    COMPILER made (a layout copy, a rewritten reduction: no `op_name`
    at all, so no `jax.named_scope` could reach it) takes the first
    scope among the instructions of the computation it calls, else of
    the first scoped instruction that uses it: it exists for their
    sake. One JAX emitted outside every scope stays `none`."""
    scopes, made, calls, inside, users = {}, [], {}, {}, {}
    computation = None
    for line in text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        inside.setdefault(computation, []).append(name)
        op_name = _HLO_OP_NAME.search(rest)
        scopes[name] = scope_of(op_name.group(1)) if op_name else none
        if not op_name:
            made.append(name)
        called = _HLO_CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        for operand in _HLO_NAME.findall(rest.split(", metadata=")[0]):
            users.setdefault(operand, []).append(name)
    for _ in range(4):              # through a short chain of such
        left = []
        for name in made:
            near = inside.get(calls.get(name), []) + users.get(name, [])
            scope = next((scopes[n] for n in near
                          if scopes.get(n, none) != none), none)
            if scope == none:
                left.append(name)
            scopes[name] = scope
        if len(left) == len(made):
            break
        made = left
    return scopes


def load_xplane(trace_dir):
    """The newest `.xplane.pb` under `trace_dir` as a `ProfileData`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def device_op_stats(data):
    """{hlo_op_name: [total_ns, calls]} over the TPU device planes'
    XLA Ops lines. Times are inclusive: a `while` spans its body's ops
    and counts them again."""
    out = collections.defaultdict(lambda: [0, 0])
    for plane in data.planes:
        if "TPU" not in plane.name and "/device:" not in plane.name:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                slot = out[ev.name]
                slot[0] += int(ev.duration_ns)
                slot[1] += 1
    return dict(out)


def device_op_times(data):
    """{hlo_op_name: total_ns}, a Counter (`most_common` sorts it)."""
    return collections.Counter(
        {name: ns for name, (ns, _) in device_op_stats(data).items()})


def device_op_table(trace_dir, top_k=30, n_steps=1):
    """[(name, ms per step, calls)] for the newest trace under
    trace_dir: total time / n_steps, and how often the op ran in all."""
    stats = device_op_stats(load_xplane(trace_dir))
    rows = sorted(stats.items(), key=lambda kv: -kv[1][0])[:top_k]
    return [(name, ns / 1e6 / n_steps, calls)
            for name, (ns, calls) in rows]
