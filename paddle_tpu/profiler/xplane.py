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
