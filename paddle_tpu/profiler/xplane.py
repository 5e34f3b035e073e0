"""Device-trace (xplane) parsing for profiler statistics.

The jax.profiler trace directory holds `*.xplane.pb` protos; the TPU
device plane's "XLA Ops" line is ground truth for per-op device time
(a host clock around one async dispatch is not). Requires the
pure-python protobuf runtime for the xplane descriptor (set
automatically).

Parity: the role of `paddle/fluid/platform/profiler/chrometracing_logger.cc`
+ `python/paddle/profiler/profiler_statistic.py`'s device-side tables.
"""
from __future__ import annotations

import collections
import glob
import os


def load_xplane(trace_dir):
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                          "python")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def device_op_times(xs):
    """{hlo_op_name: total_ns} over TPU device planes' XLA Ops lines."""
    out = collections.Counter()
    for plane in xs.planes:
        if "TPU" not in plane.name and "/device:" not in plane.name:
            continue
        ev_meta = plane.event_metadata
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                out[ev_meta[ev.metadata_id].name] += \
                    ev.duration_ps // 1000
    return out


def device_op_table(trace_dir, top_k=30, n_steps=1):
    """[(name, total_ms, calls)] for the newest trace under trace_dir."""
    times = device_op_times(load_xplane(trace_dir))
    rows = [(name, ns / 1e6 / n_steps, 1)
            for name, ns in times.most_common(top_k)]
    return rows
