"""Parse a jax.profiler xplane trace: aggregate TPU device-plane op time.

Usage: python tools/parse_xplane.py <trace_dir> [n_steps] [top_k]

Finds the newest .xplane.pb under <trace_dir>, sums duration by HLO op
name on the TPU device plane's "XLA Ops" line, and prints a per-step
table (total / n_steps).  The device trace is the ground truth for
per-kernel time; a host clock around one async dispatch is not.
The trace is read with `jax.profiler.ProfileData`: nothing but jax.
"""
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from paddle_tpu.profiler.xplane import (  # noqa: E402,F401
    load_xplane, device_op_times)


def bucket(name):
    """Group HLO op names into readable classes."""
    n = name.lower()
    for pat, label in (
            (r"splash|flash", "splash attention"),
            (r"fusion.*softmax|softmax", "softmax fusion"),
            (r"convolution|conv", "conv/matmul (convolution hlo)"),
            (r"dot", "matmul (dot)"),
            (r"all-reduce|all-gather|reduce-scatter|collective",
             "collectives"),
            (r"dynamic-update-slice", "dynamic-update-slice"),
            (r"copy|transpose|bitcast", "copy/transpose"),
            (r"scatter", "scatter"),
            (r"gather", "gather"),
            (r"reduce", "reduce fusion"),
            (r"fusion", "other fusion"),
    ):
        if re.search(pat, n):
            return label
    return "other"


def main():
    trace_dir = sys.argv[1]
    n_steps = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    top_k = int(sys.argv[3]) if len(sys.argv) > 3 else 40
    xs = load_xplane(trace_dir)
    times = device_op_times(xs)
    total = sum(times.values())
    print(f"device total: {total / 1e6 / n_steps:.2f} ms/step "
          f"({len(times)} distinct ops)")
    print("\n-- by bucket --")
    buckets = collections.Counter()
    for name, ns in times.items():
        buckets[bucket(name)] += ns
    for b, ns in buckets.most_common():
        print(f"{ns / 1e6 / n_steps:9.2f} ms  {100 * ns / total:5.1f}%  {b}")
    print(f"\n-- top {top_k} ops --")
    for name, ns in times.most_common(top_k):
        print(f"{ns / 1e6 / n_steps:9.2f} ms  {100 * ns / total:5.1f}%  "
              f"{name[:110]}")


if __name__ == "__main__":
    main()
