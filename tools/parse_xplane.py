"""Parse a jax.profiler xplane trace: aggregate TPU device-plane op time.

Usage: python tools/parse_xplane.py <trace_dir> [n_steps] [top_k]
       python tools/parse_xplane.py <trace_dir> --by-scope SCOPE[,SCOPE...]
                                    [--hlo compiled_step.txt]

Finds the newest .xplane.pb under <trace_dir>, sums duration by HLO op
name on the TPU device plane's "XLA Ops" line, and prints a per-step
table (total / n_steps).  The device trace is the ground truth for
per-kernel time; a host clock around one async dispatch is not.
The trace is read with `jax.profiler.ProfileData`: nothing but jax.

`--by-scope` sums SELF time (a `while` keeps what its body's ops leave)
by the `jax.named_scope` an op was traced under: an event goes to the
first of the given scopes that its instruction's `op_name` contains,
else to `(none)`; per execution of the program that ran most often on
the device. A v5e trace's events carry no `op_name` (their stats are
offsets and durations): `--hlo` names the program's compiled HLO text
(`jitted.trace(*args).lower().compile().as_text()`, made by the same
code at the same shapes), whose `metadata={op_name="..."}` of the
instruction an event is named after is read instead; without it only
an event's own name is searched (a kernel's).
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


def hlo_op_names(path):
    """{instruction name: its `op_name`} of an HLO module's text."""
    line = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*'
                      r'metadata=\{[^}]*op_name="([^"]*)"')
    with open(path) as f:
        return dict(m.groups() for m in map(line.match, f) if m)


def by_scope(trace_dir, scopes, hlo=None, top=12):
    """Print device self time by named scope, and the largest
    operations of each scope."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    from harness import trace_reduce
    op_names = hlo_op_names(hlo) if hlo else {}
    device, _ = trace_reduce.read_profile(
        trace_reduce.find_xplane(trace_dir))
    for plane, lines in device.items():
        events = [(trace_reduce.op_name(n), s, d)
                  for n, s, d in lines["ops"]]
        program, steps = collections.Counter(
            n.split("(")[0] for n, _, _ in lines["modules"]
        ).most_common(1)[0]
        spans = [(s, s + d) for _, s, d in events]
        busy = trace_reduce._union(spans) / 1e6 / steps
        window = max(e for _, e in spans) - min(s for s, _ in spans)
        per_scope = collections.defaultdict(collections.Counter)
        for name, self_ns in trace_reduce._self_times(events):
            text = op_names.get(name, name)
            scope = next((s for s in scopes if s in text), "(none)")
            per_scope[scope][name] += self_ns
        print(f"{plane}: {steps} executions of {program}; window "
              f"{window / 1e6 / steps:.3f} ms, busy {busy:.3f} ms an "
              f"execution (every program's ops counted)")
        for scope, ops in sorted(per_scope.items(),
                                 key=lambda kv: -sum(kv[1].values())):
            total = sum(ops.values())
            print(f"{total / 1e6 / steps:9.3f} ms  {scope}  "
                  f"({len(ops)} distinct ops)")
            for name, ns in ops.most_common(top):
                print(f"    {ns / 1e6 / steps:9.3f} ms  {name[:100]}")


def main():
    if "--by-scope" in sys.argv:
        at = sys.argv.index("--by-scope")
        hlo = sys.argv[sys.argv.index("--hlo") + 1] \
            if "--hlo" in sys.argv else None
        return by_scope(sys.argv[1], sys.argv[at + 1].split(","), hlo)
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
