"""Parse a jax.profiler xplane trace: aggregate TPU device-plane op time.

Usage: python tools/parse_xplane.py <trace_dir> [n_steps] [top_k]
       python tools/parse_xplane.py <trace_dir> --by-scope SCOPE[,SCOPE...]
                                    [--hlo compiled_step.txt |
                                     --config <configuration> [--rehearse]]

Finds the newest .xplane.pb under <trace_dir>, sums duration by HLO op
name on the TPU device plane's "XLA Ops" line, and prints a per-step
table (total / n_steps).  The device trace is the ground truth for
per-kernel time; a host clock around one async dispatch is not.
The trace is read with `jax.profiler.ProfileData`: nothing but jax.

`--by-scope` sums SELF time (a `while` keeps what its body's ops leave)
by the `jax.named_scope` an op was traced under, per execution of the
program that ran most often on the device. The scopes of a serving step
are `serving.tracing.DEVICE_SCOPES` (give `all` for the whole list):
the DEVICE half of the one timeline whose HOST half is
`serving.tracing.HOST_PHASES` (`tools/host_gaps.py` lays the device's
idle gaps over those). A v5e trace's events carry no `op_name` (their
stats are offsets and durations), so the scopes come from the compiled
program's HLO metadata:

- `--hlo FILE`: the program's compiled HLO text
  (`jitted.trace(*args).lower().compile().as_text()`, made by the same
  code at the same shapes); an event goes to the first of the given
  scopes that its instruction's `op_name` contains, else to `(none)`;
- `--config NAME`: the step of the benchmark configuration
  `benchmarks/configs/NAME.json`, built and lowered here through the
  cell's own driver, and the engine's own table
  (`ServingEngine.step_op_scopes()`: innermost scope first, an
  instruction the compiler made takes its user's); on the machine the
  trace was made on, since the table has to be the running executable's;
- neither: only an event's own name is searched (a kernel's).

A traced run of the benchmark prints the same table itself
(`benchmarks/layer_metrics/device.named_busy_pct.py`).
"""
import collections
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from paddle_tpu.profiler.xplane import (  # noqa: E402,F401
    device_op_times, hlo_op_names, load_xplane)
from paddle_tpu.serving.tracing import DEVICE_SCOPES  # noqa: E402


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


def config_table(name, rehearse=False):
    """{instruction: scope} of the mixed step of the benchmark
    configuration `name`, by the engine its cell's driver builds
    (`rehearse`: at the rehearsal's tiny sizes, to try the tool)."""
    import types

    from harness import traffic
    from harness.files import BENCH, load_json, load_module
    cell = next(w for w in load_json(ROOT, "BENCHMARK.json")["workloads"]
                if w["config"] == name)
    config = traffic.with_rehearsal(
        load_json(BENCH, "configs", name + ".json"), rehearse)
    env = types.SimpleNamespace(
        config=config, config_name=name, seed=0, chips=1,
        rehearse=rehearse, traffic=traffic.with_rehearsal(
            load_json(BENCH, "traffic", cell["traffic"] + ".json"),
            rehearse),
        log=lambda msg: print(f"parse_xplane: {msg}", flush=True))
    driver = load_module("drivers", config["driver"]).Driver(env)
    driver.setup()
    return driver.engine.step_op_scopes()


def by_scope(trace_dir, scopes, hlo=None, top=12, table=None):
    """Print device self time by named scope, and the largest
    operations of each scope. `table`: {instruction: scope}, the
    engine's own; else `hlo`, a compiled module's text file."""
    from harness import trace_reduce
    if hlo:
        with open(hlo) as f:
            op_names = hlo_op_names(f.read())
    else:
        op_names = {}
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
            scope = table.get(name, "(none)") if table else next(
                (s for s in scopes if s in text), "(none)")
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
        def option(flag):
            return sys.argv[sys.argv.index(flag) + 1] \
                if flag in sys.argv else None
        scopes = option("--by-scope")
        scopes = DEVICE_SCOPES if scopes == "all" else scopes.split(",")
        config = option("--config")
        return by_scope(
            sys.argv[1], scopes, option("--hlo"),
            table=config and config_table(
                config, rehearse="--rehearse" in sys.argv))
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
