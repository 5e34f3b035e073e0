"""One run of one cell: arguments, device gate, files by name, the
driver's phases, the per-layer readers, the result line.

From the program it takes only the system under test and its spans,
counters and kernel names. Whatever belongs to one configuration,
traffic mix, kind of job or per-layer metric is a file of its own
under `benchmarks/`, found here by the name `BENCHMARK.json` gives it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import types

from harness import stats
from harness import traffic as traffic_mod
from harness.files import BENCH, ROOT, load_json, load_module

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
NO_DEVICE = 4       # exit code: no accelerator, or too few chips


def metrics_of(manifest, group, cell):
    """The metrics of `group` that the cell reports: those that list it
    under `workloads`, and those with no such key."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


class CompileEvents:
    """Every executable jax builds or loads in this process, counted
    through `jax.monitoring`: the same event `instrumented_jit` counts
    by name, here counted whatever its name, so that a compile of ANY
    program inside the measured window shows."""

    def __init__(self, jax):
        self.total = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1


def main(argv=None, t_start=None):
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels interpreted: "
                         "debugs the harness, measures nothing")
    ap.add_argument("--traffic", default=None,
                    help="with --rehearse only: run the cell's "
                         "configuration under another mix of "
                         "benchmarks/traffic/, to try a mix before a "
                         "cell is made of it")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace here (default: "
                         "<checkout>/.bench_out/trace, removed after "
                         "the reduction)")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.traffic and not args.rehearse:
        print("bench: --traffic is for rehearsals; a measured run takes "
              "the cell's own mix", file=sys.stderr)
        return 2
    seconds = float(manifest["run_seconds"] if args.seconds is None
                    else args.seconds)
    config = traffic_mod.with_rehearsal(
        load_json(BENCH, "configs", cell["config"] + ".json"),
        args.rehearse)
    traffic = traffic_mod.with_rehearsal(
        load_json(BENCH, "traffic",
                  (args.traffic or cell["traffic"]) + ".json"),
        args.rehearse)
    chips = int(cell["chips"])
    label = "bench CPU REHEARSAL" if args.rehearse else "bench"

    if args.rehearse:
        # before jax is imported: pin the rehearsal to the CPU
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={chips}")

    import jax
    from paddle_tpu.analysis import guards
    from paddle_tpu.core.compile_cache import use_compile_cache
    from paddle_tpu.ops.pallas import interpret_mode

    cache_dir = use_compile_cache()
    # the small executables too: a program under jax's one-second
    # minimum would be compiled again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileEvents(jax)

    try:
        found = jax.devices()
    except RuntimeError as e:
        print(f"bench: jax found no device: {e}", file=sys.stderr)
        return NO_DEVICE
    dev0 = found[0]
    print(f"{label}: workload={args.workload} seed={args.seed} "
          f"seconds={seconds} trace={args.trace} "
          f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
          f"devices={len(found)} jax={jax.__version__}", flush=True)
    if not args.rehearse and dev0.platform != "tpu":
        print(f"bench: no TPU (platform {dev0.platform!r}); nothing was "
              "measured. `--rehearse` debugs the harness on the CPU.",
              file=sys.stderr)
        return NO_DEVICE
    if len(found) < chips:
        print(f"bench: the cell wants {chips} chip(s), jax has "
              f"{len(found)}", file=sys.stderr)
        return NO_DEVICE
    devices = found[:chips]
    if args.rehearse:
        peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "source": "REHEARSAL placeholder, not a device"}
    else:
        peaks = stats.load_peaks(dev0.device_kind)
    print(f"{label}: compile cache at {cache_dir}; imports and device "
          f"discovery took {time.monotonic() - t_start:.1f} s",
          flush=True)

    trace_dir = args.trace_dir or os.path.join(
        ROOT, ".bench_out", "trace", args.workload)
    env = types.SimpleNamespace(
        config=config, config_name=cell["config"], traffic=traffic,
        seed=args.seed, chips=chips,
        devices=devices, rehearse=args.rehearse, peaks=peaks,
        compiles=compiles, trace_dir=trace_dir, label=label,
        keep_trace=args.trace_dir is not None,
        log=lambda msg: print(f"{label}: {msg}", flush=True))
    driver = load_module("drivers", config["driver"]).Driver(env)

    mode = interpret_mode() if args.rehearse else contextlib.nullcontext()
    with mode, guards.sanitize(transfer_guard=None) as watchdog:
        driver.setup()
        driver.warm()
        # the driver stamps `window_start`: set-up ends there
        run = driver.run(seconds, bool(args.trace))
        checks = driver.check()
    setup_s = run["window_start"] - t_start
    if watchdog.violations:
        checks["compile watchdog"] = "; ".join(
            str(v) for v in watchdog.violations)
    for name, problem in checks.items():
        if problem:
            print(f"{label}: CHECK FAILED {name}: {problem}", flush=True)
    correct = not any(checks.values())

    peak_mem = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak_mem = max(peak_mem, int(ms.get("peak_bytes_in_use", 0)))
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(found), "memory_peak_bytes": peak_mem}
    print(f"{label}: set-up {setup_s:.1f} s; compile cache "
          f"{compiles.cache['hits']} hits {compiles.cache['misses']} "
          f"misses; {compiles.total} executables built or loaded, "
          f"{run['compiles_in_window']} inside the window; peak device "
          f"memory {peak_mem / 1e9:.2f} GB", flush=True)

    values = dict(run["end_to_end"], setup_s=setup_s)
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": {}, "device": device}
    if args.trace:
        ctx = run["ctx"]
        trace = ctx.trace
        if trace is None or trace.busy_s <= 0:
            print(f"{label}: the trace holds no device operation",
                  file=sys.stderr)
            return 1
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = trace.breakdown()
        for m in metrics_of(manifest, "per_layer", args.workload):
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {
                    "value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(manifest, "end_to_end", args.workload):
            value = values.get(m["name"])
            if value is not None:
                # a tail that touches a failed request is +inf, which
                # JSON cannot carry: the largest float stands for it
                result["metrics"][m["name"]] = {
                    "value": min(value, sys.float_info.max),
                    "unit": m["unit"]}
    if args.rehearse:
        print(f"{label}: the next line is from a CPU rehearsal at tiny "
              "sizes. It is no measurement.", flush=True)
    print(json.dumps(result), flush=True)
    return 0
