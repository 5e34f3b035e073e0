"""Which host phase the chip waits for: the idle time of a device trace
by `serving.tracing.HOST_PHASES`.

Usage: python tools/host_gaps.py <trace dir> [--rehearse]

<trace dir> holds a `jax.profiler` trace of a serving run made with
`serving.tracing` enabled (`benchmarks/run.py --trace 1 --trace-dir
DIR` keeps one). The engine and the frontend mark every host phase of a
serving cycle as a `jax.profiler.TraceAnnotation`; those land on
`/host:CPU`, on the one clock the device planes share, so a gap between
two device ops can be laid over what the host was doing in it.

Two tables. The first is the benchmark's own reduction
(`benchmarks/harness/trace_reduce.py`, `reduce_events(labels=
HOST_PHASES)`): every gap goes, whole, to the phase that overlaps it
most. The second splits each gap among all the phases that overlap it:
where a gap spans emit, note, the two executor hops and the next pack,
it says how much of it each took. `HOST_PHASES` is the HOST half of one
timeline; the DEVICE half, what the chip did while it was busy, is
`serving.tracing.DEVICE_SCOPES` (`tools/parse_xplane.py --by-scope`,
and the table a traced benchmark run prints). `--rehearse` reads a CPU trace (its
host events with an `hlo_op` stat stand in for device ops): it debugs
the tool and measures nothing.

How far the one clock is shared. The profiler lays the device planes
on the host's clock from one synchronisation per session, and the two
differ by a constant of a millisecond or two (first traces of PR 25:
every mixed-step program began 1.5-2.1 ms, in the next session 0.4-1.0
ms, BEFORE the `engine.dispatch` that launched it was entered). A
program cannot start before its dispatch does, so the largest such
lead is a lower bound of the skew: the tool prints it and moves the
device planes later by it before it names the gaps. What the skew
cannot touch is any sum over a whole cycle.
"""
import bisect
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness import trace_reduce  # noqa: E402

from paddle_tpu.serving.tracing import HOST_PHASES  # noqa: E402

PROGRAM = "serving_mixed_step"


def split_by_phase(gaps, host):
    """{phase: idle seconds} with every gap shared out among the phase
    annotations it overlaps; what no phase covers is under
    `trace_reduce.DEFAULT_GAP`."""
    notes = sorted((s, s + d, n) for n, s, d in host if n in HOST_PHASES)
    starts = [s for s, _, _ in notes]
    longest = max((e - s for s, e, _ in notes), default=0)
    out = {}
    for start_s, seconds, _ in gaps:
        gs, ge = start_s * 1e9, (start_s + seconds) * 1e9
        covered = 0.0
        i = bisect.bisect_left(starts, gs - longest)
        while i < len(notes) and notes[i][0] < ge:
            s, e, name = notes[i]
            overlap = min(ge, e) - max(gs, s)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap / 1e9
                covered += overlap
            i += 1
        rest = (ge - gs - covered) / 1e9
        if rest > 0:
            out[trace_reduce.DEFAULT_GAP] = out.get(
                trace_reduce.DEFAULT_GAP, 0.0) + rest
    return out


def device_lead(device, host):
    """Nanoseconds by which the device planes run ahead of `/host:CPU`,
    at least: the most by which a mixed-step program starts before the
    `engine.dispatch` that launches it (the first program that starts
    no earlier than 5 ms before the dispatch is entered). 0 where no
    program leads."""
    starts = sorted(s for plane in device.values()
                    for n, s, _ in plane.get("modules", [])
                    if PROGRAM in n)
    lead = 0
    for name, ds, _ in host:
        if name != "engine.dispatch":
            continue
        i = bisect.bisect_left(starts, ds - 5_000_000)
        if i < len(starts) and starts[i] < ds:
            lead = max(lead, ds - starts[i])
    return lead


def later(device, ns):
    return {name: {line: [(n, s + ns, d) for n, s, d in events]
                   for line, events in plane.items()}
            for name, plane in device.items()}


def annotation_steps(path):
    """{phase: (events, events that carry a `step` stat)} on
    `/host:CPU`."""
    from jax.profiler import ProfileData
    out = {name: (0, 0) for name in HOST_PHASES}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    n, with_step = out[ev.name]
                    has = any(k == "step" for k, _ in ev.stats)
                    out[ev.name] = (n + 1, with_step + has)
    return out


def table(title, by_phase, idle, steps):
    print(f"\n{title}")
    print(f"{'phase':26s} {'idle s':>9s} {'of idle':>8s} {'ms/step':>8s}")
    order = list(HOST_PHASES) + [trace_reduce.DEFAULT_GAP]
    for name in order:
        s = by_phase.get(name, 0.0)
        print(f"{name:26s} {s:9.4f} {s / idle:8.1%} "
              f"{s * 1e3 / steps:8.3f}")
    named = sum(s for n, s in by_phase.items() if n in HOST_PHASES)
    print(f"{'under a phase name':26s} {named:9.4f} {named / idle:8.1%} "
          f"{named * 1e3 / steps:8.3f}")


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    rehearse = "--rehearse" in argv
    dirs = [a for a in argv if not a.startswith("--")]
    if len(dirs) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = trace_reduce.find_xplane(dirs[0])
    device, host = trace_reduce.read_profile(path, rehearse)
    lead = device_lead(device, host)
    trace = trace_reduce.reduce_events(later(device, lead), host,
                                       labels=HOST_PHASES)
    idle = trace.window_s - trace.busy_s
    steps = trace.calls_of(PROGRAM, "modules") or 1
    if not trace.window_s or idle <= 0:
        print("host_gaps: the trace holds no device operation, or no "
              "idle time", file=sys.stderr)
        return 1
    print(f"host_gaps{' CPU REHEARSAL' if rehearse else ''}: {path}")
    print(f"slice {trace.window_s:.3f} s, device busy {trace.busy_s:.3f} "
          f"s, idle {idle:.4f} s ({trace.idle_share:.2%}) in "
          f"{len(trace.gaps)} gaps; {steps:.0f} mixed steps "
          f"({idle * 1e3 / steps:.3f} ms idle a step)")
    print(f"a mixed-step program starts up to {lead / 1e6:.3f} ms "
          "before the engine.dispatch that launches it: the device "
          "planes are laid that much later below")
    seen = annotation_steps(path)
    print("annotations on /host:CPU (events, with a `step` stat): "
          + ", ".join(f"{name} {n}/{with_step}"
                      for name, (n, with_step) in seen.items()))
    missing = [name for name, (n, with_step) in seen.items()
               if not n or n != with_step]
    table("each gap to the phase that overlaps it most "
          "(trace_reduce.reduce_events):", trace.idle_by_label(), idle,
          steps)
    table("each gap split among the phases that overlap it:",
          split_by_phase(trace.gaps, host), idle, steps)
    if missing:
        print(f"host_gaps: no annotation, or none with a step, for "
              f"{missing}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
