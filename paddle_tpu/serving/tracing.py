"""Fleet-wide request tracing for the serving engine (ISSUE 16).

Two host-side event stores, both bounded, both branch-gated like
`profiler.metrics._enabled`, the host phases of a serving cycle, and
the device scopes of the step:

* **Request traces** (`TRACER`, a `RequestTracer`) — one stitched
  span/event timeline per request: enqueued → admitted → prefill
  chunks → first token → handoff export → migration transport →
  decode admission → decode/verify steps → preempted / re-prefilled →
  finished | expired | cancelled. The trace id is minted at router
  dispatch (or lazily at engine submit for solo engines) and
  propagated Frontend → Scheduler → Engine → `MigrationTicket` →
  the destination replica's scheduler, so ONE trace survives disagg
  handoff, shed migration and failover. A failover re-dispatch REOPENS
  a trace the dying replica's cancel path already closed (see
  `_REOPEN_EVENTS`) — the surviving replica's terminal outcome wins.
* **Step flight recorders** (`StepFlightRecorder`, one per engine) —
  a bounded ring of per-step records (role, tokens prefilled/decoded,
  active slots, sparse skip ratio, blocks imported, jit cache size,
  step wall time, the step's host phases, the KV tokens it read and
  the pool's occupancy) exportable as chrome "X" slices on an
  `engine:<name>` track, the phases nested under their step.
* **Host phases** (`PhaseMarker`, one per engine, `HOST_PHASES`) —
  where a serving cycle's host time goes, on two clocks at once: the
  engine's monotonic clock (durations land in the step's flight
  record) and the profiler's own, as `jax.profiler.TraceAnnotation`s
  that sit on `/host:CPU` beside the device planes of a
  `jax.profiler` trace, which is the only clock device ops share.

* **Device scopes** (`DEVICE_SCOPES`) — the other half of the same
  timeline: the layer map's names for what `serving_mixed_step` does
  on the device, set as `jax.named_scope`s on every operation of the
  step. They are HLO metadata (no flag, no run-time cost); an engine
  gives its compiled step's table of instruction -> scope
  (`ServingEngine.step_op_scopes`), and `step_op_scopes()` here hands
  the live engines' tables to a reader of a device trace.
* **The tracer's own cost** — `RequestTracer.queue` lets a hot path
  pay one append an event (recorded, in order, at the next `flush`);
  `PhaseMarker` times its own marks; the engine sums both with its
  record's reads into the flight field `trace_self`.

Both stores register with the profiler's provider hooks
(`profiler.register_chrome_source` / `register_summary_section`), so
`profiler.export_chrome_tracing` and `profiler.summary()` merge them
with the existing host spans + registry counters — no profiler →
serving import, the dependency points the other way.

Hot-path discipline: every call site in engine/scheduler/router/
transport guards with ``if tracing._enabled:`` so recording off costs
one branch; recording on touches only host ints/floats already
computed by the step loop — no readback of its own (a block model's
counters come with the tokens), no new jit inputs, zero extra compiles
(tests/test_tracing.py's overhead contract), and what is only
assembled from those values (the flight record, the span events'
bookkeeping) is done after the next dispatch has launched.

Env knobs: ``PADDLE_TPU_TRACE=1`` enables at import,
``PADDLE_TPU_TRACE_CAPACITY`` bounds the retained-trace table
(default 2048, oldest finished evicted first),
``PADDLE_TPU_TRACE_EVENTS_MAX`` bounds events per trace (default 512),
``PADDLE_TPU_FLIGHT_STEPS`` bounds each flight ring (default 16384).
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time
import weakref

from ..profiler import metrics as _pmetrics
from . import metrics as _smetrics

__all__ = [
    "TRACER", "RequestTracer", "Trace", "TraceEvent",
    "StepFlightRecorder", "PhaseMarker", "HOST_PHASES", "DEVICE_SCOPES",
    "NO_SCOPE", "scope_of", "enable", "disable", "enabled",
    "register_flight_recorder", "flight_recorders", "step_op_scopes",
]

_enabled = os.environ.get(
    "PADDLE_TPU_TRACE", "0").lower() not in ("0", "", "false")


def enable():
    """Turn request tracing on process-wide (idempotent)."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled():
    return _enabled


#: events that REOPEN a finished trace. A replica death makes the dying
#: frontend's stop() cancel its live requests — the engine-side cancel
#: closes the trace "cancelled" before the router re-dispatches the
#: SAME request elsewhere. The re-dispatch (and the destination
#: enqueue) must un-close it so the surviving replica's real outcome
#: lands on the one stitched trace.
_REOPEN_EVENTS = frozenset({"dispatched", "enqueued"})

#: span taxonomy (docs/OBSERVABILITY.md documents each): the decode
#: loop coalesces one decode_step/verify_step event per emit, not one
#: per token — `tokens`/`gap` attrs carry the detail.
EVENT_NAMES = (
    "dispatched", "enqueued", "admitted", "prefill_chunk",
    "first_token", "handoff", "handoff_export", "migration_transport",
    "decode_admission", "decode_step", "verify_step", "preempted",
    "failover", "finished", "expired", "cancelled", "error",
)


class TraceEvent:
    __slots__ = ("name", "ts", "replica", "attrs")

    def __init__(self, name, ts, replica, attrs):
        self.name = name
        self.ts = ts
        self.replica = replica
        self.attrs = attrs

    def as_dict(self):
        d = {"name": self.name, "ts": self.ts}
        if self.replica is not None:
            d["replica"] = self.replica
        if self.attrs:
            d.update(self.attrs)
        return d

    def __repr__(self):
        return (f"TraceEvent({self.name!r}, ts={self.ts:.6f}, "
                f"replica={self.replica!r})")


class Trace:
    """One request's stitched timeline. Timestamps are clamped monotone
    per trace at record time (fleet clocks are per-engine monotonic
    clocks in one process; the clamp absorbs sub-microsecond races
    between the router thread and engine executor threads)."""

    __slots__ = ("trace_id", "tenant", "events", "done", "outcome",
                 "dropped_events", "_last_ts")

    def __init__(self, trace_id, tenant):
        self.trace_id = trace_id
        self.tenant = tenant
        self.events = []
        self.done = False
        self.outcome = None
        self.dropped_events = 0
        self._last_ts = None

    @property
    def replicas(self):
        return sorted({e.replica for e in self.events
                       if e.replica is not None})

    def first(self, name):
        for e in self.events:
            if e.name == name:
                return e
        return None

    def monotone(self):
        ts = [e.ts for e in self.events]
        return all(a <= b for a, b in zip(ts, ts[1:]))

    def derive(self):
        """Span-derived latencies — defined so they MATCH the registry
        histograms exactly: enqueued.ts is `req.submit_time` and
        first_token.ts the engine's emit-time `now`, the same two
        numbers `SERVING_TTFT_SECONDS` subtracts."""
        enq = self.first("enqueued")
        adm = self.first("admitted")
        ft = self.first("first_token")
        gaps = [e.attrs.get("gap") for e in self.events
                if e.name in ("decode_step", "verify_step")
                and e.attrs.get("gap") is not None]
        # tokens delivered together (block decoding) lie at gap 0
        gaps += [0.0] * sum(e.attrs.get("inside", 0) for e in self.events)
        d = {
            "trace_id": self.trace_id,
            "tenant": self.tenant,
            "outcome": self.outcome,
            "replicas": self.replicas,
            "events": len(self.events),
            "ttft": (ft.ts - enq.ts) if ft and enq else None,
            "queue_wait": (adm.ts - enq.ts) if adm and enq else None,
            "inter_token": gaps,
        }
        exp = self.first("handoff_export")
        if exp is not None:
            # handoff gap: export on the source to the next token the
            # destination emitted (the stream stall a migration costs)
            for e in self.events:
                if e.ts >= exp.ts and e.name in (
                        "first_token", "decode_step", "verify_step"):
                    d["handoff_gap"] = e.ts - exp.ts
                    break
        return d

    def as_dict(self):
        return {"trace_id": self.trace_id, "tenant": self.tenant,
                "outcome": self.outcome, "done": self.done,
                "dropped_events": self.dropped_events,
                "events": [e.as_dict() for e in self.events]}


#: a queue this long records itself: nobody flushed for a long while
_PENDING_MAX = 4096


class RequestTracer:
    """Process-global trace table + observer fan-out.

    Thread-safe: the router event loop, every engine's executor thread
    and the scheduler all record under one lock (host dict/list ops —
    nanoseconds against a multi-ms step). Observers (the SLO plane)
    are notified OUTSIDE the lock; observer exceptions are swallowed —
    observability must never take down the serving loop."""

    def __init__(self, capacity=None, max_events=None,
                 clock=time.monotonic):
        if capacity is None:
            capacity = int(os.environ.get(
                "PADDLE_TPU_TRACE_CAPACITY", 2048))
        if max_events is None:
            max_events = int(os.environ.get(
                "PADDLE_TPU_TRACE_EVENTS_MAX", 512))
        self.capacity = max(1, int(capacity))
        self.max_events = max(8, int(max_events))
        self.clock = clock
        self._traces = collections.OrderedDict()
        self._lock = threading.Lock()
        # `queue`d events wait here, in the order they were made, until
        # `flush()` records them
        self._pending = collections.deque()
        self._seq = itertools.count()
        self._observers = []
        self._open = 0   # incremental: scanning the table per event
        self.dropped_traces = 0   # would be O(capacity) on the hot path

    # ------------------------------------------------------ lifecycle
    def mint(self, tenant="default"):
        """New trace id (router dispatch / solo engine submit)."""
        tid = f"tr-{next(self._seq):08x}"
        with self._lock:
            self._traces[tid] = Trace(tid, str(tenant))
            self._open += 1
            self._evict_locked()
        self._set_active_gauge()
        return tid

    def _evict_locked(self):
        while len(self._traces) > self.capacity:
            # drop the oldest FINISHED trace first; if every retained
            # trace is still open, drop the oldest outright (a stuck
            # fleet must not pin unbounded memory)
            victim = None
            for k, tr in self._traces.items():
                if tr.done:
                    victim = k
                    break
            if victim is None:
                victim = next(iter(self._traces))
            if not self._traces[victim].done:
                self._open -= 1
            del self._traces[victim]
            self.dropped_traces += 1

    def event(self, trace_id, name, replica=None, ts=None, **attrs):
        """Record one span event, now (after whatever `queue` holds).
        Unknown ids get a shell trace (late enable / post-eviction
        stitching stays lossy-but-safe); events after a terminal are
        dropped unless `name` reopens the trace."""
        if not _enabled or trace_id is None:
            return
        if ts is None:
            ts = self.clock()
        self.flush((False, trace_id, name, replica, ts, attrs))

    def queue(self, trace_id, name, replica=None, ts=None, **attrs):
        """`event` for a hot path: the event waits, with its timestamp,
        for the next `flush()` (or the next `event` / `finish` / query,
        which flush first, so the order in which events were MADE is the
        order in which they are recorded). The engine queues a step's
        token and chunk events and flushes once its next dispatch has
        launched: the table's lock, the clamps and the counters then
        run while the device does, and the call site pays one append."""
        if not _enabled or trace_id is None:
            return
        if ts is None:
            ts = self.clock()
        self._pending.append((False, trace_id, name, replica, ts, attrs))
        if len(self._pending) >= _PENDING_MAX:
            self.flush()

    def finish(self, trace_id, outcome, replica=None, ts=None, **attrs):
        """Close a trace with a terminal outcome. Idempotent: the first
        terminal wins (the router's abandon path and the engine's
        cancel path may both fire; double-closing would double-count
        `SERVING_TRACES`)."""
        if not _enabled or trace_id is None:
            return
        if ts is None:
            ts = self.clock()
        self.flush((True, trace_id, outcome, replica, ts, attrs))

    def flush(self, then=None):
        """Record what `queue` holds, oldest first, then `then`: (is it
        a terminal, trace id, name or outcome, replica, ts, attrs)."""
        if not self._pending and then is None:
            return
        with self._lock:
            while True:
                try:
                    item = self._pending.popleft()
                except IndexError:
                    item, then = then, None
                    if item is None:
                        break
                record = self._finish_locked if item[0] \
                    else self._event_locked
                record(*item[1:])
        self._set_active_gauge()

    def _event_locked(self, trace_id, name, replica, ts, attrs):
        tr = self._traces.get(trace_id)
        if tr is None:
            tr = Trace(trace_id, str(attrs.get("tenant", "default")))
            self._traces[trace_id] = tr
            self._open += 1
            self._evict_locked()
        if tr.done:
            if name in _REOPEN_EVENTS:
                tr.done = False
                tr.outcome = None
                self._open += 1
            else:
                return
        if len(tr.events) >= self.max_events:
            tr.dropped_events += 1
            if _pmetrics._enabled:
                _smetrics.SERVING_TRACE_EVENTS_DROPPED.inc()
            return
        if tr._last_ts is not None and ts < tr._last_ts:
            ts = tr._last_ts
        tr._last_ts = ts
        tr.events.append(TraceEvent(name, ts, replica, attrs))
        if _pmetrics._enabled:
            _smetrics.SERVING_TRACE_EVENTS.labels(name).inc()

    def _finish_locked(self, trace_id, outcome, replica, ts, attrs):
        tr = self._traces.get(trace_id)
        if tr is None or tr.done:
            return
        if tr._last_ts is not None and ts < tr._last_ts:
            ts = tr._last_ts
        tr._last_ts = ts
        # the terminal event always lands, even past max_events
        tr.events.append(TraceEvent(outcome, ts, replica, attrs))
        tr.done = True
        tr.outcome = outcome
        self._open -= 1
        if _pmetrics._enabled:
            _smetrics.SERVING_TRACES.labels(outcome).inc()

    def _set_active_gauge(self):
        if _pmetrics._enabled:
            _smetrics.SERVING_TRACE_ACTIVE.set(self._open)

    # ------------------------------------------------------- queries
    def get(self, trace_id):
        self.flush()
        with self._lock:
            return self._traces.get(trace_id)

    def traces(self):
        self.flush()
        with self._lock:
            return list(self._traces.values())

    def active(self):
        """Open traces — the smoke tool's orphan check: after a clean
        drain this must be empty."""
        self.flush()
        with self._lock:
            return [t for t in self._traces.values() if not t.done]

    def reset(self):
        with self._lock:
            self._pending.clear()
            self._traces.clear()
            self._open = 0
            self.dropped_traces = 0
        self._set_active_gauge()

    # ------------------------------------------------------ observers
    def add_observer(self, obs):
        if obs not in self._observers:
            self._observers.append(obs)

    def remove_observer(self, obs):
        try:
            self._observers.remove(obs)
        except ValueError:
            pass

    def _notify(self, method, *args):
        for obs in list(self._observers):
            fn = getattr(obs, method, None)
            if fn is None:
                continue
            try:
                fn(*args)
            except Exception:
                pass

    # ----------------------------------------------- chrome / summary
    def chrome_events(self):
        """Per-trace track: phase "X" slices (queued / prefill /
        decode) + one instant per raw event, ts in µs like the host
        recorder."""
        pid = os.getpid()
        out = []
        for tr in self.traces():
            tid = f"trace:{tr.trace_id}"
            for e in tr.events:
                out.append({"name": e.name, "ph": "i", "s": "t",
                            "ts": e.ts * 1e6, "pid": pid, "tid": tid,
                            "args": e.as_dict()})
            d = tr.derive()
            enq = tr.first("enqueued")
            adm = tr.first("admitted")
            ft = tr.first("first_token")
            last = tr.events[-1] if tr.events else None
            for name, a, b in (("queued", enq, adm),
                               ("prefill", adm, ft),
                               ("decode", ft, last)):
                if a is not None and b is not None and b.ts >= a.ts:
                    out.append({"name": f"{name}[{tr.tenant}]",
                                "ph": "X", "ts": a.ts * 1e6,
                                "dur": (b.ts - a.ts) * 1e6,
                                "pid": pid, "tid": tid,
                                "args": {"trace_id": tr.trace_id}})
        return out

    def summary_table(self):
        traces = self.traces()
        if not traces:
            return ""
        by_outcome = collections.Counter(
            t.outcome or "open" for t in traces)
        ttfts = [d["ttft"] for d in (t.derive() for t in traces)
                 if d["ttft"] is not None]
        lines = ["---- request traces (serving.tracing) ----",
                 f"{'Outcome':16s} {'Traces':>8s}"]
        for outcome, n in sorted(by_outcome.items()):
            lines.append(f"{outcome:16s} {n:>8d}")
        if ttfts:
            lines.append(f"span-derived TTFT mean "
                         f"{sum(ttfts) / len(ttfts) * 1e3:.2f} ms over "
                         f"{len(ttfts)} trace(s)")
        if self.dropped_traces:
            lines.append(f"(trace table evicted {self.dropped_traces}; "
                         f"raise PADDLE_TPU_TRACE_CAPACITY)")
        return "\n".join(lines)


TRACER = RequestTracer()


# ---------------------------------------------------------------- hooks
# Engine/scheduler/router/transport call these; every CALL SITE guards
# with `if tracing._enabled:` so the off path stays one branch — the
# re-check inside is defense for direct callers, not the contract.

def ensure_trace(req):
    """Attach a trace id to a request, minting one when the router did
    not (solo engines submit without a frontend)."""
    if req.trace_id is None:
        req.trace_id = TRACER.mint(tenant=req.tenant)
    return req.trace_id


def on_submit(req, replica=None):
    ensure_trace(req)
    TRACER.event(req.trace_id, "enqueued", replica=replica,
                 ts=req.submit_time, tenant=req.tenant,
                 prompt_tokens=len(req.prompt))


def on_submit_migrated(req, replica=None, ts=None):
    ensure_trace(req)
    TRACER.event(req.trace_id, "decode_admission", replica=replica,
                 ts=ts, tenant=req.tenant, tokens_done=len(req.output))


def on_admitted(req, replica=None, kind="prefill", ts=None):
    """kind: "prefill" (fresh), "re_prefill" (after preemption, or a
    migrant that lost its imported blocks), "import" (migrated-in KV).
    Only the fresh admission observes the queue-wait histogram — its
    span twin is `admitted.ts - enqueued.ts` of the same trace."""
    TRACER.event(req.trace_id, "admitted", replica=replica, ts=ts,
                 kind=kind, slot=req.slot,
                 cached_tokens=req.cache_hit_tokens)
    if (kind == "prefill" and _pmetrics._enabled and ts is not None):
        _smetrics.SERVING_TRACE_QUEUE_WAIT.observe(
            max(0.0, ts - req.submit_time))


def on_first_token(req, replica=None, ts=None, inside=0):
    # `inside`: further tokens delivered with this one (a model that
    # decodes by blocks hands over 1..L at once), each at gap 0
    TRACER.queue(req.trace_id, "first_token", replica=replica, ts=ts,
                 **({"inside": inside} if inside else {}))
    if ts is not None:
        TRACER._notify("on_ttft", req.tenant, ts - req.submit_time, ts)


def on_tokens(req, replica=None, ts=None, n=1, gap=None, verify=False,
              inside=0):
    TRACER.queue(req.trace_id,
                 "verify_step" if verify else "decode_step",
                 replica=replica, ts=ts, tokens=n, gap=gap,
                 **({"inside": inside} if inside else {}))
    if gap is not None:
        TRACER._notify("on_inter_token", req.tenant, gap, ts)


def on_preempted(req, replica=None, ts=None):
    TRACER.event(req.trace_id, "preempted", replica=replica, ts=ts,
                 preemptions=req.preemptions)


def on_extracted(req, ticket, replica=None):
    TRACER.event(req.trace_id, "handoff_export", replica=replica,
                 ts=ticket.created_at, slot_len=ticket.slot_len,
                 blocks=sum(c.count for c in ticket.chunks),
                 shipped_ahead=ticket.total_blocks
                 - sum(c.count for c in ticket.chunks))


def on_transport(trace_id, src, dst, nbytes=0, blocks=0):
    TRACER.event(trace_id, "migration_transport",
                 replica=f"{src}->{dst}", bytes=nbytes, blocks=blocks)


def on_terminal(req, outcome, replica=None, ts=None):
    missed = outcome == "expired" or (
        req.deadline is not None and ts is not None
        and ts > req.deadline)
    TRACER.finish(req.trace_id, outcome, replica=replica, ts=ts,
                  tokens=len(req.output), deadline_missed=missed)
    TRACER._notify("on_outcome", req.tenant, outcome, missed,
                   ts if ts is not None else TRACER.clock())


# ------------------------------------------------------- host phases
#: the host phases of one serving cycle, in the order they run; they
#: tile it end to end. The `frontend.*` four are marked by
#: `ServingFrontend`'s step loop, the `engine.*` six by
#: `ServingEngine.step`. docs/OBSERVABILITY.md says what bounds each.
HOST_PHASES = (
    "frontend.admit", "frontend.hop_in", "engine.plan", "engine.pack",
    "engine.dispatch", "engine.wait", "engine.emit", "engine.note",
    "frontend.hop_out", "frontend.publish",
)


#: the other half of the timeline: what `serving_mixed_step` does on the
#: DEVICE, by the layer map's names. Every operation of the step (the
#: GPT step and the block step of `serving/engine.py`, the layer
#: functions of `models/*.py` behind `models/serving_block.py`) is traced
#: under exactly one of these `jax.named_scope`s; the compiled step's
#: HLO metadata carries them (`ServingEngine.step_op_scopes`), a device
#: trace's events do not. docs/OBSERVABILITY.md says what each holds.
DEVICE_SCOPES = (
    "plan_unpack", "embed", "attn_qkv", "kv_write", "attn_window",
    "attn_full", "attn_out", "mlp", "moe_router", "moe_experts",
    "moe_shared", "lin_proj", "lin_conv", "gated_delta", "lin_gate_out",
    "head", "sample", "diffusion_confidence", "tick_control",
    # a layer that attends through a learned selection: the indexer's
    # three products, its scores, the exact top-k, and the one-token
    # runs' gather and attention (the chunk rows' kernel: `attn_full`)
    "idx_proj", "idx_score", "idx_select", "attn_sparse",
    # a layer that attends by multi-head latent attention: the products
    # with the up-projections W_UK and W_UV, and the attention over the
    # latent pool (its kernel `mla_attend` inside it keeps its name)
    "mla_absorb", "attn_latent",
)
#: what an instruction reads whose `op_name` holds none of them
NO_SCOPE = "(none)"


def scope_of(op_name):
    """The scope of `DEVICE_SCOPES` an instruction was traced under, by
    its HLO `op_name` (`jit(serving_mixed_step)/.../attn_qkv/dot_general`),
    innermost first; `NO_SCOPE` where it names none."""
    for part in reversed(op_name.split("/")):
        if part in DEVICE_SCOPES:
            return part
    return NO_SCOPE


#: the engine's phases that `mixed_step.host_ms_per_step` sums (the wait
#: for the device left out), and so those whose tracing code the flight
#: field `trace_self` counts
SUMMED_PHASES = tuple(p for p in HOST_PHASES
                      if p.startswith("engine.") and p != "engine.wait")


def _phase_field(name):
    """`engine.plan` -> `ph_plan`: the flat flight-record field."""
    return "ph_" + name.split(".", 1)[1]


class PhaseMarker:
    """Marks where one engine's host time goes (one per engine).

    `mark(name)` ends the open phase and starts the next at one reading
    of the engine's clock, so consecutive phases share their boundary
    and tile the cycle. Each phase is also an open
    `jax.profiler.TraceAnnotation` named by the phase and carrying
    `step=<index of the cycle's engine step>`: under a `jax.profiler`
    session it lands on `/host:CPU`, on the clock of the device ops
    (which the engine's clock cannot be lined up with; the profiler's
    own alignment is good to a millisecond or two a session, which
    tools/host_gaps.py bounds); without a session it costs half a
    microsecond. Phases may start on one thread and end on another
    (`frontend.hop_in` does): the annotation then shows on the thread
    that ended it.

    Seconds per phase accumulate until `take()`, which the engine calls
    once per step for the flight record. Call sites guard with
    ``if trace_on:`` like every other tracing hook, so with tracing off
    no annotation is ever built. The marker times ITSELF too: what a
    `mark` costs after the boundary it set lies in the phase it opened,
    and `take_own` hands those seconds out by phase (the flight field
    `trace_self` counts them)."""

    __slots__ = ("clock", "step", "name", "_seconds", "_own", "_t0",
                 "_note")

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.step = 0
        self.name = None            # the open phase
        self._seconds = {}
        self._own = {}
        self._t0 = self._note = None

    def mark(self, name, step=None):
        """End the open phase, start `name`; returns the clock reading
        both share. `step` names the cycle from here on."""
        import jax
        now = self.close()
        if step is not None:
            self.step = step
        self.name, self._t0 = name, now
        self._note = jax.profiler.TraceAnnotation(name, step=self.step)
        self._note.__enter__()
        self._own[name] = self._own.get(name, 0.0) + self.clock() - now
        return now

    def close(self):
        """End the open phase, if any; returns the clock reading."""
        now = self.clock()
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._seconds[self.name] = self._seconds.get(
                self.name, 0.0) + now - self._t0
            self.name = self._t0 = self._note = None
        return now

    def take(self):
        """{flight field: seconds} of the phases ended since the last
        `take()`."""
        out = {_phase_field(k): v for k, v in self._seconds.items()}
        self._seconds = {}
        return out

    def take_own(self, phases):
        """Seconds the marks themselves took inside `phases` since the
        last call."""
        own, self._own = self._own, {}
        return sum(own.get(p, 0.0) for p in phases)


# ------------------------------------------------- step flight recorder
_FLIGHT = weakref.WeakSet()


def register_flight_recorder(rec, op_scopes=None):
    """`op_scopes`: the engine's `step_op_scopes` (bound method), held
    weakly beside its recorder, so that a reader of a device trace can
    ask a live engine for its table without importing the engine."""
    _FLIGHT.add(rec)
    if op_scopes is not None:
        rec.op_scopes = weakref.WeakMethod(op_scopes)


def flight_recorders():
    return list(_FLIGHT)


def step_op_scopes():
    """{engine name: {HLO instruction name: scope}} of every live
    engine's compiled step (`ServingEngine.step_op_scopes`). The first
    call for an engine lowers its step and loads or builds the
    executable: call it outside any measured window."""
    out = {}
    for rec in flight_recorders():
        method = rec.op_scopes and rec.op_scopes()
        if method is not None:
            out[rec.engine_name] = method()
    return out


class StepFlightRecorder:
    """Bounded per-engine ring of per-step records (ISSUE 16 tentpole
    (b)). The engine notes one record per `step()` — host ints/floats
    it already holds — only when tracing is enabled; the ring is sized
    by PADDLE_TPU_FLIGHT_STEPS (default 16384: two minutes and a half
    of 10 ms steps; it was 4096 until an engine that dispatches ahead
    ran the benchmark's 45 traced seconds in 4,600 steps) so a
    long-lived replica keeps a recent flight window, not unbounded
    history."""

    def __init__(self, engine_name, role, maxlen=None):
        if maxlen is None:
            maxlen = int(os.environ.get(
                "PADDLE_TPU_FLIGHT_STEPS", 16384))
        self.engine_name = engine_name
        self.role = role
        self.maxlen = max(1, int(maxlen))
        self.records = collections.deque(maxlen=self.maxlen)
        self.dropped = 0
        self.steps = 0
        self.op_scopes = None   # `register_flight_recorder` sets it

    def note(self, **fields):
        if len(self.records) == self.maxlen:
            self.dropped += 1
        self.records.append(fields)
        self.steps += 1

    def chrome_events(self):
        """One "X" slice per step, its engine phases nested under it in
        the order they run (a multi-tick step flushes the previous
        dispatch's notes before its wait, not after its emit: its
        `engine.note` is drawn late), and the frontend's phases of the
        gap before it beside it: hop-out and publish from the end of
        the previous step, admit and hop-in up to this one."""
        pid = os.getpid()
        tid = f"engine:{self.engine_name}"
        out = []
        before, in_step, after = (HOST_PHASES[:2], HOST_PHASES[2:8],
                                  HOST_PHASES[8:])

        def lay(names, r, at, back=False):
            spans = [(n, r[_phase_field(n)]) for n in names
                     if _phase_field(n) in r]
            if back:
                at -= sum(d for _, d in spans)
            for name, dur in spans:
                out.append({"name": name, "ph": "X", "ts": at * 1e6,
                            "dur": dur * 1e6, "pid": pid, "tid": tid,
                            "args": {}})
                at += dur

        for r in self.records:
            ts = r.get("ts", 0.0)
            args = {k: v for k, v in r.items()
                    if k not in ("ts", "dur")}
            out.append({"name": f"step[{self.role}]", "ph": "X",
                        "ts": ts * 1e6,
                        "dur": r.get("dur", 0.0) * 1e6,
                        "pid": pid, "tid": tid, "args": args})
            lay(in_step, r, ts)
            if "gap_before" in r:
                lay(after, r, ts - r["gap_before"])
                lay(before, r, ts, back=True)
            else:
                lay(after + before, r, ts, back=True)
        return out

    def summary(self):
        recs = list(self.records)
        agg = {"engine": self.engine_name, "role": self.role,
               "steps": self.steps, "dropped": self.dropped}
        if recs:
            agg["prefill_tokens"] = sum(
                r.get("prefill_tokens", 0) for r in recs)
            agg["decode_tokens"] = sum(
                r.get("decode_tokens", 0) for r in recs)
            durs = [r.get("dur", 0.0) for r in recs]
            agg["step_ms_mean"] = sum(durs) / len(durs) * 1e3
            agg["step_ms_max"] = max(durs) * 1e3
            # device-resident multi-tick dispatches (ISSUE 18): ticks
            # the while_loop ran per dispatch plus the event-bitmask
            # exit taxonomy — absent on single-tick engines, whose
            # records carry no tick fields
            ticks = [r["ticks"] for r in recs if "ticks" in r]
            if ticks:
                agg["dispatches"] = len(ticks)
                agg["ticks_total"] = sum(ticks)
                agg["ticks_per_dispatch_mean"] = (
                    sum(ticks) / len(ticks))
                agg["early_exit_finish"] = sum(
                    r.get("early_exit_finish", 0) for r in recs)
                agg["early_exit_overflow"] = sum(
                    r.get("early_exit_overflow", 0) for r in recs)
                agg["host_stall_s"] = sum(
                    r.get("host_stall", 0.0) for r in recs)
        return agg


# ----------------------------------------------- profiler registration
def _chrome_source():
    events = []
    for rec in flight_recorders():
        events.extend(rec.chrome_events())
    events.extend(TRACER.chrome_events())
    return events


def _summary_section():
    parts = []
    tbl = TRACER.summary_table()
    if tbl:
        parts.append(tbl)
    flights = [rec.summary() for rec in flight_recorders()
               if rec.steps]
    if flights:
        lines = ["---- step flight recorders (serving.tracing) ----",
                 f"{'Engine':14s} {'Role':8s} {'Steps':>7s} "
                 f"{'Prefill':>8s} {'Decode':>8s} {'ms/step':>8s}"]
        for f in sorted(flights, key=lambda f: f["engine"]):
            lines.append(
                f"{f['engine']:14s} {f['role']:8s} {f['steps']:>7d} "
                f"{f.get('prefill_tokens', 0):>8d} "
                f"{f.get('decode_tokens', 0):>8d} "
                f"{f.get('step_ms_mean', 0.0):>8.2f}")
        parts.append("\n".join(lines))
    return "\n\n".join(parts)


from .. import profiler as _profiler  # noqa: E402  (cycle-safe: the
# profiler package never imports serving; registration at import time
# is what lets export_chrome_tracing/summary() see these stores)
_profiler.register_chrome_source(_chrome_source)
_profiler.register_summary_section(_summary_section)
