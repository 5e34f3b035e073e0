"""Async multi-tenant serving frontend over the continuous-batching engine.

The ingress layer the engine was missing: PR 2-3 built a synchronous
`ServingEngine` that a single caller drives (`generate_batch` blocks
until every request finishes). `ServingFrontend` turns it into a
service: an asyncio API (`submit()` awaits the full completion,
`stream()` yields per-token) over ONE background step-loop task that
drives the engine's single compiled mixed step, with

* **admission + backpressure** — a bounded `batcher.FairQueue`;
  `submit`/`stream` await for space when the frontend is saturated
  instead of growing an unbounded queue, and lanes are served
  round-robin per tenant so one chatty tenant cannot starve the rest;
* **cancellation** — cancelling the consumer (or `handle.cancel()`)
  reclaims the request's slot, KV blocks and prefix-cache locks at the
  next step boundary;
* **deadlines** — `timeout=` maps to the scheduler's absolute deadline;
  expiry surfaces as `DeadlineExceeded` on the awaiting caller.

Threading model: ALL frontend and engine state is mutated from the
event-loop thread, except `engine.step()` itself which runs in the
default executor so the loop stays responsive during device work.
While a step is in flight the loop only ever *flags* intent
(submissions land in the fair queue, cancellations set a bool); the
step-loop task applies those flags between steps. That keeps the
engine single-threaded in effect — no locks, and the mixed step still
compiles exactly once.

Outputs are token-identical to the cache-off, single-request
`generate()` path: the frontend adds scheduling, never math
(tests/test_frontend.py asserts parity and the single compile).
"""
from __future__ import annotations

import asyncio

from . import tracing as _tracing
from .batcher import FairQueue

_DONE = object()


class DeadlineExceeded(Exception):
    """The request's deadline passed before it finished."""


class RequestCancelled(Exception):
    """The request was cancelled before it finished."""


class FrontendClosed(Exception):
    """The frontend was stopped while the request was in flight."""


class RequestMigrated(Exception):
    """The request left this replica mid-stream (prefill handoff or a
    load-shedding migration). Carries the `MigrationTicket` — KV block
    payload plus host state — the router re-submits elsewhere; tokens
    already streamed stay delivered (the ticket's `output` includes
    them, so the destination publishes only what comes after)."""

    def __init__(self, ticket):
        super().__init__("request migrated away")
        self.ticket = ticket


class FrontendHandle:
    """One in-flight request as seen by a caller."""

    def __init__(self, prompt, max_new_tokens, tenant, deadline,
                 adapter_id=None, trace_id=None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.tenant = tenant
        self.deadline = deadline
        self.adapter_id = adapter_id      # LoRA adapter (None = base)
        # fleet-wide tracing (serving.tracing): the router mints the
        # trace id at dispatch and it rides the handle to the engine
        # submit, so the request's spans stitch onto the fleet trace
        self.trace_id = trace_id
        self.req = None               # scheduler Request once admitted
        self.queue = asyncio.Queue()  # tokens, then _DONE / exception
        self.published = 0
        self.cancel_requested = False
        self.terminal = False
        # disaggregated serving (docs/SERVING.md): inbound migrations
        # carry their ticket until engine admission; prefill handoffs
        # stream completed blocks through `on_blocks`; `shed()` flags
        # live decodes for extraction at the next step boundary
        self.ticket = None
        self.on_blocks = None
        self.extract_requested = False

    @property
    def tokens(self):
        """Tokens generated so far (live view once admitted)."""
        return list(self.req.output) if self.req is not None else []

    def cancel(self):
        """Request cancellation; applied at the next step boundary."""
        self.cancel_requested = True


class ServingFrontend:
    """Bounded async ingress over one `ServingEngine`.

    Usage::

        frontend = ServingFrontend(engine, max_pending=64)
        async with frontend:
            toks = await frontend.submit(prompt, max_new_tokens=64)
            async for tok in frontend.stream(prompt2, tenant="b"):
                ...
    """

    #: backoff for unproductive iterations (engine reported no work
    #: done while work remained — e.g. expiry-only rounds): the loop
    #: sleeps IDLE_BACKOFF_S doubling up to IDLE_BACKOFF_MAX_S instead
    #: of hammering the executor with no-op engine.step calls
    IDLE_BACKOFF_S = 0.001
    IDLE_BACKOFF_MAX_S = 0.05

    def __init__(self, engine, *, max_pending=256, engine_queue_depth=None):
        self.engine = engine
        self.step_calls = 0           # executor dispatches of engine.step
        self._fair = FairQueue(max_pending)
        # how many requests may sit in the ENGINE's FIFO beyond the
        # resident slots: deep enough to keep every slot busy the
        # moment one frees, shallow enough that fairness (which lives
        # in the frontend lanes) still governs admission order
        self._engine_depth = (engine.kv.max_slots if engine_queue_depth
                              is None else int(engine_queue_depth))
        self._live = []               # handles admitted to the engine
        self._wake = asyncio.Event()
        self._space = asyncio.Event()
        self._task = None
        self._closed = False

    # ---------------------------------------------------------- lifecycle
    async def start(self):
        if self._task is None:
            self._closed = False
            self._task = asyncio.get_running_loop().create_task(
                self._step_loop())
        return self

    async def stop(self):
        """Stop the step loop; in-flight requests get FrontendClosed."""
        self._closed = True
        self._wake.set()
        self._space.set()     # release backpressure waiters to fail
        if self._task is not None:
            try:
                await self._task
            finally:
                self._task = None
        err = FrontendClosed("frontend stopped")
        while True:
            handle = self._fair.pop()
            if handle is None:
                break
            self._finish_handle(handle, err)
        for handle in list(self._live):
            if handle.req is not None:
                self.engine.cancel(handle.req)
            self._finish_handle(handle, err)
        self._live.clear()
        # what the stopped cycle and the cancels' drain marked belongs
        # to no later step: none of it stays for the next flight record
        self.engine.phases.close()
        self.engine.phases.take()

    async def __aenter__(self):
        return await self.start()

    async def __aexit__(self, *exc):
        await self.stop()

    # ------------------------------------------------------------ intake
    async def _enqueue(self, prompt, max_new_tokens, tenant, timeout,
                       adapter_id=None, trace_id=None):
        deadline = (self.engine.clock() + float(timeout)
                    if timeout is not None else None)
        handle = FrontendHandle(list(prompt), int(max_new_tokens),
                                str(tenant), deadline,
                                adapter_id=adapter_id,
                                trace_id=trace_id)
        return await self._enqueue_handle(handle)

    async def _enqueue_handle(self, handle):
        if self._closed or self._task is None:
            raise FrontendClosed("frontend is not running")
        deadline = handle.deadline
        while not self._fair.push(handle.tenant, handle):
            # bounded queue full: wait until the step loop drains
            # space — but never past the request's own deadline (a
            # handle not yet in the fair queue is invisible to the
            # admission-time expiry checks)
            self._space.clear()
            if deadline is not None:
                remaining = deadline - self.engine.clock()
                if remaining <= 0:
                    raise DeadlineExceeded()
                try:
                    await asyncio.wait_for(self._space.wait(), remaining)
                except asyncio.TimeoutError:
                    raise DeadlineExceeded() from None
            else:
                await self._space.wait()
            if self._closed:
                raise FrontendClosed("frontend stopped while waiting")
        self._wake.set()
        return handle

    async def submit(self, prompt, max_new_tokens=32, *,
                     tenant="default", timeout=None, adapter_id=None):
        """Run one request to completion; returns its generated token
        ids. Cancelling the awaiting task cancels the request.
        `adapter_id` selects a registered LoRA adapter (None = base)."""
        out = []
        async for tok in self.stream(prompt, max_new_tokens,
                                     tenant=tenant, timeout=timeout,
                                     adapter_id=adapter_id):
            out.append(tok)
        return out

    async def stream(self, prompt, max_new_tokens=32, *,
                     tenant="default", timeout=None, adapter_id=None,
                     on_admitted=None, on_blocks=None, trace_id=None):
        """Async generator of generated tokens, one per decode step
        (speculative acceptance can deliver several per step). Closing
        the generator — or cancelling its consumer — cancels the
        request and reclaims its resources. `on_admitted` (if given)
        is called once the request is in the fair queue — i.e. visible
        to this frontend's own accounting; the router uses it to stop
        double-counting the dispatch in its load estimate.

        `on_blocks` (disaggregated serving) is called after each step
        with a `BlockChunk` of KV blocks the prefill completed since
        the last call — the router ships them ahead to the handoff
        destination. On a prefill-role engine the stream ends with
        `RequestMigrated(ticket)` once the first token is sampled."""
        handle = await self._enqueue(prompt, max_new_tokens, tenant,
                                     timeout, adapter_id=adapter_id,
                                     trace_id=trace_id)
        handle.on_blocks = on_blocks
        if on_admitted is not None:
            on_admitted()
        async for tok in self._consume(handle):
            yield tok

    async def stream_ticket(self, ticket, *, on_admitted=None):
        """Admit a migrated-in request (disaggregated serving): the
        ticket's KV blocks are imported at engine admission and tokens
        stream from where the source replica left off — `published`
        starts past the ticket's already-delivered output, so nothing
        is re-sent. Deadline/tenant/backpressure semantics match
        `stream` (the ticket carries the original absolute deadline)."""
        handle = FrontendHandle(list(ticket.prompt),
                                int(ticket.max_new_tokens),
                                str(ticket.tenant), ticket.deadline,
                                adapter_id=getattr(ticket,
                                                   "adapter_id", None),
                                trace_id=getattr(ticket,
                                                 "trace_id", None))
        handle.ticket = ticket
        handle.published = len(ticket.output)
        await self._enqueue_handle(handle)
        if on_admitted is not None:
            on_admitted()
        async for tok in self._consume(handle):
            yield tok

    async def _consume(self, handle):
        try:
            while True:
                item = await handle.queue.get()
                if item is _DONE:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            if not handle.terminal:
                handle.cancel()
                self._wake.set()

    # --------------------------------------------------------- step loop
    def _finish_handle(self, handle, outcome):
        """Publish the terminal outcome (sentinel or exception)."""
        if handle.terminal:
            return
        handle.terminal = True
        handle.queue.put_nowait(outcome)

    def _apply_cancellations(self):
        # cancelled before admission: drop from the fair queue now so
        # the slot of backpressure it held frees immediately
        queued = [h for h in self._fair.items() if h.cancel_requested]
        for handle in queued:
            self._fair.remove(handle)
            self._finish_handle(handle, RequestCancelled())
        if queued:
            self._space.set()
        for handle in list(self._live):
            if handle.cancel_requested and not handle.terminal:
                self.engine.cancel(handle.req)
                self._live.remove(handle)
                self._finish_handle(handle, RequestCancelled())

    def _admit_pending(self):
        """Fair-drain the frontend queue into the engine, keeping its
        FIFO shallow so frontend fairness governs admission order."""
        sch = self.engine.scheduler
        now = self.engine.clock()
        while len(sch.queue) < self._engine_depth:
            handle = self._fair.pop()
            if handle is None:
                break
            if handle.cancel_requested:
                self._finish_handle(handle, RequestCancelled())
                continue
            # >= (not >): the idle wait below sleeps max(0, deadline -
            # now), so a handle expiring exactly NOW must be expired on
            # this pass — a strict > would zero-delay-loop until the
            # clock ticks past it (forever under a frozen test clock)
            if handle.deadline is not None and now >= handle.deadline:
                self._finish_handle(handle, DeadlineExceeded())
                continue
            try:
                if handle.ticket is not None:
                    # migrated-in request: block import happens at the
                    # scheduler's next plan, not here — engine state
                    # only mutates between steps either way
                    handle.req = self.engine.submit_migrated(
                        handle.ticket)
                    handle.ticket = None
                else:
                    handle.req = self.engine.submit(
                        handle.prompt, handle.max_new_tokens,
                        deadline=handle.deadline, tenant=handle.tenant,
                        adapter_id=handle.adapter_id,
                        trace_id=handle.trace_id)
            except ValueError as e:      # oversized / empty prompt /
                self._finish_handle(handle, e)  # mismatched KV geometry
                continue
            self._live.append(handle)
        self._space.set()

    def shed(self, n=1):
        """Flag up to `n` live decodes for extraction at the next step
        boundary (load shedding, disaggregated serving): each victim's
        stream ends with `RequestMigrated(ticket)` and the router
        re-places it on a lighter replica. Victims are the decodes with
        the MOST remaining work (max_new_tokens - generated), so one
        migration sheds the most future load; requests that have not
        produced a token yet are skipped (nothing to hand off
        mid-stream — they are cheaper to let finish prefill first).
        Returns how many were flagged."""
        cands = [h for h in self._live
                 if not h.terminal and not h.cancel_requested
                 and not h.extract_requested and h.req is not None
                 and h.req.state == "decode" and h.req.output]
        cands.sort(key=lambda h: (
            -(h.req.max_new_tokens - len(h.req.output)),
            h.req.arrival))
        picked = cands[:int(n)]
        for h in picked:
            h.extract_requested = True
        if picked:
            self._wake.set()
        return len(picked)

    def _apply_extractions(self):
        """Extract shed-flagged decodes (between steps, loop thread —
        the same engine-mutation discipline as cancellation). Tokens
        generated before the flag were published by the previous
        `_publish`, so the migration sentinel is strictly ordered
        after every delivered token. An engine that dispatches ahead
        holds a step's tokens unread between steps: they are read back
        and published first, so the ticket carries, and the client
        has, every token computed."""
        if any(h.extract_requested and not h.terminal
               for h in self._live):
            self.engine.drain()
            self._publish()
        for handle in list(self._live):
            if not handle.extract_requested or handle.terminal:
                continue
            req = handle.req
            if req is None or req.state != "decode" or not req.output:
                continue                 # not extractable (yet)
            self._live.remove(handle)
            ticket = self.engine.extract_request(req)
            self._finish_handle(handle, RequestMigrated(ticket))

    def _stream_blocks(self):
        """Ship newly completed prefill blocks for handoff-destined
        requests (runs right after each step, before `_publish`, so
        the extraction tail stays minimal)."""
        for handle in self._live:
            if handle.on_blocks is None or handle.terminal:
                continue
            req = handle.req
            if req is None or req.slot < 0 or req.state != "prefill":
                continue
            chunk = self.engine.export_unshipped(req)
            if chunk is not None:
                handle.on_blocks(chunk)

    def _publish(self):
        """Push newly generated tokens + terminal states to waiters.
        On a prefill-role engine, requests that reached the "handoff"
        state (first token sampled) are extracted HERE — their stream
        delivers the token(s) first, then `RequestMigrated(ticket)`."""
        for handle in list(self._live):
            req = handle.req
            n = len(req.output)
            if n > handle.published:
                for tok in req.output[handle.published:n]:
                    handle.queue.put_nowait(tok)
                handle.published = n
            if req.done:
                self._live.remove(handle)
                if req.state == "finished":
                    self._finish_handle(handle, _DONE)
                elif req.state == "expired":
                    self._finish_handle(handle, DeadlineExceeded())
                else:
                    self._finish_handle(handle, RequestCancelled())
            elif req.state == "handoff":
                self._live.remove(handle)
                ticket = self.engine.extract_request(req)
                self._finish_handle(handle, RequestMigrated(ticket))

    def _next_pending_deadline(self):
        # handles waiting in the frontend queue never reach the
        # scheduler's expiry sweep, so the idle wait must wake for them
        soonest = None
        for h in self._fair.items():
            if h.deadline is not None and \
                    (soonest is None or h.deadline < soonest):
                soonest = h.deadline
        return soonest

    async def _step_loop(self):
        try:
            await self._step_loop_inner()
        except Exception as e:  # noqa: BLE001 — step/engine failure
            # a dying step loop must not strand awaiting callers on
            # queues nobody will ever fill: fail every handle with the
            # error and close the frontend
            self._closed = True
            self._space.set()
            while True:
                handle = self._fair.pop()
                if handle is None:
                    break
                self._finish_handle(handle, e)
            for handle in list(self._live):
                self._finish_handle(handle, e)
            self._live.clear()
        finally:
            # stopped mid-cycle: no host phase stays open
            self.engine.phases.close()

    def _traced_step(self):
        """`engine.step` on the executor thread, the hop back to the
        loop marked from the instant it returns."""
        did = self.engine.step()
        self.engine.phases.mark("frontend.hop_out")
        return did

    async def _step_loop_inner(self):
        loop = asyncio.get_running_loop()
        ph = self.engine.phases
        backoff = 0.0
        while not self._closed:
            # host phases of the cycle (tracing.HOST_PHASES): the
            # engine marks its own inside step(); sampled once a cycle
            trace_on = _tracing._enabled
            if trace_on:
                ph.mark("frontend.admit", self.engine.steps_run)
            elif ph.name is not None:
                # tracing went off mid-cycle: drop the open phase
                ph.close()
                ph.take()
            self._apply_cancellations()
            self._apply_extractions()
            self._admit_pending()
            if self.engine.scheduler.has_work:
                self.step_calls += 1
                if trace_on:
                    ph.mark("frontend.hop_in")
                did = await loop.run_in_executor(
                    None,
                    self._traced_step if trace_on else self.engine.step)
                if trace_on:
                    ph.mark("frontend.publish")
                self._stream_blocks()
                self._publish()
                if did:
                    backoff = 0.0
                elif self.engine.scheduler.has_work:
                    # engine stall: the block pool cannot cover the
                    # resident working set (ServingEngine.run raises
                    # here) — fail the affected requests rather than
                    # spin
                    err = RuntimeError(
                        "serving engine stalled: KV block pool too "
                        "small for the resident working set")
                    for handle in list(self._live):
                        self.engine.cancel(handle.req)
                        self._live.remove(handle)
                        self._finish_handle(handle, err)
                else:
                    # unproductive round (no tokens, no expiries, and
                    # the work drained between the check and the step):
                    # back off instead of spinning the executor
                    backoff = min(backoff * 2 or self.IDLE_BACKOFF_S,
                                  self.IDLE_BACKOFF_MAX_S)
                    await asyncio.sleep(backoff)
                continue
            # idle: the engine has no work, which means _admit_pending
            # drained the fair queue (engine FIFO empty => depth free),
            # so sleep until a submission or cancel wakes us — or the
            # soonest frontend-held deadline passes (those handles
            # never reach the scheduler's expiry sweep). A multi-tick
            # engine may still hold the last dispatch's deferred
            # metrics/flight record — publish before sleeping so
            # scrapes during idle see the drained totals.
            flush = getattr(self.engine, "flush_observability", None)
            if flush is not None:
                flush()
            if trace_on:
                ph.close()           # waiting for work is no phase
            self._wake.clear()
            soonest = self._next_pending_deadline()
            try:
                if soonest is not None:
                    delay = max(0.0, soonest - self.engine.clock())
                    await asyncio.wait_for(self._wake.wait(), delay)
                else:
                    await self._wake.wait()
            except asyncio.TimeoutError:
                pass
