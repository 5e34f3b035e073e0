"""Continuous-batching scheduler.

FIFO admission over a fixed set of slots, chunked prefill under a
per-step token budget, and block-pressure preemption against the paged
KV cache:

* **Admission** — requests queue FIFO; a request is admitted to the
  lowest free slot as soon as one exists. Prefill then streams the
  prompt through the mixed step in budget-sized chunks (so one giant
  prompt cannot starve running decodes: decodes are planned FIRST each
  step, prefill fills the remaining budget).
* **Preemption** — when a decode cannot get its next KV block, the
  scheduler evicts the decode holding the MOST blocks (the
  longest-running sequence — freeing the most memory per eviction;
  ties break toward the latest arrival, preserving FIFO fairness).
  The victim re-enters the FRONT of the queue with its generated
  prefix folded into the prompt, so a later re-prefill resumes the
  sequence exactly. Prefill never preempts (only free blocks), which
  keeps admission from thrashing running decodes.
* **Deadlines** — an optional absolute deadline per request; queued or
  resident requests past it are expired and their blocks reclaimed.
* **Migration** (disaggregated serving, docs/SERVING.md) — a request
  arriving from another replica (`submit_migrated`) joins the FRONT of
  the queue carrying its KV payload; admission IMPORTS the blocks into
  a slot (`kv.import_into_slot`) instead of prefilling, and `extract`
  releases a resident request migrating away (its blocks were exported
  by the engine first). Prefill-role engines park completed prompts in
  the `"handoff"` state, which plans neither prefill nor decode.

The scheduler is pure host-side bookkeeping — it orchestrates through
the kv-cache API (which owns any device work, like the import scatter)
and never touches device arrays itself; the engine turns its plans
into the fixed-shape step inputs.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Optional

from . import batcher
from . import tracing as _tracing


@dataclasses.dataclass(eq=False)   # identity semantics: requests live
class Request:                     # in sets/queues across state moves
    req_id: int
    prompt: list                      # original prompt token ids
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    deadline: Optional[float] = None  # absolute time.monotonic()
    arrival: float = 0.0
    state: str = "queued"
    # queued|prefill|handoff|decode|finished|expired|cancelled|migrated
    slot: int = -1
    output: list = dataclasses.field(default_factory=list)
    fed: int = 0                      # runtime-prompt tokens fed so far
    preemptions: int = 0
    cache_hit_tokens: int = 0         # prefix-cache tokens skipped
    tenant: str = "default"           # frontend fairness bucket
    # multi-LoRA (serving.adapters): the registered adapter this
    # request decodes under (None = base model) and, while resident,
    # the device slot its pin holds (0 = the reserved null slot)
    adapter_id: object = None
    adapter_slot: int = 0
    # disaggregated serving (serving.distributed.transport): inbound
    # migrations carry their KV payload until admission imports it;
    # prefill-role engines track which full blocks were already
    # streamed ahead so extraction ships only the tail
    ticket: Optional[object] = None
    shipped_blocks: int = 0
    # fleet-wide request tracing (serving.tracing, ISSUE 16): minted at
    # router dispatch and carried across migrations via the ticket so
    # one stitched trace covers every replica the request touched
    trace_id: Optional[str] = None
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    _last_token_time: Optional[float] = None
    # decoding by blocks (`Scheduler.block_decoding`): the current
    # block's first position (= the slot's committed length), its L
    # token ids (a masked position holds the mask id), which positions
    # are decided (by POSITION: a prompt's or a candidate's id may equal
    # the mask id), and the denoise passes it has had: the state after
    # the last pass READ BACK (a pass in flight moves the device's copy
    # first). Kept across a preemption: what was decided is never
    # decided again
    block_start: int = -1
    block_tokens: Optional[list] = None
    block_decided: Optional[list] = None
    block_passes: int = 0
    # tokens of this request that a dispatched step has sampled and the
    # host has not read back yet, or (decoding by blocks) passes over its
    # block (0 or 1: the engine's pipeline is one step deep; docs/
    # SERVING.md "Dispatching ahead"). The engine counts it up at
    # dispatch and down at readback
    in_flight: int = 0

    @property
    def runtime_prompt(self):
        """What prefill must feed: the prompt plus any tokens already
        generated before a preemption dropped the KV blocks."""
        return self.prompt + self.output

    @property
    def done(self):
        return self.state in ("finished", "expired", "cancelled")


@dataclasses.dataclass
class Plan:
    decode: list        # [(slot, [token ids], first position)]: one id
    #                     a plain decode, the last token and its drafts
    #                     a verify group, a block's L ids (block decoding)
    prefills: list      # [(slot, chunk ndarray, start_pos, completes)]
    expired: list       # requests expired this round

    @property
    def empty(self):
        return not self.decode and not self.prefills


class Scheduler:
    def __init__(self, kv_cache, *, max_slots, token_budget,
                 clock=time.monotonic, draft_k=0, draft_fn=None,
                 device_draft=False, prefix_cache=None,
                 adapter_cache=None, reserve_region=False,
                 prefill_align=1, prefill_chunk=None, block_decoding=None):
        self.kv = kv_cache
        # a model that decodes by blocks (`models.serving_block.
        # BlockDecoding`; None: a token at a time)
        self.block_decoding = block_decoding
        if block_decoding is not None:
            L = block_decoding.block_length
            if int(prefill_align) % L or draft_k or prefix_cache is not None:
                raise ValueError(
                    "block decoding: prefill chunks end on multiples of "
                    f"the block length {L} (prefill_align={prefill_align}"
                    "), and neither drafts nor a prefix cache are built")
        # a model with recurrent (linear) layers: a prompt's prefill
        # chunks end on multiples of this many tokens (all but its
        # last), so that the chunked recurrence cuts a prompt at the
        # same positions whatever rides with it in a step, and a
        # request served alone, in company or again after a preemption
        # computes the same numbers bit for bit
        self.prefill_align = int(prefill_align)
        # a prefill chunk's size before that cut, from (remaining,
        # budget left): the largest power of two by default
        self.prefill_chunk = prefill_chunk or batcher.prefill_chunk
        self.max_slots = max_slots
        self.token_budget = token_budget
        self.clock = clock
        self.queue = collections.deque()
        self.slots = [None] * max_slots
        self._ids = itertools.count()
        self.preemption_count = 0
        # speculative decoding: each decode may carry up to draft_k
        # proposed tokens (draft_fn(seq) -> list of draft_k ints); the
        # engine verifies them and advances slot_lens itself, so
        # note_fed leaves decode lengths alone when draft_k > 0
        self.draft_k = int(draft_k)
        self.draft_fn = draft_fn
        # device-resident drafting (ISSUE 19): the multi-tick engine
        # proposes drafts INSIDE the while_loop from the on-device
        # token ring, so plan() emits plain single-token decode groups
        # ([last] only — the device widens them) while the reserved-
        # region budget and note_fed/note_accept bookkeeping keep the
        # full draft_k treatment
        self.device_draft = bool(device_draft)
        # radix prefix cache (serving.prefix_cache): admission skips
        # cached prompt heads, prefill completion / finish publish the
        # written blocks for later requests
        self.prefix_cache = prefix_cache
        # multi-LoRA adapter cache (serving.adapters): admission pins
        # the request's adapter into a device slot — and BLOCKS at the
        # queue head when every slot is pinned by in-flight requests;
        # `_free_slot` drops the pin on every release path
        self.adapters = adapter_cache
        # block-sparse decode (ISSUE 15): the engine reserves the
        # per-slot decode region even at draft_k == 0, so prefill
        # budgets must treat it as spoken for exactly like the
        # speculative verify region
        self.reserve_region = bool(reserve_region)
        # replica label the tracing hooks stamp on span events; the
        # owning engine overwrites it with its own name
        self.replica = None
        # while a dispatched step is unread, the engine's `drain` (read
        # it back, emit its tokens; the engine sets it): work, whatever
        # the slots hold, and what `plan` calls where it needs the step's
        # outcome first
        self.drain = None

    # ---------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens, eos_token_id=None,
               deadline=None, tenant="default", adapter_id=None,
               trace_id=None):
        total = len(prompt) + max_new_tokens - 1  # last token never fed
        if self.block_decoding is not None:
            # the last block is fed whole, whatever the horizon
            L = self.block_decoding.block_length
            total = -(-(len(prompt) + max_new_tokens) // L) * L
        if total > self.kv.max_slot_tokens:
            raise ValueError(
                f"request needs {total} cached tokens; a slot holds at "
                f"most {self.kv.max_slot_tokens}")
        if adapter_id is not None and self.adapters is None:
            raise ValueError("request names an adapter but the "
                             "scheduler has no adapter cache")
        now = self.clock()
        req = Request(req_id=next(self._ids), prompt=list(prompt),
                      max_new_tokens=int(max_new_tokens),
                      eos_token_id=eos_token_id, deadline=deadline,
                      arrival=now, submit_time=now, tenant=str(tenant),
                      adapter_id=adapter_id, trace_id=trace_id)
        self.queue.append(req)
        if _tracing._enabled:
            _tracing.on_submit(req, self.replica)
        return req

    def submit_migrated(self, ticket):
        """Queue a request migrated in from another replica: its KV
        payload rides `req.ticket` until a slot frees and the blocks
        fit, then admission IMPORTS the blocks instead of prefilling.
        Joins the FRONT of the queue — like a preemption victim, the
        request is already mid-stream and its caller is watching the
        token gap. Timing fields carry over so TTFT is observed once
        (on the source) and inter-token histograms stay continuous."""
        total = len(ticket.prompt) + int(ticket.max_new_tokens) - 1
        if total > self.kv.max_slot_tokens:
            raise ValueError(
                f"migrated request needs {total} cached tokens; a slot "
                f"holds at most {self.kv.max_slot_tokens}")
        now = self.clock()
        req = Request(req_id=next(self._ids),
                      prompt=list(ticket.prompt),
                      max_new_tokens=int(ticket.max_new_tokens),
                      eos_token_id=ticket.eos_token_id,
                      deadline=ticket.deadline,
                      arrival=now, submit_time=ticket.submit_time,
                      tenant=str(ticket.tenant),
                      output=list(ticket.output),
                      cache_hit_tokens=int(ticket.cache_hit_tokens),
                      preemptions=int(ticket.preemptions),
                      ticket=ticket,
                      adapter_id=getattr(ticket, "adapter_id", None),
                      trace_id=getattr(ticket, "trace_id", None))
        req.first_token_time = ticket.first_token_time
        self.queue.appendleft(req)
        if _tracing._enabled:
            _tracing.on_submit_migrated(req, self.replica, ts=now)
        return req

    @property
    def num_active(self):
        return sum(s is not None for s in self.slots)

    @property
    def has_work(self):
        return bool(self.queue) or self.num_active > 0 \
            or self.drain is not None

    # ------------------------------------------------------- internals
    def _free_slot(self, req):
        if self.prefix_cache is not None:
            self.prefix_cache.unlock_slot(req.slot)
        if self.adapters is not None and req.adapter_id is not None:
            # every release path (finish/preempt/expire/cancel/extract)
            # funnels through here, so each admission's pin is dropped
            # exactly once; the adapter stays resident until LRU
            # eviction needs its slot
            self.adapters.release(req.adapter_id)
            req.adapter_slot = 0
        self.kv.release_slot(req.slot)
        self.slots[req.slot] = None
        req.slot = -1

    def _expire(self, now):
        expired = []
        for req in list(self.queue):
            if req.deadline is not None and now > req.deadline:
                self.queue.remove(req)
                req.state = "expired"
                req.finish_time = now
                expired.append(req)
        for req in list(self.slots):
            if req is not None and req.deadline is not None \
                    and now > req.deadline:
                self._free_slot(req)
                req.state = "expired"
                req.finish_time = now
                expired.append(req)
        if _tracing._enabled:
            for req in expired:
                _tracing.on_terminal(req, "expired", self.replica,
                                     ts=now)
        return expired

    def _acquire_adapter(self, req):
        """Pin the queue head's adapter into a device slot. True on
        success (or no adapter); False = every slot is pinned by
        in-flight requests — admission BLOCKS at the head until one
        finishes (residency gating, never slot corruption)."""
        if self.adapters is None or req.adapter_id is None:
            req.adapter_slot = 0
            return True
        slot_a = self.adapters.acquire(req.adapter_id)
        if slot_a is None:
            return False
        req.adapter_slot = int(slot_a)
        return True

    def _admit(self):
        for slot in range(self.max_slots):
            if not self.queue:
                break
            if self.slots[slot] is None:
                if self.queue[0].ticket is not None:
                    # migrated request at the head: admission imports
                    # its transported KV blocks instead of prefilling.
                    # If the free list (after prefix-cache eviction)
                    # can't cover them yet, it WAITS at the head —
                    # head-of-line priority is deliberate: the request
                    # is mid-stream and resuming it beats admitting
                    # fresh prompts behind it.
                    req = self.queue[0]
                    if not self._acquire_adapter(req):
                        break
                    if not self.kv.import_into_slot(
                            slot, req.ticket.slot_len,
                            req.ticket.chunks):
                        # release the fresh pin so the retry next plan
                        # can't stack a second one
                        if self.adapters is not None \
                                and req.adapter_id is not None:
                            self.adapters.release(req.adapter_id)
                            req.adapter_slot = 0
                        break
                    self.queue.popleft()
                    req.slot = slot
                    req.state = "decode"
                    # the whole runtime prompt's K/V is resident — the
                    # next step feeds output[-1] at position slot_len,
                    # exactly like a post-prefill decode
                    req.fed = len(req.runtime_prompt)
                    req.ticket = None          # payload consumed
                    self.slots[slot] = req
                    if _tracing._enabled:
                        _tracing.on_admitted(req, self.replica,
                                             kind="import",
                                             ts=self.clock())
                    continue
                if not self._acquire_adapter(self.queue[0]):
                    break
                req = self.queue.popleft()
                req.slot = slot
                req.state = "prefill"
                req.fed = 0
                self.slots[slot] = req
                if self.block_decoding is not None:
                    if req.block_tokens is None:
                        # the prompt's whole blocks are prefilled; its
                        # tail starts the first block, decided
                        L = self.block_decoding.block_length
                        self._open_block(
                            req, len(req.prompt) // L * L,
                            req.prompt[len(req.prompt) // L * L:])
                    if req.block_start == 0:
                        req.state = "decode"    # nothing to prefill
                if _tracing._enabled:
                    # a re-prefill resumes a preempted sequence (its
                    # generated prefix folds into the prompt) — a
                    # distinct span kind so queue-wait is observed
                    # only on the FIRST admission
                    kind = ("re_prefill"
                            if (req.output or req.preemptions)
                            else "prefill")
                    _tracing.on_admitted(req, self.replica, kind=kind,
                                         ts=self.clock())
                if self.prefix_cache is not None \
                        and req.adapter_id is None:
                    # cached prompt head: adopt the shared blocks, mark
                    # their K/V as already resident, and start chunked
                    # prefill at the first uncached token. Re-admission
                    # after a preemption rides the same path — the
                    # victim's own published blocks usually cover most
                    # of its re-prefill. Requests under a non-null
                    # adapter BYPASS the prefix cache entirely: their
                    # K/V depends on the adapter, and the radix tree
                    # keys by token ids alone — sharing across
                    # adapters would serve another finetune's cache.
                    hit = self.prefix_cache.lookup_and_adopt(
                        slot, req.runtime_prompt)
                    req.fed = hit
                    req.cache_hit_tokens += hit
                    self.kv.slot_lens[slot] = hit
        return

    def _open_block(self, req, start, decided=()):
        """`req`'s current block is the one from `start`, with the
        tokens `decided` at its first positions and the rest masked."""
        bd = self.block_decoding
        n = len(decided)
        req.block_start = int(start)
        req.block_tokens = [int(t) for t in decided] \
            + [bd.mask_token_id] * (bd.block_length - n)
        req.block_decided = [True] * n + [False] * (bd.block_length - n)
        req.block_passes = 0

    def prefill_target(self, req):
        """The tokens `req`'s prefill feeds: the runtime prompt, or
        (block decoding) what lies before its current block."""
        if self.block_decoding is None:
            return req.runtime_prompt
        return req.runtime_prompt[:req.block_start]

    def _preempt_victim(self, exclude):
        """Evict the decode holding the most blocks (tie: latest
        arrival). Returns the victim or None. The victim re-prefills
        from position 0, which is also what makes a recurrent state
        (a cache with linear layers) start from zero again: the step
        starts a run at position 0 from zeros, nothing is reset here."""
        cands = [r for r in self.slots
                 if r is not None and r.state == "decode"
                 and r not in exclude]
        if not cands:
            return None
        victim = max(cands, key=lambda r: (self.kv.slot_num_blocks(
            r.slot), r.arrival))
        self._free_slot(victim)
        victim.state = "queued"
        victim.fed = 0
        victim.preemptions += 1
        self.preemption_count += 1
        self.queue.appendleft(victim)
        if _tracing._enabled:
            _tracing.on_preempted(victim, self.replica,
                                  ts=self.clock())
        return victim

    # ------------------------------------------------- speculative draft
    def _draft_tokens(self, req, pos):
        """[last_token, d_1..d_k] for one decode's verify group.

        k starts at draft_k and shrinks to what is actually worth
        feeding: never past the request's remaining horizon (a draft
        beyond max_new_tokens could only emit discarded tokens), never
        past the slot's token capacity, and never past what FREE blocks
        can back — draft tokens extend only with free blocks, exactly
        like prefill chunks, so a speculative burst can't preempt a
        neighbour's accepted work."""
        k = min(self.draft_k,
                req.max_new_tokens - len(req.output) - 1,
                self.kv.max_slot_tokens - (pos + 1))
        if k > 0:
            # free-block extension only: shrink k to the free coverage
            while k > 0 and not self.kv.ensure_capacity(
                    req.slot, pos + 1 + k):
                fit = self.kv.fit_tokens(req.slot) - (pos + 1)
                k = min(k - 1, fit) if fit > 0 else 0
        if k <= 0:
            return [req.output[-1]]
        draft = self.draft_fn(req.prompt + req.output)
        return [req.output[-1]] + [int(t) for t in draft[:k]]

    # ------------------------------------------- multi-tick preallocation
    def extend_for_ticks(self, slot, pos, n_ticks):
        """Pre-extend one decode slot's block tables so a multi-tick
        dispatch (engine `ticks_per_dispatch`, docs/SERVING.md) can
        append up to `n_ticks` tokens starting at `pos` without host
        intervention. The first tick's block is already guaranteed by
        `plan()` (with preemption); the extra ticks extend with FREE
        blocks only — exactly the draft/prefill discipline — so a tick
        burst can never evict a neighbour's resident KV. Returns the
        capacity in tokens the dispatch may fill (`cap`, with
        pos + 1 <= cap <= pos + n_ticks); the engine truncates back to
        what was actually emitted at harvest, so the block accounting
        at every dispatch boundary matches a 1-tick engine's."""
        k = min(int(n_ticks) - 1, self.kv.max_slot_tokens - (pos + 1))
        while k > 0 and not self.kv.ensure_capacity(slot, pos + 1 + k):
            fit = self.kv.fit_tokens(slot) - (pos + 1)
            k = min(k - 1, fit) if fit > 0 else 0
        return pos + 1 + max(k, 0)

    # ------------------------------------------------------------ plan
    def plan(self) -> Plan:
        """One engine iteration's work. Mutates scheduler/cache state
        (admissions, block allocation, preemptions, expiries).

        While a dispatched step is unread (`self.drain` is set), a
        decode slot whose newest token is in that step
        (`Request.in_flight`) is fed the sentinel `batcher.PREV_TOKEN`
        at its next position, and the compiled step takes the token from
        the step before it, on the device; a request whose horizon the
        tokens in flight reach is not fed again. Decoding by blocks, a
        block with a pass in flight is fed L sentinels, and the step
        takes the block's ids, decided positions and passes from the
        state the pass in flight hands over; where the host's state
        (one pass stale) already has the block whole, the pass in flight
        is the commit and this one opens the next block. What needs the
        step's OUTCOME first calls `drain()` and then runs as it always
        did: the expiry of a request with a token in flight, and a
        preemption."""
        drain = self.drain
        now = self.clock()
        if drain is not None and any(
                r is not None and r.in_flight and r.deadline is not None
                and now > r.deadline for r in self.slots):
            drain()
            drain = None        # nothing is in flight any more
        expired = self._expire(now)
        # window layers: what lies behind every slot's window goes back
        # first, before anything is allotted. A block released here and
        # allotted again below, while the step before is still running,
        # is safe: that step reads its OWN copy of the tables (the
        # engine's `_pack` copied them into its plan buffer), so the
        # host's tables are no step's input; and the next step's writes
        # to the block follow that step's reads of it, because the next
        # step takes the pools the step before hands back (donated: one
        # buffer, in program order on the device)
        self.kv.release_behind_windows()
        self._admit()

        decode = []
        protected = set()
        drained = False
        # decodes first, oldest arrival first: block pressure falls on
        # the youngest/longest sequences, never the queue head
        decoders = sorted(
            (r for r in self.slots
             if r is not None and r.state == "decode"),
            key=lambda r: r.arrival)
        blocks = self.block_decoding is not None
        for req in decoders:
            if req.slot < 0:    # preempted by an earlier iteration
                continue
            if req.in_flight and not blocks and len(req.output) \
                    + req.in_flight >= req.max_new_tokens:
                # the token in flight ends it by LENGTH: the host can
                # count, and the slot is not fed again
                continue
            # position of the token being fed = tokens already cached
            pos, width = int(self.kv.slot_lens[req.slot]), 1
            if blocks:
                # a block's rows are all written, every pass, from the
                # block's first position: the next block's, where the
                # pass in flight is the commit (the same position after
                # a drain below: the commit has landed by then)
                width = self.block_decoding.block_length
                pos = req.block_start + width * (
                    req.in_flight and all(req.block_decided))
            while not self.kv.ensure_capacity(req.slot, pos + width):
                if drain is not None:
                    # the pool is dry with a step unread: a request it
                    # ends gives blocks back, and a victim must carry
                    # every token it was given. Read it back, try again
                    drain()
                    drain, drained = None, True
                    if req.slot < 0:
                        break
                    continue
                if self._preempt_victim(protected | {req}) is None:
                    # nothing left to evict: preempt THIS decode
                    self._preempt_victim(protected)
                    break
            if req.slot < 0:
                continue
            protected.add(req)
            if blocks:
                ids = [batcher.PREV_TOKEN] * width if req.in_flight \
                    else list(req.block_tokens)
                decode.append((req.slot, ids, pos))
            elif self.draft_k > 0 and not self.device_draft:
                decode.append((req.slot,
                               self._draft_tokens(req, pos), pos))
            elif self.draft_k > 0:
                # device drafting: feed only the last accepted token —
                # the engine's extend_for_ticks preallocation covers
                # the verify burst, and the loop body widens the group
                decode.append((req.slot, [req.output[-1]], pos))
            else:
                # the newest token: the host's, or still on the device
                last = batcher.PREV_TOKEN if req.in_flight \
                    else req.output[-1]
                decode.append((req.slot, [last], pos))
        if drained:
            # the drain ended requests (by EOS) that were planned before
            # it: their slots are free
            decode = [e for e in decode if self.slots[e[0]] is not None]

        # with speculation (or the sparse decode region) the region is
        # RESERVED up front (see batcher.pack_step) — prefill budget
        # never depends on the mix
        reserved = sum(len(toks) for _, toks, _ in decode) \
            if self.draft_k == 0 and not self.reserve_region \
            else self.max_slots * (self.draft_k + 1)
        budget_left = self.token_budget - reserved
        prefills = []
        prefillers = sorted(
            (r for r in self.slots
             if r is not None and r.state == "prefill"),
            key=lambda r: r.arrival)
        for req in prefillers:
            if budget_left <= 0:
                break
            tokens = self.prefill_target(req)
            remaining = len(tokens) - req.fed
            def cut(n):     # a chunk that does not end the prompt
                return n if n >= remaining else \
                    n // self.prefill_align * self.prefill_align

            chunk = cut(self.prefill_chunk(remaining, budget_left))
            # prefill only uses FREE blocks — shrink to what fits
            while chunk > 0 and not self.kv.ensure_capacity(
                    req.slot, req.fed + chunk):
                # blocks of BOTH kinds, where the cache has two
                fit = self.kv.fit_tokens(req.slot) - req.fed
                chunk = cut(min(chunk - 1, fit)) if fit > 0 else 0
            if chunk <= 0:
                continue
            import numpy as np
            arr = np.asarray(tokens[req.fed:req.fed + chunk], np.int32)
            completes = req.fed + chunk == len(tokens)
            prefills.append((req.slot, arr, req.fed, completes))
            req.fed += chunk
            budget_left -= chunk
        return Plan(decode=decode, prefills=prefills, expired=expired)

    # ------------------------------------------------- post-step hooks
    def note_fed(self, plan: Plan):
        """Advance slot lengths for every token the step consumed.

        Speculative decodes are NOT advanced here: how far a verify
        group really got is only known after the engine reads the
        accept length back, so `note_accept` owns that bookkeeping; nor
        are blocks: a block grows its slot when the host learns that a
        pass was its commit (`note_commit`)."""
        if self.block_decoding is None and self.draft_k == 0:
            for slot, _toks, pos in plan.decode:
                self.kv.slot_lens[slot] = pos + 1
        for slot, chunk, start, completes in plan.prefills:
            self.kv.slot_lens[slot] = start + len(chunk)
            if completes and self.prefix_cache is not None:
                # the whole prompt's K/V is resident now — publish its
                # full blocks so concurrent same-prefix requests hit
                # (base-model requests only: adapter K/V must never
                # enter the token-keyed tree)
                req = self.slots[slot]
                if req is not None and req.adapter_id is None:
                    self.prefix_cache.insert(slot, req.runtime_prompt)

    def note_commit(self, req):
        """The pass that fed `req`'s block with nothing masked has been
        read back: it was the COMMIT, the block's K/V is final, the slot
        grows by it and the next block opens."""
        end = req.block_start + self.block_decoding.block_length
        self.kv.slot_lens[req.slot] = end
        self._open_block(req, end)

    def note_accept(self, slot, new_len):
        """Record a verify group's outcome: `new_len` tokens of the
        slot are cached and valid; blocks allocated for rejected draft
        tokens beyond it are rolled back. Returns blocks freed."""
        self.kv.slot_lens[slot] = new_len
        return self.kv.truncate_slot(slot, new_len)

    def finish(self, req, now=None):
        req.state = "finished"
        req.finish_time = self.clock() if now is None else now
        if self.prefix_cache is not None and req.slot >= 0 \
                and req.adapter_id is None:
            # publish prompt + generated history (chat-turn reuse);
            # only tokens whose K/V was actually written count — the
            # last emitted token never fed the step. Cut at the tokens
            # EMITTED: a request that ended on EOS with the next step
            # already dispatched was fed one row more (`slot_lens`
            # counts it), whose K/V belongs to no request
            n = min(int(self.kv.slot_lens[req.slot]),
                    len(req.prompt) + len(req.output) - 1)
            self.prefix_cache.insert(req.slot,
                                     (req.prompt + req.output)[:n])
        self._free_slot(req)
        if _tracing._enabled:
            _tracing.on_terminal(req, "finished", self.replica,
                                 ts=req.finish_time)

    def extract(self, req, now=None):
        """Release a resident request that is migrating away: its slot,
        blocks and prefix locks are reclaimed here (the engine exported
        the block payload FIRST), and the request reaches the terminal-
        for-this-replica state "migrated" — it keeps producing tokens,
        just on another engine. Shared prefix blocks the slot adopted
        stay cached (refcounted), so the source replica keeps serving
        the prefix to future same-head requests."""
        if req.slot < 0:
            raise ValueError(f"request {req.req_id} is not resident")
        self._free_slot(req)
        req.state = "migrated"
        req.finish_time = self.clock() if now is None else now

    def cancel(self, req, now=None):
        """Abort a queued or resident request: its blocks (and prefix
        locks) are reclaimed and it never produces another token.
        Returns False when the request already reached a terminal
        state."""
        if req.done:
            return False
        if req.state == "queued":
            try:
                self.queue.remove(req)
            except ValueError:
                return False
        elif req.slot >= 0:
            self._free_slot(req)
        req.state = "cancelled"
        req.finish_time = self.clock() if now is None else now
        if _tracing._enabled:
            _tracing.on_terminal(req, "cancelled", self.replica,
                                 ts=req.finish_time)
        return True
