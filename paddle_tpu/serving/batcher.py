"""Token-budget batching + sampling heads for the serving engine.

This module owns the pieces `incubate/nn/generation.py` and the
continuous-batching engine share (generation.py imports them from here):

* `SamplingConfig` / `select_token` — the greedy/sampling head applied
  to one step's logits, device-side.
* `next_pow2` / `round_up` — the power-of-two shape discipline every
  compiled entry point uses so shapes come from a tiny closed set.
* `pack_step` — pack one engine iteration (decode tokens + prefill
  chunks) into the FIXED `[token_budget]` flat-token layout of the
  mixed step, so admission/eviction never changes a compiled shape.

The flat-token step protocol (the "Ragged Paged Attention" shape
discipline — one compiled program serves a churning request mix):

    token_ids    [T] int32  — decode tokens and prefill-chunk tokens,
                              concatenated; 0 past num_tokens
    slot_ids     [T] int32  — owning slot per token; -1 = padding
    positions    [T] int32  — position of the token in its sequence
    sample_index [S] int32  — per slot, the index in [0, T) of the
                              token whose hidden state samples that
                              slot's next token; -1 = no sample this
                              step (mid-prefill). `[S, L]` for a model
                              that decodes by blocks of L positions: the
                              rows of the slot's block, every one a
                              sample row

Every array has the same shape every step. In the engine they are
views of ONE flat int32 buffer (`PlanLayout`, `PlanBuffers`) that also
holds a copy of the block tables (and the per-token adapter ids): the
whole plan is one upload a step.
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    strategy: str = "greedy"       # "greedy" | "sampling"
    temperature: float = 1.0
    top_k: int = 0                 # 0 = off
    top_p: float = 1.0             # 1.0 = off
    repetition_penalty: float = 1.0   # 1.0 = off (HF semantics)
    presence_penalty: float = 0.0     # 0.0 = off (additive, one-shot)
    frequency_penalty: float = 0.0    # 0.0 = off (count-scaled)
    penalty_window: int = 128      # tokens of context the penalties see


def needs_history(sc: SamplingConfig) -> bool:
    """True when `select_token` wants the per-slot token-history input
    (any logit processor active) — the engine then packs a fixed
    `[max_slots, penalty_window]` history tensor into the mixed step."""
    return (sc.repetition_penalty != 1.0 or sc.presence_penalty != 0.0
            or sc.frequency_penalty != 0.0)


def apply_count_penalties(logits, counts, sc: SamplingConfig):
    """Repetition / presence / frequency processors from a token-count
    histogram (ISSUE 19 device-resident form).

    logits [..., V]; counts [..., Vb] — per-context occurrence counts
    over `Vb` vocab bins (bin of token t is t % Vb; Vb == V is exact,
    smaller Vb trades penalty precision for state size —
    docs/SERVING.md). The count tensor is what the multi-tick engine
    keeps resident on device and updates per accepted token, so the
    processors advance inside the decode while_loop without a host
    history rebuild. Any leading batch shape works: the speculative
    verify path passes per-position [S, K, Vb] prior counts.

    * repetition (HF semantics): seen tokens' logits are divided by
      the penalty when positive, multiplied when negative.
    * presence: a flat subtraction per seen token (one-shot).
    * frequency: a COUNT-SCALED subtraction — each occurrence in the
      window adds another `frequency_penalty`, so chronic repeaters
      are pushed down harder than one-off mentions (the OpenAI-style
      companion of the one-shot presence penalty)."""
    import jax.numpy as jnp
    V = logits.shape[-1]
    Vb = counts.shape[-1]
    cnt = counts.astype(logits.dtype)
    if Vb != V:
        cnt = cnt[..., jnp.arange(V, dtype=jnp.int32) % Vb]
    seen = cnt > 0
    if sc.repetition_penalty != 1.0:
        rp = float(sc.repetition_penalty)
        logits = jnp.where(
            seen, jnp.where(logits > 0, logits / rp, logits * rp),
            logits)
    if sc.presence_penalty != 0.0:
        logits = logits - float(sc.presence_penalty) * seen.astype(
            logits.dtype)
    if sc.frequency_penalty != 0.0:
        logits = logits - float(sc.frequency_penalty) * cnt
    return logits


def history_to_counts(history, vocab_bins, dtype=None):
    """[B, W] -1-padded token history -> [B, vocab_bins] float counts:
    ONE scatter-add (duplicates coalesce; -1 padding scatters weight
    0). The bridge between the host-rebuilt history tensor and the
    count-histogram form `apply_count_penalties` consumes."""
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    valid = history >= 0
    idx = jnp.where(valid, history % int(vocab_bins), 0)
    return jnp.zeros((history.shape[0], int(vocab_bins)), dtype).at[
        jnp.arange(history.shape[0])[:, None], idx].add(
        valid.astype(dtype))


def apply_logit_penalties(logits, history, sc: SamplingConfig):
    """Repetition / presence / frequency processors from a [B, W]
    -1-padded token-history window (the host-rebuilt form
    `incubate/nn/generation.py` feeds). Exactly
    `apply_count_penalties` over the history's exact-vocab count
    histogram — one scatter, then the shared count math, so the two
    entry points can never disagree on penalty semantics."""
    return apply_count_penalties(
        logits, history_to_counts(history, logits.shape[-1],
                                  dtype=logits.dtype), sc)


def filter_logits(logits, sc: SamplingConfig):
    """The temperature / top-k / top-p logit transform of the sampling
    strategy, factored out so the speculative verify path can reuse
    it: the distribution non-speculative sampling draws from is
    EXACTLY `softmax(filter_logits(logits, sc))`, and the rejection
    rule must target that same distribution (serving/engine.py)."""
    import jax
    import jax.numpy as jnp
    if sc.temperature != 1.0:
        logits = logits / max(sc.temperature, 1e-6)
    if sc.top_k and sc.top_k > 0:
        kth = jax.lax.top_k(logits, sc.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e9, logits)
    if sc.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p; the
        # cutoff is the SMALLEST kept logit
        keep = cum - probs < sc.top_p
        kth = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                      keepdims=True)
        logits = jnp.where(logits < kth, -1e9, logits)
    return logits


def select_token(logits, key, sc: SamplingConfig, history=None,
                 counts=None):
    """logits [B, V] -> token [B] int32 (device-side sampling).

    `history` [B, W] int32 (-1 pad) or `counts` [B, Vb] (the
    device-resident histogram form, ISSUE 19) feeds the repetition/
    presence/frequency logit processors; they compose with greedy AND
    the top-k/top-p/temperature path (penalties first, then the
    strategy)."""
    import jax
    import jax.numpy as jnp
    logits = logits.astype(jnp.float32)
    if counts is not None and needs_history(sc):
        logits = apply_count_penalties(logits, counts, sc)
    elif history is not None and needs_history(sc):
        logits = apply_logit_penalties(logits, history, sc)
    if sc.strategy == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_logits(logits, sc)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


#: the id of a decode token that is still on the device: the step takes
#: it from the sampled tokens of the step before (`prev_tokens[slot]`)
PREV_TOKEN = -1


def take_prev_tokens(token_ids, prev_tokens, slots):
    """The step's flat token ids [T] with every `PREV_TOKEN` replaced by
    its slot's entry of `prev_tokens` [S], what the step before sampled
    (`slots` [T]: each token's slot, clipped into range): one gather and
    one select, on the device."""
    import jax.numpy as jnp
    return jnp.where(token_ids == PREV_TOKEN, prev_tokens[slots],
                     token_ids)


def next_pow2(n, lo=16):
    p = lo
    while p < n:
        p *= 2
    return p


def round_up(n, m):
    return ((n + m - 1) // m) * m


def choose_token_budget(max_slots, block_size, requested=None,
                        verify_width=1, role="mixed",
                        reserve_region=False):
    """Per-step token budget: a power of two >= max(max_slots,
    2*block_size) so a full decode round always fits and prefill chunks
    cover at least two KV blocks per step (generation.py's bucket
    discipline applied to the step axis). An explicit `requested`
    budget is rounded up to a power of two and floored at `max_slots`
    (a budget below the slot count would stall resident requests
    forever while they hold KV blocks).

    With speculation on (`verify_width` = draft_k + 1 > 1) the first
    `max_slots * verify_width` flat tokens are the RESERVED verify
    region (see `pack_step`), so the floor rises to that region plus
    prefill room — a budget that left prefill zero tokens would starve
    admission forever.

    `role="decode"` (disaggregated serving, docs/SERVING.md) shrinks
    the DEFAULT: a decode-role replica admits migrated requests whose
    KV arrives by block transport, so its steps are decode-dominated
    and the budget only needs the decode/verify tokens plus a little
    prefill headroom (preempted migrants re-prefill locally; +1 keeps
    at least one prefill token even with every slot decoding). Every
    step pays the full fixed `[T]` compute whether or not prefill rides
    along — the small budget is where disaggregation's inter-token
    latency win comes from. Explicit `requested` always wins.

    `reserve_region=True` reserves the per-slot decode region even at
    `verify_width == 1` (block-sparse decode, ISSUE 15: the sparse
    engine routes the region through shortened block tables, so its
    tokens must sit at fixed per-slot indices) — the floors follow the
    speculative treatment."""
    vw = int(verify_width)
    region = max_slots * vw
    region_on = vw > 1 or reserve_region
    if requested is not None:
        floor = max_slots if not region_on else region + 1
        return next_pow2(max(int(requested), floor), lo=1)
    if role == "decode":
        return next_pow2(region + 1, lo=1)
    if not region_on:
        return next_pow2(max(max_slots, 2 * block_size))
    return next_pow2(region + 2 * block_size)


def prefill_chunk(remaining, budget_left):
    """Chunk size for one prefill slice under the remaining budget:
    the whole remainder when it fits, else the largest power of two
    <= budget_left (keeps chunk boundaries bucket-aligned so a long
    prompt is consumed in a handful of predictable slices)."""
    remaining = int(remaining)
    budget_left = int(budget_left)
    if budget_left <= 0 or remaining <= 0:
        return 0
    if remaining <= budget_left:
        return remaining
    p = 1
    while p * 2 <= budget_left:
        p *= 2
    return p


class FairQueue:
    """Bounded round-robin admission queue across tenants.

    The frontend's backpressure + fairness primitive: each tenant gets
    its own FIFO lane, `pop()` serves lanes round-robin so one chatty
    tenant cannot starve the others, and the TOTAL size is bounded —
    `push` refuses above `max_pending` and the async frontend turns
    that refusal into awaiting-for-space. Pure host-side and
    synchronous; all coordination lives in the frontend's event loop.
    """

    def __init__(self, max_pending=256):
        self.max_pending = int(max_pending)
        self._lanes = collections.OrderedDict()   # tenant -> deque
        self._size = 0

    def __len__(self):
        return self._size

    @property
    def full(self):
        return self._size >= self.max_pending

    def push(self, tenant, item):
        """False (item NOT queued) when the queue is at capacity."""
        if self.full:
            return False
        lane = self._lanes.get(tenant)
        if lane is None:
            lane = self._lanes[tenant] = collections.deque()
        lane.append(item)
        self._size += 1
        return True

    def pop(self):
        """Next item, rotating across tenants; None when empty. A
        tenant whose lane still has items goes to the BACK of the
        rotation after serving one, so K tenants each get ~1/K of
        admissions regardless of lane depth."""
        while self._lanes:
            tenant, lane = next(iter(self._lanes.items()))
            self._lanes.move_to_end(tenant)
            if not lane:
                del self._lanes[tenant]
                continue
            item = lane.popleft()
            self._size -= 1
            if not lane:
                del self._lanes[tenant]
            return item
        return None

    def items(self):
        """Iterate queued items across all lanes (inspection only)."""
        for lane in self._lanes.values():
            yield from lane

    def remove(self, item):
        """Drop a queued item (cancellation before admission)."""
        for tenant, lane in list(self._lanes.items()):
            try:
                lane.remove(item)
            except ValueError:
                continue
            self._size -= 1
            if not lane:
                del self._lanes[tenant]
            return True
        return False


@dataclasses.dataclass
class StepPlan:
    """Host-side plan for one mixed step (fixed-shape numpy arrays)."""
    token_ids: np.ndarray       # [T] int32
    slot_ids: np.ndarray        # [T] int32, -1 pad
    positions: np.ndarray       # [T] int32
    sample_index: np.ndarray    # [max_slots] int32, -1 = no sample
    num_tokens: int             # real tokens this step
    decode_slots: list          # slots that fed decode/verify tokens
    prefill_done: list          # slots whose prompt completed this step
    prefill_tokens: int
    decode_tokens: int
    verify_width: int = 1       # 1 + draft_k (1 = no speculation)
    decode_entries: list = dataclasses.field(default_factory=list)
    #                         [(slot, [tokens], position)] as planned —
    #                         the engine replays these against the
    #                         verify logits to compute accept lengths
    buffers: "PlanBuffers" = None   # what the arrays are views of


class PlanLayout:
    """Where each field of a step's plan lies in ONE flat int32 buffer:
    `fields[name] = (offset, shape)`, in the order

        token_ids [T], slot_ids [T], positions [T], sample_index [S]
        (or [S, L]: `sample_rows`), each block table [S, MB] (one, or full then window),
        adapter_ids [T] (only with adapters registered)

    Built once an engine, from what it can see of itself. The packer
    (`PlanBuffers`) and the compiled step (`unpack` on the traced
    buffer) read the same object, so the two cannot drift: everything
    the host decides about a step reaches the device as one array."""

    def __init__(self, token_budget, max_slots, tables, adapters=False,
                 sample_rows=1):
        T, S = int(token_budget), int(max_slots)
        # the sample rows a slot: one, or a block's L (block decoding)
        sample = (S,) if sample_rows == 1 else (S, int(sample_rows))
        shapes = [("token_ids", (T,)), ("slot_ids", (T,)),
                  ("positions", (T,)), ("sample_index", sample)]
        shapes += [(name, tuple(int(d) for d in shape))
                   for name, shape in tables]
        if adapters:
            shapes.append(("adapter_ids", (T,)))
        #: the block tables' field names, in `kv.tables()` order
        self.tables = tuple(name for name, _ in tables)
        self.fields = {}
        self._spans = {}        # name -> the field's slice of the buffer
        self.size = 0
        for name, shape in shapes:
            self.fields[name] = (self.size, shape)
            self._spans[name] = slice(self.size,
                                      self.size + int(np.prod(shape)))
            self.size = self._spans[name].stop

    def unpack(self, flat):
        """name -> that field of `flat`, a static slice reshaped: numpy
        VIEWS of a host buffer, or slices of the traced one."""
        return {name: flat[self._spans[name]].reshape(shape)
                for name, (_, shape) in self.fields.items()}

    def replace(self, flat, **fields):
        """The traced buffer with the named fields swapped (the device
        loop rebuilds a tick's flat tokens and leaves the tables)."""
        for name, value in fields.items():
            flat = flat.at[self._spans[name]].set(value.reshape(-1))
        return flat


class PlanBuffers:
    """One step's plan as the host packs it: ONE flat int32 buffer
    (`flat`, what the engine uploads) laid out by a `PlanLayout`, with a
    numpy VIEW of it under every field's name: `pack_step` writes
    `token_ids`, `slot_ids`, `positions` and `sample_index` as it always
    did, the engine copies the block tables (and the per-token adapter
    ids) in beside them at pack time.

    The engine keeps TWO of these and alternates: the buffer a
    dispatched step may still be reading (an async host-to-device
    transfer; on the CPU backend the device array IS the numpy memory)
    is never the one the host packs next."""

    def __init__(self, layout):
        self.flat = np.zeros(layout.size, np.int32)
        vars(self).update(layout.unpack(self.flat))
        self.reset()

    def reset(self):
        self.token_ids[:] = 0
        self.slot_ids[:] = -1
        self.positions[:] = 0
        self.sample_index[:] = -1


def pack_step(token_budget, max_slots, decode, prefills,
              verify_width=1, reserve_region=False,
              buffers: PlanBuffers = None) -> StepPlan:
    """Pack decode entries + prefill chunks into the flat-token layout.

    decode: [(slot, tokens, first position)] — one entry per running
        decode, as `Scheduler.plan` gives them: a list of token ids at
        consecutive positions. One id is the plain one-token decode
        (`PREV_TOKEN` where the host has not read the token back yet);
        [last, d_1..d_k] a speculative verify group (k <= draft_k
        proposed tokens after the last accepted one); L ids a block of a
        model that decodes by blocks (`buffers.sample_index` is [S, L]
        then: every row of the block is a sample row). A bare int
        stands for a list of one.
    prefills: [(slot, chunk_tokens: ndarray, start_pos, completes)] —
        `completes` marks the chunk that reaches the end of the prompt
        (its last token's hidden state samples the slot's first output).

    Layout: with `verify_width == 1` decode tokens pack densely from
    index 0 and prefill chunks follow (the PR 2 layout, unchanged).
    With speculation (`verify_width` = draft_k + 1 > 1) the first
    `max_slots * verify_width` flat tokens are a FIXED verify region —
    slot s owns indices [s*vw, (s+1)*vw) — so the compiled step can
    reshape it to `[max_slots, vw]` and run the verify-shaped paged
    attention + per-position logits without any gather indices that
    change shape as the decode mix churns; prefill packs after the
    region. `reserve_region=True` applies the same fixed per-slot
    layout at `verify_width == 1` (block-sparse decode, ISSUE 15:
    decode token of slot s sits at flat index s, and its hidden state
    still samples through `sample_index` like the dense layout).

    `buffers` (a `PlanBuffers`) packs into its views of the one flat
    buffer instead of allocating fresh arrays — same layout, same
    contents; the plan then names it (`StepPlan.buffers`)."""
    vw = int(verify_width)
    region_on = vw > 1 or reserve_region
    region = max_slots * vw if region_on else 0
    if buffers is not None:
        buffers.reset()
        token_ids = buffers.token_ids
        slot_ids = buffers.slot_ids
        positions = buffers.positions
        sample_index = buffers.sample_index
    else:
        token_ids = np.zeros(token_budget, np.int32)
        slot_ids = np.full(token_budget, -1, np.int32)
        positions = np.zeros(token_budget, np.int32)
        sample_index = np.full(max_slots, -1, np.int32)
    # rows a decode entry may feed: a verify group's, or a block's
    width = sample_index.shape[1] if sample_index.ndim == 2 else vw
    i = 0
    decode_slots = []
    decode_entries = []
    n_decode = 0
    for slot, tok, pos in decode:
        toks = [int(t) for t in np.atleast_1d(tok)]
        if len(toks) > width:
            raise ValueError(
                f"decode group of {len(toks)} tokens exceeds the "
                f"verify width {width}")
        base = slot * vw if region_on else i
        token_ids[base:base + len(toks)] = toks
        slot_ids[base:base + len(toks)] = slot
        positions[base:base + len(toks)] = np.arange(
            pos, pos + len(toks), dtype=np.int32)
        if sample_index.ndim == 2:
            sample_index[slot, :len(toks)] = np.arange(
                base, base + len(toks), dtype=np.int32)
        elif vw == 1:
            sample_index[slot] = base
        if not region_on:
            i += len(toks)
        decode_slots.append(slot)
        decode_entries.append((slot, toks, int(pos)))
        n_decode += len(toks)
    if region_on:
        i = region
    n = n_decode + sum(len(c[1]) for c in prefills) \
        + (region - n_decode if region_on else 0)
    if n > token_budget:
        raise ValueError(f"plan of {n} tokens exceeds token budget "
                         f"{token_budget}")
    prefill_done = []
    n_prefill = 0
    for slot, chunk, start, completes in prefills:
        m = len(chunk)
        token_ids[i:i + m] = chunk
        slot_ids[i:i + m] = slot
        positions[i:i + m] = np.arange(start, start + m, dtype=np.int32)
        if completes:
            if sample_index.ndim == 1:
                # (a block-decoding prefill samples nothing)
                sample_index[slot] = i + m - 1
            prefill_done.append(slot)
        i += m
        n_prefill += m
    return StepPlan(token_ids=token_ids, slot_ids=slot_ids,
                    positions=positions, sample_index=sample_index,
                    num_tokens=i, decode_slots=decode_slots,
                    prefill_done=prefill_done,
                    prefill_tokens=n_prefill,
                    decode_tokens=n_decode, verify_width=vw,
                    decode_entries=decode_entries, buffers=buffers)
