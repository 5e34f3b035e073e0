"""ServingEngine — paged-KV continuous batching over the fused GPT stack.

One jitted **mixed step** serves a churning mix of requests: every
input is a fixed-shape slot tensor (flat token ids, positions, block
tables, per-slot sample indices), so admissions, completions,
preemptions and ragged prompt lengths never change a compiled shape —
the step compiles exactly ONCE per engine (asserted by
tests/test_serving.py via the PR 1 `instrumented_jit` compile counter).

The step runs the same math as `GPTForGeneration`'s compiled
prefill/decode (`incubate/nn/generation.py`) — same `_ln`/`_mm`/
`_qkv`/`_ffn_dense` cores from `incubate/nn/fused_transformer.py`,
attention through `ops.pallas.flash_attention.ragged_paged_attention`
— so serving output is token-identical to single-request
`generate()` for the same prompts (the parity test).

Host loop per `step()`:
  scheduler.plan()  →  pack_step()  →  jitted mixed step  →  sample
  bookkeeping (TTFT / inter-token metrics, EOS + length termination,
  block release). The loop is a PIPELINE ONE STEP DEEP where the engine
  can be (docs/SERVING.md "Dispatching ahead"): step k+1 is planned,
  packed and dispatched before step k is read back, a decode row whose
  token is still on the device takes it there (`batcher.PREV_TOKEN`),
  and the host's work runs behind the chip instead of in front of it.

MoE decoder stacks (`GPTForGeneration(moe=...)`) serve through the
same step: per-token top-k routing into FIXED expert-capacity slots
(`_ffn_moe_tokens` — T is the static token budget, so the [E, C, D]
dispatch buffers are compile-time shapes and capacity overflow
degrades to the residual path, never a recompile); per-expert token
counts / dropped totals / the balance-loss gauge ride the step
outputs (docs/MOE.md). `serving.distributed.TPServingEngine` adds
TP x EP sharding over a 2-D (ep, mp) mesh.

Disaggregated roles (docs/SERVING.md "Disaggregated serving"):
`role="prefill"` parks each request in the "handoff" state right after
its first sampled token — `extract_request` then exports its KV blocks
(int8 scale rows included) into a `MigrationTicket` a decode-role
engine admits mid-stream via `submit_migrated`, with greedy outputs
token-identical to a monolithic engine; `role="decode"` defaults to a
decode-sized token budget and admits migrated requests by IMPORTING
their blocks at scheduler admission (never a new compiled shape — the
one-compile contract holds across migration admits).

With `draft_k > 0` (greedy only) each decode feeds a verify group —
the last accepted token plus up to draft_k n-gram prompt-lookup
proposals (`serving.draft`) — through a fixed `[max_slots, draft_k+1]`
verify region scored by `verify_paged_attention`; the host accepts the
longest sequential-greedy prefix, emits 1..draft_k+1 tokens, and rolls
back KV blocks the rejected tail had claimed. Output stays
token-identical to `draft_k=0`, and the step still compiles exactly
once (docs/SERVING.md).

A model that brings its own block (`models.serving_block.ServingBlock`:
`models/afmoe.py`, `models/olmo_hybrid.py`, `models/sdar_moe.py`) is
stepped through `_block_step_body` instead of the GPT scan: its layers
unrolled, each on its own K/V pool or recurrent state, the same packed
plan, the same host loop. What such a block may ask of the engine:
window, full and linear layers, grouped queries, dropless experts,
greedy or plain sampling, and DECODING BY BLOCKS (an architecture whose
`block_decoding` is set generates by diffusion over blocks; docs/
SERVING.md "Decoding by blocks"): a decode entry is the slot's current
block of L positions, the step samples all L rows and returns the
candidates and their confidences, `_run_block_tick` decides some of
them by the model's rule, delivers tokens in position order, and the
pass that feeds a block with nothing masked commits its K/V; attention
is block-causal, in the prefill too. What stays built into the GPT scan
only is refused with the reason: drafts, quantized pools, adapters,
sparse decode, the device loop, disaggregated roles, penalized
sampling; a prefix cache with window or linear layers or block
decoding; block decoding with window or linear layers or with a
temperature.
"""
from __future__ import annotations

import functools
import math
import time

import numpy as np

from ..jit.functional import instrumented_jit
from ..profiler import metrics as _pmetrics
from . import batcher
from . import metrics as smetrics
from . import tracing as _tracing
from .batcher import SamplingConfig, pack_step, select_token
from .kv_cache import PagedKVCache, kv_jnp_dtype
from .scheduler import Plan, Scheduler

STEP_FN_NAME = "serving_mixed_step"
SWAP_FN_NAME = "serving_weight_swap"

# default replica names (`role` + sequence): stable labels for trace
# span events and flight-recorder tracks when the caller names nothing
import itertools as _itertools  # noqa: E402
_ENGINE_SEQ = _itertools.count()


def _plan_groups(plan):
    """(first position, tokens) of every slot a plan feeds."""
    groups = [(pos, len(toks)) for _, toks, pos in plan.decode]
    return groups + [(start, len(chunk))
                     for _, chunk, start, _ in plan.prefills]


def _attention_work(plan, block_size):
    """The attention work of one tick fed `plan`, as the flight
    record's fields. Per slot fed `n` query tokens from position
    `start` (a decode group is `width` tokens from `pos`, a prefill
    chunk `len(chunk)` from its `start`): the context it must read once
    is `start + n` tokens (`kv_tokens_read`), `ceil((start + n) /
    block_size)` blocks (`kv_blocks_needed`), and its queries attend
    `start + 1 .. start + n` keys, `n * start + n * (n + 1) / 2`
    `attn_pairs` in all. `kv_blocks_walked` is what the paged kernel
    fetches for the same groups, by its own rule
    (`paged_attention.blocks_walked`): the packer lays each group as
    one run. Token and block counts only: what they cost in bytes and
    FLOPs is the benchmark's arithmetic."""
    from ..ops.pallas.paged_attention import blocks_walked
    read = pairs = needed = 0
    groups = _plan_groups(plan)
    for start, n in groups:
        read += start + n
        pairs += n * start + n * (n + 1) // 2
        needed += -(-(start + n) // block_size)
    walked = blocks_walked(groups, block_size)
    return dict(kv_tokens_read=int(read), attn_pairs=int(pairs),
                kv_blocks_needed=int(needed),
                kv_blocks_walked=int(walked))


def _attention_work_by_kind(plan, window=None, causal_block=None,
                            groups=None):
    """`_attention_work` for a model with window and full layers: the
    work of ONE layer of each kind (the benchmark multiplies by the
    layers of the kind). A window layer's query at p reads and attends
    keys `p - window < j <= p`: a group of `n` tokens from `start`
    reads `start + n - max(start - window + 1, 0)` tokens once. With no
    window (`window=None`: no layer is sliding) the `_window` fields are
    there and 0. With a `causal_block` L (no window beside it) a query
    attends the keys to the END of its block of L positions, and none
    past its group's last token: the group reads the same `start + n`
    tokens and attends more pairs. `groups` (default: every slot the
    plan feeds): the (first position, tokens) the kernel walks, where
    the step leaves some of the plan's rows out of it."""
    groups = _plan_groups(plan) if groups is None else groups
    out = {}
    for kind, w in (("window", window), ("full", None)):
        read = pairs = 0
        for start, n in groups if w or kind == "full" else ():
            read += start + n - (max(start - w + 1, 0) if w else 0)
            if causal_block and w is None:
                # block by block: its queries x the keys to its end
                end, L = start + n, causal_block
                for b in range(start // L * L, end, L):
                    pairs += (min(b + L, end) - max(b, start)) \
                        * min(b + L, end)
                continue
            # a query at p attends min(p + 1, w) keys: p + 1 runs over
            # start + 1 .. start + n, capped at w
            lo, hi = start + 1, start + n
            cap = hi if w is None else min(hi, max(w, lo - 1))
            pairs += (lo + cap) * (cap - lo + 1) // 2 \
                + (0 if w is None else w * (hi - cap))
        out[f"kv_tokens_read_{kind}"] = int(read)
        out[f"attn_pairs_{kind}"] = int(pairs)
    return out


def _linear_work(plan, chunk):
    """The work of ONE linear (delta-rule) layer fed `plan`: valid
    tokens; runs (a state read and written once each: one a slot fed)
    and those of them that hold ONE token (they take the recurrence on
    their one row); the chunks of `chunk` rows the runs make (what
    the traffic is under 64-row chunking, whatever the kernel does with
    it) and the rows of q, k, v the kernel really loads for them
    (`gated_delta.rows_walked`: 1 a one-token chunk, a tile a chunk of
    more, nothing a chunk slot that stays empty)."""
    from ..ops.pallas.gated_delta import rows_walked
    lens = [n for _, n in _plan_groups(plan)]
    return dict(lin_tokens=int(sum(lens)), lin_runs=len(lens),
                lin_single_runs=sum(n == 1 for n in lens),
                lin_chunks=int(sum(-(-n // chunk) for n in lens)),
                lin_chunk_size=int(chunk),
                lin_rows_walked=sum(rows_walked(n, chunk) for n in lens))


#: the longest query run the paged kernel takes at once in the step of a
#: model-provided block: its softmax state is `max_run x query heads`
#: rows of VMEM, and a longer prefill chunk walks its slot once a cut
_BLOCK_MAX_RUN = 128


class _SparseLayers:
    """What the block step does for a layer that attends THROUGH A
    LEARNED SELECTION (`layer_kinds` "sparse", `arch.selection`; docs/
    SERVING.md "Attention through a learned selection"): beside K and V
    the layer writes its rows' indexer keys into its indexer-key pool,
    at the K/V's (block, offset); every row scores its slot's cached
    indexer keys up to its own position, `[rows, context]` float32, and
    keeps the exact `topk` best (`ops.pallas.topk_select`: equal scores
    to the lower position); then

    * a row that is a run of ONE token (a decode row; the odd last
      token of a cut chunk) attends over its GATHERED selection: the
      K/V it reads is `min(topk, position + 1)` tokens, whatever its
      context (`_sparse_work`: `sparse_kv_tokens_read`);
    * the rows of longer runs (prefill chunks) go through the run
      kernel over their slot's pages, the selection applied as data
      (`ragged_paged_attention(select=)`); the one-token runs are left
      out of the kernel's runs, so it does not walk their contexts.

    Every shape is the token budget's, the slot count's, `topk`'s or
    the table's: one compile. The one-token runs are at most one a
    slot (a slot is fed one run a step; a cut at `max_run` leaves one
    odd token at most)."""

    #: pages of indexer keys a chunk run scores at a time
    PAGES = 256

    def __init__(self, engine, max_run):
        kv = engine.kv
        self.sel = engine._select
        self.T, self.S = engine.token_budget, kv.max_slots
        self.BS, self.MB = engine.block_size, kv.max_blocks_per_slot
        self.C = self.MB * self.BS
        self.max_run = max_run
        self.pages = min(self.PAGES, self.MB)

    def split(self, runs, table, pos, valid, rows_at):
        """The step's runs (`paged_runs`, cut at `max_run`) parted into
        the one-token runs, compacted to a row a slot, and the others,
        as the run kernel takes them; the candidates of both; where the
        sample rows lie among them. Once a step, for all its layers."""
        import jax.numpy as jnp
        T, S, C = self.T, self.S, self.C
        n_runs, start, length, rslot, first = runs
        r = jnp.arange(T, dtype=jnp.int32)
        live = r < n_runs[0]
        single = live & (length == 1)
        order = jnp.argsort(~single, stable=True)[:S]
        d_live = single[order]
        d_row = jnp.where(d_live, start[order], 0)
        d_slot = jnp.where(d_live, rslot[order], 0)
        d_pos = jnp.where(d_live, first[order], 0)
        chunk = live & (length > 1)
        order = jnp.argsort(~chunk, stable=True)
        n_chunk = jnp.sum(chunk, dtype=jnp.int32)
        kruns = (n_chunk.reshape(1), start[order],
                 jnp.where(r < n_chunk, length[order], 0), rslot[order],
                 first[order])
        keys = jnp.arange(C, dtype=jnp.int32)[None, :]
        # where a flat row lies among the one-token runs (S: nowhere)
        d_of = jnp.full((T,), S, jnp.int32).at[
            jnp.where(d_live, d_row, T)].set(
            jnp.arange(S, dtype=jnp.int32), mode="drop")
        return dict(
            kruns=kruns, d_row=d_row, d_table=table[d_slot],
            d_to=jnp.where(d_live, d_row, T),
            cand_d=(keys <= d_pos[:, None]) & d_live[:, None],
            cand_c=(keys <= pos[:, None]) & valid[:, None],
            samp_d=d_of[rows_at], samp_row=rows_at)

    def chunk_scores(self, qI, w, ip, table, kruns):
        """I(t, s) [T, C] float32 of the rows of the runs `kruns` over
        their slots' cached indexer keys, a run and `pages` pages at a
        time, only as far as the run's last position; the rows of no
        run and the keys past a run's reach hold whatever: no candidate
        lies there. A run's tile of `max_run` rows may overhang into
        the next runs' rows, which are written after it (ascending)."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas.topk_select import index_scores
        T, R, BS = self.T, self.max_run, self.BS
        pages = self.pages
        KC = pages * BS
        n_kc = -(-self.MB // pages)
        table = jnp.pad(table, ((0, 0), (0, n_kc * pages - self.MB)))
        qI = jnp.pad(qI, ((0, R), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, R), (0, 0)))
        n_chunk, c_start, c_len, c_slot, c_first = kruns

        def run(r, scores):
            start = c_start[r]
            qr = jax.lax.dynamic_slice_in_dim(qI, start, R)
            wr = jax.lax.dynamic_slice_in_dim(w, start, R)
            row = table[c_slot[r]]

            def keys(c, scores):
                kc = ip[jax.lax.dynamic_slice_in_dim(
                    row, c * pages, pages)].reshape(KC, -1)
                return jax.lax.dynamic_update_slice(
                    scores, index_scores(qr, wr, kc), (start, c * KC))

            return jax.lax.fori_loop(
                0, (c_first[r] + c_len[r] - 1) // KC + 1, keys, scores)

        scores = jax.lax.fori_loop(
            0, n_chunk[0], run,
            jnp.zeros((T + R, n_kc * KC), jnp.float32))
        return scores[:T, :self.C]

    def attend(self, pools, at, ix, q, k, v, idx, wb, wo, slot_ids, pos,
               table, sp):
        """One sparse layer of the step: -> (attention output [T, Hq,
        Dh], the sample rows' selections [S, C] bool). `pools[at]`,
        `pools[at + 1]`: the layer's K and V pool; `pools[ix]`: its
        indexer-key pool; all three updated in place."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas.flash_attention import ragged_paged_attention
        from ..ops.pallas.topk_select import (index_scores,
                                              mask_positions, topk_mask)
        S, C = self.S, self.C
        topk = self.sel.topk
        qI, kI, w = idx
        kp, vp, ip = pools[at], pools[at + 1], pools[ix]
        lanes = ip.shape[-1] - kI.shape[-1]
        with jax.named_scope("kv_write"):
            kp = kp.at[wb, wo].set(k.astype(kp.dtype))
            vp = vp.at[wb, wo].set(v.astype(vp.dtype))
            # a row of the pool is a whole lane tile: zeros past the key
            ip = ip.at[wb, wo].set(
                jnp.pad(kI, ((0, 0), (0, lanes))).astype(ip.dtype))
        pools[at], pools[at + 1], pools[ix] = kp, vp, ip
        with jax.named_scope("idx_score"):
            qI = jnp.pad(qI.astype(ip.dtype), ((0, 0), (0, 0), (0, lanes)))
            # a one-token run: one query row over its slot's pages
            score_d = jax.vmap(index_scores)(
                qI[sp["d_row"]][:, None], w[sp["d_row"]][:, None],
                ip[sp["d_table"]].reshape(S, C, -1))[:, 0]
            score_c = self.chunk_scores(qI, w, ip, table, sp["kruns"])
        with jax.named_scope("idx_select"):
            keep_d = topk_mask(score_d, topk, sp["cand_d"])
            at_d = mask_positions(keep_d, topk)
            keep_c = topk_mask(score_c, topk, sp["cand_c"])
            kept = jnp.where(
                (sp["samp_d"] < S)[:, None],
                keep_d[jnp.minimum(sp["samp_d"], S - 1)],
                keep_c[sp["samp_row"]])
        with jax.named_scope("attn_full"):
            o = ragged_paged_attention(
                q, kp, vp, table, slot_ids, pos, runs=sp["kruns"],
                max_run=self.max_run, select=keep_c)
        with jax.named_scope("attn_sparse"):
            # the one-token runs over their gathered selection
            od = attend_gathered(q[sp["d_row"]], kp, vp, sp["d_table"],
                                 at_d)
            o = o.at[sp["d_to"]].set(od.astype(o.dtype), mode="drop")
        return o, kept


def attend_gathered(q, k_pool, v_pool, tables, at):
    """Attention of one query a row over the keys at the positions `at`
    of its own block table: q [S, Hq, Dh]; pools [NB, BS, Hkv, Dh];
    tables [S, MB]; at [S, K] int32 positions (-1: none). The K and V
    of the K positions are gathered a token at a time, and nothing else
    of the context is read. Grouped queries, 1/sqrt(Dh), float32
    logits and sums, the products' operands in q's dtype. -> [S, Hq,
    Dh] float32."""
    import jax
    import jax.numpy as jnp
    S, Hq, Dh = q.shape
    BS, Hkv = k_pool.shape[1], k_pool.shape[2]
    safe = jnp.maximum(at, 0)
    blk = jnp.take_along_axis(tables, safe // BS, axis=1)
    kg, vg = k_pool[blk, safe % BS], v_pool[blk, safe % BS]
    qg = q.reshape(S, Hkv, Hq // Hkv, Dh)
    s = jnp.einsum("shgd,skhd->shgk", qg, kg.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    s = jnp.where((at >= 0)[:, None, None, :], s / math.sqrt(Dh), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("shgk,skhd->shgd", p, vg.astype(q.dtype),
                      preferred_element_type=jnp.float32).reshape(
        S, Hq, Dh)


def _sparse_work(plan, topk, max_run):
    """The work of ONE sparse layer fed `plan`, as the flight record's
    fields (host arithmetic on the plan; FLIGHT_FIELDS_SPARSE.md): a
    slot's tokens are cut at `max_run` as the step cuts them; a piece
    of one token is a DECODE row (it attends over its gathered
    selection: `sparse_kv_tokens_context` the keys dense attention
    would read for it, `sparse_kv_tokens_read` those it reads), the
    rows of longer pieces are CHUNK rows (`sparse_pairs_causal` the
    (query, key) pairs the causal rule allows them, `sparse_pairs_kept`
    those the selection keeps). `idx_keys_scored`: rows x candidate
    keys, both kinds. -> (fields, the groups the run kernel walks)."""
    dec = ctx = read = rows = scored = causal = kept = 0
    walked = []
    for start, n in _plan_groups(plan):
        lo, hi = start + 1, start + n          # keys a row sees: lo..hi
        scored += (lo + hi) * n // 2
        if n % max_run == 1:
            dec, ctx, read = dec + 1, ctx + hi, read + min(topk, hi)
            n, hi = n - 1, hi - 1
        if n:
            walked.append((start, n))
            rows += n
            causal += (lo + hi) * n // 2
            cap = min(hi, max(topk, lo - 1))   # rows that keep them all
            kept += (lo + cap) * (cap - lo + 1) // 2 + topk * (hi - cap)
    return dict(sparse_rows_decode=dec, sparse_rows_chunk=rows,
                sparse_kv_tokens_context=ctx, sparse_kv_tokens_read=read,
                idx_keys_scored=scored, sparse_pairs_causal=causal,
                sparse_pairs_kept=kept), walked


class _Flight:
    """One dispatched step: the plan as packed (`sp`), the requests it
    fed by slot (`reqs`: emit goes by THEM, a slot may hold another
    request by the time the tokens are read), its head output still on
    the device (`out`), the host form the readback fills in (`got`),
    whether the `step()` call that dispatched it was traced, and the
    requests that count a token of it as in flight until it is read
    (`owed`: none for a runner that reads back what it dispatched)."""
    __slots__ = ("sp", "got", "out", "reqs", "traced", "owed")

    def __init__(self, sp, got, out, reqs, traced, owed=()):
        self.sp, self.got, self.out = sp, got, out
        self.reqs, self.traced, self.owed = reqs, traced, owed


class ServingEngine:
    def __init__(self, model, *, max_slots=8, block_size=16,
                 num_blocks=None, max_seq_len=None, token_budget=None,
                 sampling=None, eos_token_id=None, cache_dtype=None,
                 kv_dtype=None, seed=0, clock=time.monotonic,
                 draft_k=0, draft_ngram=3, draft_ring=128,
                 penalty_vocab_bins=None, prefix_caching=False,
                 role="mixed", max_adapters=0, lora_rank=8,
                 lora_alpha=None, moe_weight_dtype=None,
                 sparse_blocks=None, sparse_recent=2,
                 track_summaries=None, name=None,
                 ticks_per_dispatch=1, device=None):
        import jax
        import jax.numpy as jnp
        model.eval()
        self.model = model
        # the seam: a model that brings its own block (embed, a layer
        # body given `attend(q, k, v, layer)` and, with linear layers,
        # `recur(...)`, final norm + head; see `models.serving_block.
        # ServingBlock`) is stepped through it, layer by layer, each
        # layer on its own K/V pool or recurrent state. The GPT decoder
        # has no such block: its step is the scan over stacked layers
        # below.
        self._block = (model.serving_block()
                       if hasattr(model, "serving_block") else None)
        if self._block is not None:
            arch = self._block.arch
            # options built into the GPT scan body and not (yet) into
            # the step of a model-provided block
            asked = dict(draft_k=draft_k, kv_dtype=kv_dtype,
                         max_adapters=max_adapters,
                         moe_weight_dtype=moe_weight_dtype,
                         sparse_blocks=sparse_blocks,
                         track_summaries=track_summaries,
                         ticks_per_dispatch=ticks_per_dispatch != 1,
                         role=role != "mixed")
            bad = [k for k, v in asked.items() if v]
            kinds_of = set(arch.layer_kinds)
            # a model that generates by diffusion over blocks says so in
            # its description, as it says what its layers are
            self._diff = getattr(arch, "block_decoding", None)
            self._causal_block = self._diff and self._diff.block_length
            if self._diff is not None:
                other = kinds_of - {"full"}
                why = (f"with {sorted(other)} layers: a block's "
                       "provisional rows would have to be taken out of a "
                       "window table or a recurrent state again"
                       if other else
                       "with prefix_caching: a cached prefix would have to "
                       "end on a committed block" if prefix_caching else
                       "with a temperature: the candidates inside a block "
                       "are greedy (batcher.select_token has one key a "
                       "step)" if (sampling or SamplingConfig()).strategy
                       != "greedy" else None)
                if why:
                    raise ValueError(
                        f"{type(model).__name__} decodes by blocks "
                        f"(block_decoding); that is not built {why}")
            # a model whose layers attend through a learned selection
            # says so in its description too (`layer_kinds` "sparse",
            # `selection`: a `serving_block.LearnedSelection`)
            self._select = arch.selection if "sparse" in kinds_of \
                else None
            if self._select is not None and (
                    prefix_caching or "full" in kinds_of):
                raise ValueError(
                    "attention through a learned selection is not built "
                    + ("with prefix_caching: a shared block's indexer "
                       "keys would have to follow `cow_block`, which a "
                       "cache with an indexer-key pool refuses"
                       if prefix_caching else
                       "beside full layers: the flight fields "
                       "`kv_tokens_read_full` / `attn_pairs_full` count "
                       "one kind of layer"))
            if "linear" in kinds_of and (prefix_caching or draft_k):
                raise ValueError(
                    f"{'prefix_caching' if prefix_caching else 'draft_k'}"
                    " with linear layers is not built: a recurrent state "
                    "can be neither truncated nor shared by blocks")
            if prefix_caching:
                raise ValueError(
                    "prefix_caching with window layers is not built: a "
                    "window table lets go of the blocks a cached prefix "
                    "would share" if "sliding" in kinds_of else
                    f"prefix_caching is not built for the step of a "
                    f"model-provided block ({type(model).__name__})")
            if bad or batcher.needs_history(sampling or SamplingConfig()):
                raise ValueError(
                    f"{type(model).__name__} is served through its own "
                    f"block; {bad or ['penalized sampling']} is built "
                    "into the GPT step only")
            self.num_experts = 0    # the capacity router's statistics
            L, H, Dh = len(arch.layers), arch.num_heads, arch.head_dim
        else:
            dec = model.decoder
            self.num_experts = int(getattr(dec, "_num_experts", 0))
            if self.num_experts and getattr(dec, "_ep_size", 1) > 1:
                raise ValueError(
                    "serve a FULL MoE stack (ep_size=1): the engine "
                    "shards experts itself (TPServingEngine "
                    "expert_parallel=)")
            L, H, Dh = dec.num_layers, dec.num_heads, dec.head_dim
            self._diff = self._causal_block = self._select = None
        maxpos = model.max_position_embeddings
        max_seq_len = min(max_seq_len or maxpos, maxpos)
        if block_size == "auto":
            # tuned KV block size (ISSUE 11): the kernel autotuner's
            # cached winner for this engine's shape bucket, falling
            # back to the hand-picked 16. Candidates are admitted
            # through the SAME alignment predicate as the serve-time
            # Pallas dispatch gate, so "auto" can never pick a block
            # size the kernels would refuse (nothing populates the
            # cache on the chip yet: ROADMAP queue 3).
            from ..ops.pallas import autotune as _kt

            from .kv_cache import KV_DTYPES, kv_jnp_dtype
            # quantized pools key the lookup by their storage dtype
            # (KV_DTYPES' quantized flag is the single source of
            # truth, not a re-hardcoded name list); float pools share
            # the fp32 key
            quant_bs = kv_dtype is not None and \
                KV_DTYPES.get(str(kv_dtype), (0, False))[1]
            block_size = _kt.ensure(
                "paged_block_size",
                _kt.shape_bucket(max_slots, H, Dh),
                np.dtype(kv_jnp_dtype(kv_dtype)) if quant_bs
                else np.dtype(np.float32),
                {"block_size": 16})["block_size"]
            # geometry clamp: a winner tuned under a longer context
            # must never exceed THIS engine's sequence bound (one
            # block spanning the whole sequence would degrade paging/
            # CoW/prefix sharing to whole-sequence granularity); the
            # candidate list shares the gate's alignment predicate
            allowed = [c["block_size"]
                       for c in _kt.paged_block_size_candidates(
                           Dh, max_seq_len)]
            if block_size not in allowed:
                block_size = 16 if 16 in allowed else allowed[-1]
        self.block_size = int(block_size)
        mbps = -(-max_seq_len // self.block_size)
        if num_blocks is None:
            # full residency for every slot, + the reserved null block
            num_blocks = max_slots * mbps + 1
        # disaggregated serving role (docs/SERVING.md): "prefill" runs
        # chunked prefill only — the request parks in the "handoff"
        # state after its first sampled token and the frontend extracts
        # it toward a decode replica; "decode" behaves like "mixed" at
        # the engine level (it can still re-prefill a preempted
        # migrant) but defaults to a decode-sized token budget. The
        # router's dispatch policy is what keeps fresh prompts off
        # decode replicas.
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        self.role = role
        # replica label stamped on trace span events + the flight
        # recorder track (serving.tracing, ISSUE 16)
        self.name = (str(name) if name is not None
                     else f"{role}{next(_ENGINE_SEQ)}")
        self.draft_k = int(draft_k)
        self.draft_ngram = int(draft_ngram)
        self.sampling = sampling or SamplingConfig()
        # config validation is LOUD (ISSUE 19): the silent draft_k
        # zeroing under penalized sampling is gone — penalties now
        # compose with speculation through per-position count priors
        # (docs/SERVING.md "Feature matrix"), so what remains invalid
        # is refused up front instead of quietly degraded
        if self.draft_k < 0:
            raise ValueError(f"draft_k={draft_k} must be >= 0")
        if self.draft_k > 0 and int(draft_ngram) < 1:
            raise ValueError(
                f"draft_ngram={draft_ngram} must be >= 1 with "
                "speculation on")
        self.draft_ring = int(draft_ring)
        if self.draft_k > 0 and self.draft_ring < 2:
            raise ValueError(
                f"draft_ring={draft_ring} must be >= 2 with "
                "speculation on (the n-gram scan needs at least one "
                "earlier token besides the tail)")
        # penalty count-histogram bins (ISSUE 19): the device-resident
        # [max_slots, Vb] token-count tensor the in-step logit
        # processors read; Vb defaults to the full vocab (exact HF
        # semantics), smaller Vb trades penalty precision for state
        # size via t % Vb binning (docs/SERVING.md)
        vocab = int(getattr(model, "vocab_size", 0) or 0)
        self._penalty_bins = (vocab if penalty_vocab_bins is None
                              else int(penalty_vocab_bins))
        if batcher.needs_history(self.sampling) \
                and self._penalty_bins < 1:
            raise ValueError(
                f"penalty_vocab_bins={penalty_vocab_bins} must be "
                ">= 1 with penalized sampling")
        # plain sampling (temperature/top-k/top-p) keeps speculation
        # via the standard REJECTION rule against the filtered target
        # distribution; penalized sampling composes too — the verify
        # head rebuilds each draft position's count prior from the
        # fed tokens, so every position is penalized by exactly the
        # context a 1-token-at-a-time engine would have seen. The
        # output DISTRIBUTION therefore matches draft_k=0 sampling,
        # and the greedy path keeps its exact token-identity verify.
        self.spec_sampling = (self.draft_k > 0
                              and self.sampling.strategy != "greedy")
        # device-resident multi-tick decode (docs/SERVING.md "Device-
        # resident decode"): with ticks_per_dispatch=N>1, pure-decode
        # dispatches run N ticks inside ONE lax.while_loop around the
        # mixed step — the host regains control only on per-slot
        # events (finish/overflow) or when the tick budget runs out.
        # "auto" sizes N per dispatch from measured step/host times
        # (staging width stays the fixed maximum, 8). N=1 keeps the
        # legacy single-tick path byte-for-byte.
        self._ticks_auto = ticks_per_dispatch == "auto"
        tp = 8 if self._ticks_auto else int(ticks_per_dispatch)
        if tp < 1:
            raise ValueError(
                f"ticks_per_dispatch={ticks_per_dispatch!r} must be "
                ">= 1 (or 'auto')")
        self.ticks_per_dispatch = tp
        # ISSUE 19: speculation and penalized sampling now run INSIDE
        # the device loop (on-device n-gram drafting from the token
        # ring + count-histogram penalties), so the PR 18 single-tick
        # fallbacks are gone — ticks_per_dispatch > 1 always takes
        # the while_loop path
        self._multitick = tp > 1
        # operator-visible speculation state (tools/metrics_dump.py):
        # off (draft_k=0) / host (1-tick host n-gram drafting) /
        # device (drafting traced into the multi-tick loop body)
        self.speculation_mode = (
            "off" if self.draft_k == 0
            else "device" if self._multitick else "host")
        # block-sparse paged decode attention (ISSUE 15, docs/
        # SERVING.md "Long-context serving"): with `sparse_blocks=B`,
        # every decode/verify query scores the slot's candidate blocks
        # against per-block channel-wise min/max key summaries
        # (Quest-style upper bound) and attends only a FIXED budget of
        # blocks — B top-scoring plus the first block (attention sink)
        # and a recency window of `sparse_recent` blocks (always
        # including the in-flight tail, widened so a K-wide verify
        # group's own writes are always resident). Fixed width means
        # fixed shapes: sparsity never recompiles, and `sparse_blocks
        # >= allocated blocks` is token-identical to the dense engine.
        if sparse_blocks == "auto":
            # tuned sparse budget (ISSUE 17 satellite): the smallest
            # block budget that met the >=99% needle-agreement floor
            # under `serving.sparse_budget.tune_sparse_budget`, keyed
            # by head geometry; a cold cache falls back to the
            # hand-picked 8 of docs/SERVING.md
            from ..ops.pallas import autotune as _kt
            tuned = _kt.ensure(
                "sparse_budget", _kt.shape_bucket(H, Dh),
                np.dtype(np.float32),
                {"sparse_blocks": 8,
                 "sparse_recent": int(sparse_recent)})
            sparse_blocks = tuned["sparse_blocks"]
            sparse_recent = tuned.get("sparse_recent", sparse_recent)
        self.sparse_blocks = (None if sparse_blocks is None
                              else int(sparse_blocks))
        self._sparse = self.sparse_blocks is not None
        self.sparse_table_width = 0
        self._sparse_recent = 0
        if self._sparse:
            if self.sparse_blocks < 1:
                raise ValueError(
                    f"sparse_blocks={sparse_blocks} must be >= 1 "
                    "(or None for dense decode attention)")
            K_w = self.draft_k + 1
            # the recency window must cover every block a verify
            # group's K fed tokens can span, so the group's own
            # just-written keys are always attended
            self._sparse_recent = max(
                int(sparse_recent),
                1 + -(-(K_w - 1) // self.block_size))
            self.sparse_table_width = min(
                mbps, 1 + self._sparse_recent + self.sparse_blocks)
        # `track_summaries=True` maintains the block summaries WITHOUT
        # the sparse decode region: the prefill-role half of a sparse
        # disaggregated fleet (docs/SERVING.md) — prefill runs at
        # dense speed paying only the append-side scatter, while its
        # exported blocks carry the summary rows a sparse decode
        # replica's kv_meta requires
        self._track_summaries = (self._sparse if track_summaries
                                 is None else bool(track_summaries))
        if self._sparse and not self._track_summaries:
            raise ValueError(
                "sparse_blocks needs the block summaries; don't pass "
                "track_summaries=False on a sparse engine")
        self.token_budget = batcher.choose_token_budget(
            max_slots, self.block_size, token_budget,
            verify_width=self.draft_k + 1, role=self.role,
            reserve_region=self._sparse)
        dtype = cache_dtype or getattr(model, "_gen_cache_dtype",
                                       "bfloat16")
        # `device`: the one chip this replica lives on (None = jax's
        # default device). Weights, KV pools and the rng are COMMITTED
        # to it, so the mixed step — and every pool copy/import jit —
        # runs there whatever the process default is: four one-chip
        # replicas of a router sit on four chips, not all on chip 0.
        # The pools are also created there, never staged through the
        # default device's HBM.
        self.device = device
        commit = self._commit = (lambda a: a) if device is None else \
            functools.partial(jax.device_put, device=device)
        # where a step's host arrays go, ALL in one call (`_step_args`)
        self._upload = functools.partial(jax.device_put, device=device)
        kinds = {}
        if self._block is not None:
            # K/V heads that do not fill the pools' tiles (30 of 32):
            # XLA then keeps a pool in another layout than the kernel
            # reads and copies the WHOLE pool between the two, every
            # step, a full layer (read on the chip: 12 copies of 1.45
            # GB). The pools hold whole tiles of heads instead; the
            # step pads q, k and v with zero heads and drops them again
            Hkv = arch.num_kv_heads
            self._kv_heads = Hkv if Hkv % 8 == 0 or Hkv != arch.num_heads \
                else -(-Hkv // 8) * 8
            kinds = dict(num_kv_heads=self._kv_heads,
                         layer_kinds=arch.layer_kinds)
            if "sliding" in arch.layer_kinds:
                # a window pool never runs dry: every slot a whole
                # window and a step's tokens
                kinds.update(
                    window=arch.window,
                    num_window_blocks=max_slots * min(mbps, -(-(
                        arch.window + self.token_budget)
                        // self.block_size) + 1) + 1)
            if self._diff is not None \
                    and self.token_budget <= max_slots \
                    * self._diff.block_length:
                raise ValueError(
                    f"token_budget={self.token_budget} leaves nothing "
                    f"beside {max_slots} slots' blocks of "
                    f"{self._diff.block_length} rows: a prompt could "
                    "never be prefilled")
            if "linear" in arch.layer_kinds:
                if self.token_budget - max_slots < arch.delta_chunk:
                    raise ValueError(
                        f"token_budget={self.token_budget} leaves "
                        f"{self.token_budget - max_slots} tokens beside "
                        f"{max_slots} decoding slots: a prefill chunk is "
                        f"cut to multiples of the delta rule's chunk "
                        f"({arch.delta_chunk}) and could never be fed")
                # a float32 state a head, and the convolution's last
                # inputs, a slot a linear layer
                kinds.update(
                    linear_state=(arch.linear_heads, arch.linear_key_dim,
                                  arch.linear_value_dim),
                    conv_tail=(arch.conv_width - 1, arch.conv_channels))
            if self._select is not None:
                if self._kv_heads != Hkv:
                    raise ValueError(
                        "attention through a selection is not built "
                        f"with padded K/V heads ({Hkv} -> "
                        f"{self._kv_heads})")
                # an indexer key a token a sparse layer, beside its K/V
                kinds.update(indexer_dim=self._select.head_dim)
        with jax.default_device(device):
            self.kv = PagedKVCache(
                L, H, Dh, num_blocks=num_blocks,
                block_size=self.block_size, max_slots=max_slots,
                max_blocks_per_slot=mbps, dtype=dtype, kv_dtype=kv_dtype,
                summaries=self._track_summaries, **kinds)
        self.kv._set_pools([commit(p) for p in self.kv._pools()])
        # radix prefix cache: cross-request KV reuse for shared prompt
        # heads (system prompts, few-shot templates, chat history) —
        # registers itself as the kv cache's eviction backstop
        self.prefix_cache = None
        if prefix_caching:
            from .prefix_cache import RadixPrefixCache
            self.prefix_cache = RadixPrefixCache(self.kv)
        # multi-LoRA adapter slots (ISSUE 14, docs/SERVING.md
        # "Multi-tenant serving"): fixed [L, K, ...] slot tensors per
        # hooked projection ride the mixed step as inputs; the host
        # cache pins/evicts/loads without ever changing a compiled
        # shape. The compute dtype matches the step's so deltas cast
        # once.
        cdt_name = getattr(model, "_compute_dtype", "float32")
        self.adapters = None
        if int(max_adapters):
            from .adapters import AdapterCache
            with jax.default_device(device):
                self.adapters = AdapterCache(
                    dec, max_adapters=int(max_adapters),
                    rank=int(lora_rank), alpha=lora_alpha,
                    dtype=cdt_name, clock=clock)
            for n in self.adapters.array_names:
                self.adapters._arrays[n] = commit(
                    self.adapters._arrays[n])
        from .draft import ngram_propose

        def _windowed_draft(tokens, _k=self.draft_k,
                            _ng=int(draft_ngram), _w=self.draft_ring):
            # the host proposer scans the SAME trailing window the
            # device ring holds, so a 1-tick host-drafting engine and
            # an N-tick device-drafting one propose identically —
            # the token-identity contract of the spec matrix tests
            return ngram_propose(tokens[-_w:], _k, max_ngram=_ng)

        self.scheduler = Scheduler(
            self.kv, max_slots=max_slots,
            token_budget=self.token_budget, clock=clock,
            draft_k=self.draft_k,
            draft_fn=_windowed_draft,
            device_draft=self._multitick and self.draft_k > 0,
            prefix_cache=self.prefix_cache,
            adapter_cache=self.adapters,
            reserve_region=self._sparse,
            prefill_align=(
                1 if self._block is None
                else arch.delta_chunk if self.kv.linear_layers
                else self._diff.block_length if self._diff else 1),
            block_decoding=self._diff)
        self.scheduler.replica = self.name
        self.eos_token_id = eos_token_id
        self.clock = clock
        # the sampling key LIVES on the device: the step splits it and
        # returns the advanced chain, `_dispatch` rebinds it like the
        # pools and nothing reads it back
        self._rng = commit(jax.random.PRNGKey(int(seed)))
        # the host loop is a pipeline one step deep (docs/SERVING.md
        # "Dispatching ahead") where nothing the NEXT plan needs exists
        # only on the device: not with host drafting (the drafter reads
        # tokens), not with penalized sampling (the counts are host
        # arrays), not in the device loop or the decide loop of block
        # diffusion (they have loops of their own), not on a prefill
        # replica (its requests park at their first token). Decided
        # here, once, from what the engine sees of itself. `_ahead`
        # shapes the compiled step (one more argument: the sampled
        # tokens of the step before, which never leave the device);
        # `_depth` is how far the loop runs ahead NOW: 1, or 0, which is
        # the synchronous order (dispatch, read back, emit)
        self._ahead = (self.draft_k == 0 and not self._multitick
                       and self._diff is None and role != "prefill"
                       and not batcher.needs_history(self.sampling))
        self._depth = int(self._ahead)
        self._prev_tokens = commit(jnp.zeros((max_slots,), jnp.int32)) \
            if self._ahead else None
        self._inflight = None       # the `_Flight` not read back yet
        self.steps_ahead = 0        # steps dispatched over an unread one
        self.ahead_wasted_rows = 0  # rows fed to a request that had ended
        # cast float params to the compute dtype ONCE (same discipline
        # as generation.generate: a per-step astype re-reads the full
        # parameter set every token)
        cdt = jnp.dtype(cdt_name)
        if self._block is not None:
            # the block's weights are made in the compute dtype and
            # held ONCE: the step takes the model's own tree
            self._arrays = jax.tree.map(commit, self._block.weights)
            self._names = []
        else:
            self._arrays = [
                commit(a.astype(cdt)
                       if a.dtype in (jnp.float32, jnp.float64) else a)
                for a in (t._data for t in model._gen_tensors())]
            # the engine owns its decoder-param NAME list (a copy of
            # the model's): engine-side expert quantization below may
            # extend it with scale entries the float model never had
            self._names = list(model._dec_names)
        # engine-side weight-only expert quantization (ISSUE 14):
        # serve a float/bf16 MoE stack with int8 or packed-int4
        # experts without rebuilding the model — the expert arrays in
        # self._arrays are quantized in place and the step cfg carries
        # the matching moe_quant_bits
        self.moe_weight_dtype = moe_weight_dtype
        self._moe_weight_bits = 0
        if moe_weight_dtype is not None:
            self._quantize_moe_experts(str(moe_weight_dtype))
        # the step's plan: ONE flat int32 buffer a step, laid out once
        # from what the engine sees of itself (budget, slots, the kinds
        # of table, adapters). The packer and the compiled step share
        # the layout; two buffers alternate, so the one a dispatched
        # step may still be reading is never the one being packed
        self.plan_layout = batcher.PlanLayout(
            self.token_budget, max_slots,
            [(n, t.shape) for n, t in zip(
                ("block_tables", "window_tables"), self.kv.tables())],
            adapters=self.adapters is not None,
            sample_rows=self._diff.block_length if self._diff else 1)
        self._plan_buffers = (batcher.PlanBuffers(self.plan_layout),
                              batcher.PlanBuffers(self.plan_layout))
        self._plan_flip = 0
        # quantized pools donate their scale arrays and summary-
        # tracking pools their min/max rows alongside the K/V pools,
        # so every in-step pool write aliases in place
        donate = tuple(range(1, 1 + len(self.kv._pools())))
        step_fn = self._build_step()
        if self._multitick:
            # the while_loop wraps the RESULT of _build_step (for the
            # TP engine that's the shard_map'ed body, so the loop sits
            # OUTSIDE the mesh partitioning) and shares the single
            # serving_mixed_step compile budget: n_ticks is a traced
            # scalar, so mixed 1-tick and pure-decode N-tick
            # dispatches run the same executable
            step_fn = self._build_multitick(step_fn)
        self._step_fn = instrumented_jit(
            step_fn, STEP_FN_NAME, donate_argnums=donate)
        self._aot_step = False
        # multi-tick host runtime state: the deferred observability
        # lane (dispatch k's metrics/flight flush after dispatch k+1
        # launches), and the measured-time EMAs the "auto" tick
        # heuristic sizes dispatches from
        self._deferred = None
        self._tick_ema = None        # seconds per device tick
        self._gap_ema = None         # host seconds between dispatches
        self._last_harvest = None
        self.dispatches_run = 0
        self.device_ticks_run = 0
        self.host_stall_total = 0.0
        self.early_exit_counts = {"finish": 0, "overflow": 0,
                                  "reject": 0}
        # host mirrors of the cumulative draft economics (both the
        # host-drafting 1-tick path and the device loop's spec stats
        # fold in here; bench/smoke contracts read them directly)
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        if _pmetrics._enabled:
            # operators see WHY a replica is or isn't speculating:
            # exactly one mode label reads 1 (tools/metrics_dump.py)
            for m in ("off", "host", "device"):
                smetrics.SERVING_SPECULATION_STATE.labels(m).set(
                    1.0 if m == self.speculation_mode else 0.0)
        # fleet control plane (ISSUE 17): checkpoint version label
        # (rides router_requests_total + trace spans) and the ONE
        # jitted budget-1 weight-swap cast shared by every rolling-
        # upgrade flip on this engine (built lazily on first swap)
        self.weights_version = "v0"
        self._swap_fn = None
        # register this engine's paged-kernel shape buckets with the
        # autotuner (ISSUE 11): keys derive from the token budget /
        # slot count / per-shard head slice, so the tuner-cache audit
        # (tools/kernel_coverage.py --tuner-audit) can flag buckets
        # serving traffic hits that hold no tuned entry. Pure host
        # dict probes — the step itself is untouched.
        self._kernel_buckets = self._note_kernel_buckets()
        self._counts_seen = {}    # counter: its count, last snapshot
        self._released_seen = 0          # window blocks, since a record
        self._logits_issued = None       # (heads, the kernel's count)
        #: the last step's float32 logits at the sample rows,
        #: [max_slots, V] on the device (a model-provided block only;
        #: [max_slots, L, V], a row a position of the slot's block,
        #: where the model decodes by blocks)
        self.sample_logits = None
        #: a model with sparse layers: the selections of the same rows,
        #: bool [sparse layers, max_slots, max_slot_tokens] on the
        #: device (True: the row attended the key at this position);
        #: read by nothing but a caller who holds them against a
        #: reference
        self.sample_selection = None
        #: block decoding: called for every slot pass with (request,
        #: block's first position, ids fed, positions decided before,
        #: positions the pass decided, their tokens); a commit decides
        #: none. What a caller holds the engine's passes against
        self.on_block_pass = None
        self.steps_run = 0
        # block-sparse decode accounting (host mirrors of the fixed
        # selection arithmetic — the per-step selected count is
        # min(allocated, sparse_table_width) by construction, so the
        # metrics need no extra device readback)
        self.sparse_candidate_blocks = 0
        self.sparse_selected_blocks = 0
        # cumulative MoE routing state (host mirrors of the per-step
        # device stats; the smoke contracts read these directly)
        self.moe_expert_counts = np.zeros(max(self.num_experts, 1),
                                          np.float64)
        self.moe_dropped_total = 0.0
        self.moe_last_aux = 0.0
        # per-engine step flight recorder (serving.tracing): one host
        # record per step, noted only while tracing is enabled;
        # registered so profiler chrome export / summary() merge it
        self.flight = _tracing.StepFlightRecorder(self.name, self.role)
        _tracing.register_flight_recorder(self.flight,
                                          self.step_op_scopes)
        self._op_scopes = None           # `step_op_scopes()`, kept
        # where the host's time goes (tracing.HOST_PHASES): marked by
        # step() and by the frontend's step loop, only while tracing
        # is enabled; `_step_end` is when the last traced step ended
        self.phases = _tracing.PhaseMarker(self.clock)
        self._step_end = None
        self._trace_self = 0.0

    def _flight_extra(self):
        """Extra per-step flight-recorder fields; TPServingEngine
        overrides to stamp its mesh split."""
        return {}

    def _quantize_moe_experts(self, dtype_str):
        """Quantize the expert FFN stacks of `self._arrays` in place
        (weight-only int8, or nibble-packed int4 with fp16 scales) and
        extend `self._names` with the scale entries. Host-side, once,
        at build — the mixed step then reads int8/int4 expert bytes
        from HBM and dequantizes at the matmul (grouped kernel or
        einsum path alike). Refused on non-MoE stacks and on models
        that are already weight-only (requantizing int8 -> int4 would
        compound quantization error silently)."""
        import jax.numpy as jnp

        from ..incubate.nn.fused_transformer import \
            _quantize_expert_stack
        if dtype_str not in ("int8", "int4"):
            raise ValueError(
                f"moe_weight_dtype={dtype_str!r} not supported; use "
                "'int8' or 'int4'")
        if not self.num_experts:
            raise ValueError(
                "moe_weight_dtype needs a MoE decoder stack")
        if "ffn1_s" in self._names or "ffn2_s" in self._names:
            raise ValueError(
                "model experts are already weight-only quantized; "
                "build the float model and let the engine quantize, "
                "or pick the dtype at model build "
                "(FusedMultiTransformerMoeWeightOnly(moe_quant_bits=))")
        bits = 4 if dtype_str == "int4" else 8
        for wname in ("ffn1_w", "ffn2_w"):
            i = self._names.index(wname)
            w = self._arrays[2 + i]            # [L, E, In, Out]
            q, s = _quantize_expert_stack(
                jnp.asarray(w).astype(jnp.float32), bits)
            self._arrays[2 + i] = q
            sname = wname[:-2] + "_s"
            self._names.insert(i + 1, sname)
            self._arrays.insert(2 + i + 1, s)
        self._moe_weight_bits = bits

    def _note_kernel_buckets(self):
        """The (kernel, shape-bucket, dtype) keys this engine's mixed
        step resolves tuned configs under — one `kernel_config` probe
        each (recording cache hits/misses + the audit trail). The
        bucket derives from the token budget: with speculation the
        verify region [S, K] rides `paged_verify` and the remaining
        flat tokens `paged_ragged`; without, the whole [T] axis is one
        ragged bucket. Head counts are the PER-SHARD slice under TP
        (`_step_cfg`), so a TP=2 engine tunes different keys than
        TP=1 — topology is part of the key by construction, alongside
        the backend/device-count component `autotune.backend_key`
        already carries."""
        from ..ops.pallas import autotune as _kt
        if self._block is not None:
            return []       # the block's kernels take their shape defaults
        cfg = self._step_cfg()
        H, Dh, BS = cfg.num_heads, cfg.head_dim, self.block_size
        # key by the POOL dtype (int8 pools are int8, fp8 pools
        # float8_e4m3fn, fp pools their own dtype) — exactly what the
        # kernels' trace-time lookups resolve under
        dt = self.kv.k_pool.dtype
        T, S, K = self.token_budget, self.kv.max_slots, self.draft_k + 1
        dtn = np.dtype(dt).name
        keys = []
        if self._sparse:
            # the decode/verify region reads the SHORTENED tables: its
            # bucket carries the table width (sparse_table_width) so a
            # sparse winner can never alias a dense one
            keys.append(("paged_sparse",
                         _kt.shape_bucket(S, K, H, Dh, BS,
                                          self.sparse_table_width),
                         dtn))
            keys.append(("paged_ragged",
                         _kt.shape_bucket(max(T - S * K, 1), 1, H, Dh,
                                          BS), dtn))
        elif K > 1:
            keys.append(("paged_verify",
                         _kt.shape_bucket(S, K, H, Dh, BS), dtn))
            keys.append(("paged_ragged",
                         _kt.shape_bucket(max(T - S * K, 1), 1, H, Dh,
                                          BS), dtn))
        else:
            keys.append(("paged_ragged",
                         _kt.shape_bucket(T, 1, H, Dh, BS), dtn))
        for kernel, bucket, dtype in keys:
            # ensure(): a hit is one dict probe; a miss falls back to
            # the hand defaults — except under
            # PADDLE_TPU_KERNEL_AUTOTUNE=tune, where the registered
            # search runs HERE, at build time, before the step is ever
            # traced (the tuning-outside-the-jitted-step contract),
            # and persists the winner for every later engine
            _kt.ensure(kernel, bucket, dtype, default=None)
        return keys

    # ------------------------------------------------------- mixed step
    def _step_cfg(self):
        """The decoder config the step body runs under. The TP engine
        (`serving.distributed.tp_engine`) overrides this with the
        per-shard head count and an `mp_axis`, and `_step_body` then
        emits the matching psums — same math, sharded. Engine-side
        expert quantization overrides the cfg's moe bits so `_deq`/
        the grouped kernel dequantize what the engine actually packed."""
        import dataclasses
        cfg = self.model.decoder._cfg()
        if self._moe_weight_bits:
            cfg = dataclasses.replace(
                cfg, moe_quant_bits=self._moe_weight_bits)
        return cfg

    def _build_step(self):
        if self._block is not None:
            return self._block_step_body()
        return self._step_body(self._step_cfg())

    def _block_step_body(self):
        """The mixed step of a model that brings its own block: embed,
        then the layers UNROLLED — each of its own kind (window or
        full attention over its own K/V pool, or a linear layer over its
        slots' recurrent states; dense or expert FFN), each updating
        what it keeps in place — then final norm, head and sampling at
        the slots' sample rows. The engine owns what the block must not
        know: the paged pools, the kinds of block table, the states,
        the query runs, padding. One compile: every shape is the token
        budget's, the slot count's or the pool's.

        step(weights, k0, v0, k1, v1, ..., s0, c0, s1, c1, ..., plan,
        [the step before's tokens [max_slots], where the engine can
        dispatch ahead,] key) -> (tokens [max_slots], the pools and
        states in the same
        order (`kv._pools()`), the block's counters (`block.stat_names`,
        folded over the layers by `block.fold_stats`: the engine does
        not know what they count), float32 logits of the sample rows
        [max_slots, V], the key advanced). `plan` is the packed buffer
        (`plan_layout`: flat tokens, sample index, the block tables),
        sliced once, before the layers.

        A model that decodes by blocks (`arch.block_decoding`, block
        length L): attention is block-causal (`causal_block=L`; a
        prefill chunk and a block's run under the same mask), the
        sample index is `[max_slots, L]`, the rows of each slot's block,
        and in place of tokens the step returns int32 `[max_slots, 2,
        L]`: each row's greedy candidate, and the bits of its float32
        confidence (the softmax probability of the candidate): one
        array, one readback. The logits are `[max_slots, L, V]`. Which
        positions take their candidate is the host's (`_run_block_tick`):
        the step does not know which rows are masked."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas.flash_attention import ragged_paged_attention
        from ..ops.pallas.paged_attention import paged_runs
        block = self._block
        arch = block.arch
        kinds = arch.layer_kinds
        BS, T = self.block_size, self.token_budget
        sc = self.sampling
        max_run = min(T, _BLOCK_MAX_RUN)
        layout = self.plan_layout
        n_pools = len(self.kv._pools())
        # where a layer's arrays lie among the step's pools: K, V of the
        # i-th attention layer; state, tail of the j-th linear layer
        at = {li: 2 * i for i, li in enumerate(self.kv.attention_layers)}
        at.update({li: 2 * (len(at) + j) for j, li in
                   enumerate(self.kv.linear_layers)})
        linear = bool(self.kv.linear_layers)
        more_heads = self._kv_heads - arch.num_kv_heads
        diff, causal_block = self._diff, self._causal_block
        ahead = self._ahead
        sel = self._select
        if sel is not None:
            sparse = _SparseLayers(self, max_run)
            # a sparse layer's indexer-key pool: the last of the pools
            ix = {li: n_pools - len(self.kv.sparse_layers) + j
                  for j, li in enumerate(self.kv.sparse_layers)}
        if diff and max_run % causal_block:
            raise ValueError(
                f"block length {causal_block} does not divide the "
                f"kernel's longest run ({max_run}): a cut run would end "
                "inside a block")

        def step(weights, *rest):
            # every operation under one scope of `tracing.DEVICE_SCOPES`
            # (the block's own functions set theirs): HLO metadata only
            pools = list(rest[:n_pools])
            plan, *prev, key = rest[n_pools:]
            with jax.named_scope("plan_unpack"):
                f = layout.unpack(plan)
                token_ids, slot_ids, positions, sample_index = (
                    f["token_ids"], f["slot_ids"], f["positions"],
                    f["sample_index"])
                valid = slot_ids >= 0
                pos = jnp.where(valid, positions, 0)
                safe_slot = jnp.where(valid, slot_ids, 0)
                if ahead:
                    # a decode token the host never saw: the step before
                    # sampled it, and hands it over on the device
                    token_ids = batcher.take_prev_tokens(
                        token_ids, prev[0], safe_slot)
                tables = {"full": f["block_tables"]}
                if "window_tables" in f:
                    tables["sliding"] = f["window_tables"]
                # padding tokens write into the reserved NULL block
                wb = {k: jnp.where(valid, t[safe_slot, pos // BS], 0)
                      for k, t in tables.items()}
                wo = pos % BS
                runs = paged_runs(slot_ids, pos, max_run)
                ids = jnp.where(valid, token_ids, 0)
                rows_at = jnp.clip(sample_index.reshape(-1), 0, T - 1)
                if sel is not None:
                    split = sparse.split(runs, tables["full"], pos, valid,
                                         rows_at)
            with jax.named_scope("sample"):
                key, rng = jax.random.split(key)
            selections = []

            def attend(q, k, v, li, idx=None):
                kind = kinds[li]
                kp, vp = pools[at[li]], pools[at[li] + 1]
                if kind == "sparse":
                    o, kept = sparse.attend(
                        pools, at[li], ix[li], q, k, v, idx, wb["full"],
                        wo, slot_ids, pos, tables["full"], split)
                    selections.append(kept)
                    return o
                with jax.named_scope("kv_write"):
                    if more_heads:
                        q, k, v = (jnp.pad(a, ((0, 0), (0, more_heads),
                                               (0, 0)))
                                   for a in (q, k, v))
                    kp = kp.at[wb[kind], wo].set(k.astype(kp.dtype))
                    vp = vp.at[wb[kind], wo].set(v.astype(vp.dtype))
                pools[at[li]], pools[at[li] + 1] = kp, vp
                sliding = kind == "sliding"
                with jax.named_scope(
                        "attn_window" if sliding else "attn_full"):
                    o = ragged_paged_attention(
                        q, kp, vp, tables[kind], slot_ids, pos,
                        runs=runs, max_run=max_run,
                        window=arch.window if sliding else None,
                        causal_block=causal_block)
                    return o[:, :arch.num_heads] if more_heads else o

            extra = ()
            if linear:
                extra = (self._recur(pools, at, slot_ids, pos),)
            h = block.embed(arch, weights, ids)
            # one array: one readback
            stats = jnp.zeros((len(block.stat_names),), jnp.int32)
            for li, lw in enumerate(weights["layers"]):
                h, st = block.layer(arch, li, lw, h, pos, valid, attend,
                                    *extra)
                if st is not None:
                    with jax.named_scope("moe_experts"):
                        stats = block.fold_stats(stats, st)
            with jax.named_scope("head"):
                logits = block.head(arch, weights, h[rows_at]).astype(
                    jnp.float32)
            with jax.named_scope("sample"):
                tok = select_token(logits, rng, sc)
            if diff:
                with jax.named_scope("diffusion_confidence"):
                    # the candidate's softmax probability, float32
                    top = jnp.take_along_axis(logits, tok[:, None], 1)
                    conf = jnp.exp(top[:, 0] - jax.nn.logsumexp(
                        logits, axis=-1))
                    tok = jnp.stack(
                        [tok, jax.lax.bitcast_convert_type(
                            conf, jnp.int32)]).reshape(
                        2, *sample_index.shape).swapaxes(0, 1)
                    logits = logits.reshape(*sample_index.shape, -1)
            if sel is not None:
                return (tok, *pools, stats, logits,
                        jnp.stack(selections), key)
            return (tok, *pools, stats, logits, key)

        return step

    def _recur(self, pools, at, slot_ids, pos):
        """The `recur(x, g, beta, li, conv)` a linear layer of the block
        step calls (`models.olmo_hybrid.linear_mixer`), over the step's
        runs WITHOUT the `max_run` cut: one run a slot, so the runs
        touch distinct slots and are independent.

        (a) every token's last `conv_width` inputs side by side: its
        own run's earlier tokens, before them the slot's tail (zeros
        where the run starts at position 0), handed to the model's
        `conv`; the slot's new tail is the run's last inputs (a run
        shorter than the tail shifts it); (b) the delta rule over the
        runs from each slot's state (zero at position 0), the state
        read and written once a run: `gated_delta_ragged` takes q, k, v
        token major as the convolution leaves them and the step's chunk
        tables (`delta_chunks`, made once for all the linear layers);
        what it costs follows the chunks that hold tokens, a run of one
        token its one row; (c) slots with no run this step keep state
        and tail untouched, padding tokens change nothing."""
        import jax
        import jax.numpy as jnp

        from ..ops.pallas import gated_delta as gd
        from ..ops.pallas.paged_attention import paged_runs
        arch = self._block.arch
        T, S, W = self.token_budget, self.kv.max_slots, arch.conv_width
        R = min(S, T)                       # one run a slot
        with jax.named_scope("plan_unpack"):
            runs = paged_runs(slot_ids, pos, None)
            chunks = gd.delta_chunks(runs, T, S, arch.delta_chunk)
            _, start, length, rslot, first = runs
            r, valid, off, fresh = gd.token_runs(runs, T)
            slot = jnp.clip(rslot[r], 0, S - 1)
            t = jnp.arange(T, dtype=jnp.int32)
            ZERO = T + S * (W - 1)              # the row of zeros
            # a token's input `back` positions earlier: in its own run,
            # or in the slot's tail (rows oldest first), or before the
            # sequence
            src = [jnp.where(
                ~valid | ((off < back) & fresh), ZERO,
                jnp.where(off >= back, t - back,
                          T + slot * (W - 1) + (W - 1) + off - back))
                for back in range(W - 1, 0, -1)]
            # the last W - 1 inputs of each run, the same three ways
            i = jnp.arange(W - 1, dtype=jnp.int32)[None, :]
            o_run = (length[:R, None] - (W - 1)) + i   # offset in the run
            live = (jnp.arange(R) < runs[0][0])[:, None]
            rs = jnp.clip(rslot[:R], 0, S - 1)[:, None]
            new_src = jnp.where(
                ~live | ((o_run < 0) & (first[:R, None] == 0)), ZERO,
                jnp.where(o_run >= 0, start[:R, None] + o_run,
                          T + rs * (W - 1) + (W - 1) + o_run))
            to_slot = jnp.where(live[:, 0], rs[:, 0], S)     # S: dropped

        def recur(x, g, beta, li, conv):
            state, tail = pools[at[li]], pools[at[li] + 1]
            with jax.named_scope("lin_conv"):
                rows = jnp.concatenate(
                    [x, tail.reshape(S * (W - 1), -1).astype(x.dtype),
                     jnp.zeros((1, x.shape[1]), x.dtype)])
                q, k, v = conv(jnp.stack(
                    [rows[s] for s in src] + [x], axis=1))
                pools[at[li] + 1] = tail.at[to_slot].set(
                    rows[new_src].astype(tail.dtype), mode="drop")
            with jax.named_scope("gated_delta"):
                o, pools[at[li]] = gd.gated_delta_ragged(
                    q, k, v, g, beta, runs, state,
                    chunk=arch.delta_chunk, chunks=chunks)
            return o

        return recur

    def _step_body(self, cfg):
        import jax
        import jax.numpy as jnp

        from ..incubate.nn.fused_transformer import (
            _ffn_dense, _ffn_moe_tokens, _ln, _lora_delta, _maybe_psum,
            _mm, _qkv)
        from ..ops.pallas.flash_attention import (
            ragged_paged_attention, verify_paged_attention)
        from ..ops.pallas.paged_attention import layer_blocks, paged_runs

        from .kv_cache import FP8_MAX, SUMMARY_INIT, kv_jnp_dtype

        model = self.model
        names = list(self._names)
        L = cfg.num_layers
        BS = self.block_size
        T = self.token_budget
        S = self.kv.max_slots
        K = self.draft_k + 1          # verify width (1 = no speculation)
        sparse = self._sparse
        track = self._track_summaries  # summaries maintained on append
        Bt = self.sparse_table_width  # shortened table width (sparse)
        W_rec = self._sparse_recent   # forced recency window (blocks)
        MB = self.kv.max_blocks_per_slot
        # the reserved per-slot region: speculation reshapes it to
        # [S, K] for the verify entry; block-sparse decode reserves it
        # even at K == 1 so the selection is one fixed [S, ...] batch
        region_on = K > 1 or sparse
        R = S * K                     # region width when region_on
        sc = self.sampling
        quant = self.kv.quantized
        fp8 = self.kv.kv_dtype == "fp8_e4m3"
        use_hist = batcher.needs_history(sc)
        Vb = self._penalty_bins       # penalty count-histogram bins
        moe = cfg.num_experts > 0
        spec_sampling = self.spec_sampling
        lora = self.adapters is not None
        ad_names = tuple(self.adapters.array_names) if lora else ()
        K_ad = self.adapters.max_adapters if lora else 0
        layout = self.plan_layout
        ahead = self._ahead

        def quantize(x):
            """[T, H, Dh] fp -> (quantized values, [T, H] fp32
            scales): symmetric per-token-per-head amax scaling — to
            the int8 grid, or to the fp8 e4m3 finite range (scaling
            amax onto 448 spends the format's whole mantissa budget
            per entry; the clip keeps boundary values off the NaN
            cast). A pure function of the token's own K/V, so
            quantization is independent of append order, chunking and
            block sharing (the property the prefix-cache/preemption
            parity tests rely on)."""
            xf = x.astype(jnp.float32)
            if fp8:
                s = jnp.max(jnp.abs(xf), axis=-1) / FP8_MAX
                qv = xf / jnp.maximum(s, 1e-20)[..., None]
                qv = jnp.clip(qv, -FP8_MAX, FP8_MAX)
                return qv.astype(kv_jnp_dtype("fp8_e4m3")), s
            s = jnp.max(jnp.abs(xf), axis=-1) / 127.0
            q8 = jnp.round(xf / jnp.maximum(s, 1e-20)[..., None])
            return jnp.clip(q8, -127, 127).astype(jnp.int8), s

        def select_blocks(q_r, pos_r, block_tables, smin, smax, li):
            """Top-B block selection for the decode/verify region
            (ISSUE 15, Quest-style): score every candidate block of
            each slot by the channel-wise upper bound of q . k over
            the block's [min, max] summary box, force-keep the first
            block (attention sink) and the last `W_rec` blocks (the
            recency window — which always covers the group's own
            just-written keys), take the fixed top `Bt`, and emit

              * a SHORTENED `[S, Bt]` block table (selected blocks in
                their original order; NULL-padded when a slot holds
                fewer than Bt blocks), and
              * COMPACTED query positions `[S, K]` — each query's
                position translated into the shortened table's
                coordinates, so the kernels' `key_pos <= query_pos`
                mask stays exactly right: full selected blocks before
                the query's own block are wholly visible, the query's
                block is visible up to its true offset, and the NULL
                padding columns (compacted positions past the query)
                are never read through.

            With Bt >= the slot's allocated blocks the selection is
            the identity (same table prefix, same positions), which is
            what makes `sparse_blocks >= allocated` bit-identical to
            the dense engine.

            q_r [S, K, H, Dh] raw queries; pos_r [S, K] true
            positions; smin/smax [L, NB, H, Dh] the stacked summaries,
            layer `li`'s rows gathered in place."""
            from ..incubate.nn.fused_transformer import _maybe_psum
            qf = q_r.astype(jnp.float32)
            qpos = jnp.maximum(qf, 0.0)
            qneg = jnp.minimum(qf, 0.0)
            bt_r = block_tables[:S]                     # [S, MB]
            bt_l, (smin_f, smax_f) = layer_blocks(bt_r, li, smin, smax)
            sming = smin_f[bt_l]                        # [S, MB, H, Dh]
            smaxg = smax_f[bt_l]
            # ub(q, block) = sum_d max(q_d*min_d, q_d*max_d)
            #             = sum_d (max(q_d,0)*max_d + min(q_d,0)*min_d)
            # summed over heads: under TP each shard holds its head
            # slice, so the psum makes every shard select from the
            # GLOBAL head total — TP=2 selections match TP=1 exactly.
            # The psum must come BEFORE the max over the group's K
            # queries: max_k(a_k + b_k) != max_k(a_k) + max_k(b_k)
            # when different queries achieve each shard's maximum, so
            # a post-max psum would make TP=2 rank blocks differently
            # than TP=1 whenever speculation meets real sparsity
            score = (jnp.einsum("skhd,smhd->skm", qpos, smaxg)
                     + jnp.einsum("skhd,smhd->skm", qneg, sming))
            score = _maybe_psum(cfg, score)             # [S, K, MB]
            score = jnp.max(score, axis=1)              # [S, MB]
            n_blk = jnp.max(pos_r, axis=1) // BS + 1    # [S] allocated
            m_idx = jnp.arange(MB, dtype=jnp.int32)[None, :]
            forced = (m_idx == 0) | (m_idx >= (n_blk - W_rec)[:, None])
            score = jnp.where(forced, jnp.float32(jnp.inf), score)
            # candidates past the allocated prefix can never be
            # selected, whatever their (stale) summaries say
            score = jnp.where(m_idx < n_blk[:, None], score,
                              -jnp.float32(jnp.inf))
            _, sel = jax.lax.top_k(score, Bt)           # [S, Bt]
            selv = jnp.take_along_axis(score, sel, axis=1)
            # re-sort the selection into original table order (the
            # compaction below depends on it); slots with fewer than
            # Bt valid blocks sort their -inf picks to the end as MB
            ord_ = jnp.sort(jnp.where(selv > -jnp.float32(jnp.inf),
                                      sel, MB), axis=1)
            short_bt = jnp.where(
                ord_ < MB,
                jnp.take_along_axis(bt_r, jnp.minimum(ord_, MB - 1),
                                    axis=1),
                0).astype(jnp.int32)
            bq = pos_r // BS                            # [S, K]
            cnt = jnp.sum(ord_[:, None, :] < bq[:, :, None], axis=-1)
            pos_c = (cnt * BS + pos_r % BS).astype(jnp.int32)
            return short_bt, pos_c

        def verify_tokens(lv, tok, pools, key, token_ids, counts, rngs):
            """The step's outputs with a verify region: `lv` [S, K, V]
            the float32 logits of every verify position, `tok` the
            sample rows' tokens, `rngs` the three keys of rejection
            sampling (None for greedy verification)."""
            fed = token_ids[:R].reshape(S, K)
            if use_hist:
                # per-position count PRIORS (ISSUE 19): verify
                # position j scores the context [.., fed[0..j]];
                # fed[0] (the last accepted token) is already in the
                # base histogram, so the prior adds the running count
                # of fed[1..j] — each draft position is penalized by
                # exactly the context a 1-token engine would have seen
                inc = jax.nn.one_hot(fed[:, 1:] % Vb, Vb,
                                     dtype=jnp.float32)
                prior = counts.astype(jnp.float32)[:, None, :] \
                    + jnp.concatenate(
                        [jnp.zeros((S, 1, Vb), jnp.float32),
                         jnp.cumsum(inc, axis=1)], axis=1)
                lv = batcher.apply_count_penalties(lv, prior, sc)
            if not spec_sampling:
                # greedy scores for EVERY verify-region position:
                # tok_v[s, j] is the model's next token after slot s's
                # j-th fed token — the host accepts the longest draft
                # prefix matching it
                tok_v = jnp.argmax(lv, axis=-1).astype(jnp.int32)
                return ((tok, tok_v),) + pools + (key,)
            # REJECTION-SAMPLING verify (ISSUE 11 satellite): the
            # n-gram proposer is deterministic (a point-mass draft
            # distribution q), so the standard rule reduces to:
            # accept draft d at position j w.p. min(1, p_j(d)) where
            # p_j = softmax(filter_logits(...)) is EXACTLY the
            # distribution non-speculative sampling draws from; on
            # rejection, emit a sample of the residual
            # norm(max(p_j - q, 0)) = p_j with d removed; when every
            # draft is accepted the bonus token samples the full p at
            # the last fed position. Emitted tokens are therefore
            # p-distributed at every position — the output
            # DISTRIBUTION matches draft_k=0 sampling.
            fl = batcher.filter_logits(lv, sc)          # [S, K, V]
            # fed token at position j+1, scored by position j (last
            # column pads with 0 — the host never reads its verdict)
            nxt = jnp.concatenate(
                [fed[:, 1:], jnp.zeros((S, 1), jnp.int32)], axis=1)
            probs = jax.nn.softmax(fl, axis=-1)
            p_draft = jnp.take_along_axis(
                probs, nxt[..., None], axis=-1)[..., 0]  # [S, K]
            u = jax.random.uniform(rngs[0], (S, K))
            acc = u < p_draft
            # residual resample: p with the rejected draft removed
            res_mask = jax.nn.one_hot(nxt, fl.shape[-1],
                                      dtype=jnp.bool_)
            tok_res = jax.random.categorical(
                rngs[1], jnp.where(res_mask, -1e9, fl),
                axis=-1).astype(jnp.int32)
            tok_v = jax.random.categorical(
                rngs[2], fl, axis=-1).astype(jnp.int32)
            return ((tok, tok_v, tok_res, acc),) + pools + (key,)

        def step(arrays, k_pool, v_pool, *rest):
            # static signature variants (one compile each way):
            # quantized pools add (k_scale, v_scale) after the pools
            # and summary-tracking pools (k_sum_min, k_sum_max) after
            # those — the kv_cache._pools() order; adapter slot
            # tensors follow them; then the packed plan (`plan_layout`:
            # flat tokens, sample index, block table, per-token adapter
            # ids), sliced ONCE here, before the layer scan; an engine
            # that can dispatch ahead adds the [S] tokens the step
            # before sampled (a decode token whose id is the sentinel
            # `batcher.PREV_TOKEN` is taken from them); active
            # logit processors add the [S, Vb] token-count histogram
            # before the key (ISSUE 19: the count form replaces the
            # [S, W] history tensor so the multi-tick loop can advance
            # it per accepted token). The key is the carried CHAIN: the
            # step splits it and returns the advanced chain last
            # every operation under one scope of `tracing.DEVICE_SCOPES`:
            # HLO metadata only, the program is the same
            rest = list(rest)
            k_scale = v_scale = counts = None
            k_sum_min = k_sum_max = None
            if quant:
                k_scale, v_scale = rest[:2]
                rest = rest[2:]
            if track:
                k_sum_min, k_sum_max = rest[:2]
                rest = rest[2:]
            ad_arrays = ()
            if lora:
                ad_arrays = rest[:len(ad_names)]
                rest = rest[len(ad_names):]
            plan = rest.pop(0)
            prev = rest.pop(0) if ahead else None
            if use_hist:
                counts = rest.pop(0)
            (key,) = rest
            with jax.named_scope("sample"):
                key, rng = jax.random.split(key)
            n_dec = len(names)
            we, pe = arrays[0], arrays[1]
            dec_arrays = arrays[2:2 + n_dec]
            lnw, lnb, head = arrays[-3], arrays[-2], arrays[-1]
            params = dict(zip(names, dec_arrays))
            with jax.named_scope("plan_unpack"):
                f = layout.unpack(plan)
                token_ids, slot_ids, positions, sample_index = (
                    f["token_ids"], f["slot_ids"], f["positions"],
                    f["sample_index"])
                block_tables = f["block_tables"]
                adapter_ids = f["adapter_ids"] if lora else None
                if lora:
                    # the [L, K, ...] slot tensors join the scanned
                    # params so each layer's xs slice carries its own
                    # adapter rows; ONE [T, K] one-hot feeds every
                    # layer's deltas
                    params.update(dict(zip(ad_names, ad_arrays)))
                    lora_oh = jax.nn.one_hot(adapter_ids, K_ad,
                                             dtype=jnp.float32)
                else:
                    lora_oh = None
                valid = slot_ids >= 0
                pos = jnp.where(valid, positions, 0)
                safe_slot = jnp.where(valid, slot_ids, 0)
                if ahead:
                    # a decode token the host never saw: the step before
                    # sampled it, and hands it over on the device
                    token_ids = batcher.take_prev_tokens(
                        token_ids, prev, safe_slot)
                # padding tokens write into the reserved NULL block
                wb = jnp.where(valid,
                               block_tables[safe_slot, pos // BS], 0)
                wo = pos % BS
                # the query runs the ragged kernel walks (a decode token
                # a run of 1, a prefill chunk one run): the same for
                # every layer, so derived here, once a step
                r0 = R if region_on else 0
                runs = paged_runs(slot_ids[r0:], pos[r0:])
                sidx = jnp.clip(sample_index, 0, T - 1)
            with jax.named_scope("embed"):
                x = model._embed(we, pe, token_ids, pos)      # [T, D]

            def attention(q, kp, vp, ksc, vsc, smin, smax, li):
                """Layer `li`'s attention of the flat tokens over the
                pools just appended to -> [T, H, Dh]."""
                if sparse:
                    # region queries attend the SHORTENED tables: the
                    # kernels read Bt blocks per slot instead of the
                    # whole context, and the compacted positions keep
                    # the causal mask exact; prefill chunks (whose
                    # queries sit mid-prompt) keep the dense path
                    q_r = q[:R].reshape(S, K, cfg.num_heads,
                                        cfg.head_dim)
                    pos_r = pos[:R].reshape(S, K)
                    short_bt, pos_c = select_blocks(
                        q_r, pos_r, block_tables, smin, smax, li)
                    if K == 1:
                        ar = ragged_paged_attention(
                            q[:R], kp, vp, short_bt,
                            slot_ids[:R], pos_c[:, 0], ksc, vsc,
                            kernel_name="paged_sparse", layer=li)
                    else:
                        ar = verify_paged_attention(
                            q_r, kp, vp, short_bt,
                            jnp.arange(S, dtype=jnp.int32), pos_c,
                            ksc, vsc, kernel_name="paged_sparse",
                            layer=li).reshape(
                            R, cfg.num_heads, cfg.head_dim)
                    ap = ragged_paged_attention(
                        q[R:], kp, vp, block_tables,
                        slot_ids[R:], pos[R:], ksc, vsc, runs=runs,
                        layer=li)
                    return jnp.concatenate(
                        [ar.reshape(R, cfg.num_heads, cfg.head_dim),
                         ap], axis=0)
                if K == 1:
                    return ragged_paged_attention(
                        q, kp, vp, block_tables, slot_ids, pos,
                        ksc, vsc, runs=runs, layer=li)
                # the fixed verify region (slot s owns flat tokens
                # [s*K, (s+1)*K)) runs through the verify-shaped
                # entry — ONE block-table gather per slot instead of
                # one per flat token; prefill chunks keep the
                # flat-token ragged path
                qv = q[:R].reshape(S, K, cfg.num_heads, cfg.head_dim)
                av = verify_paged_attention(
                    qv, kp, vp, block_tables,
                    jnp.arange(S, dtype=jnp.int32),
                    pos[:R].reshape(S, K), ksc, vsc, layer=li)
                ap = ragged_paged_attention(
                    q[R:], kp, vp, block_tables,
                    slot_ids[R:], pos[R:], ksc, vsc, runs=runs,
                    layer=li)
                return jnp.concatenate(
                    [av.reshape(R, cfg.num_heads, cfg.head_dim),
                     ap], axis=0)

            def layer(carry, xs):
                at = 3
                h, kp, vp = carry[:3]
                ksc = vsc = smin = smax = None
                if quant:
                    ksc, vsc = carry[at:at + 2]
                    at += 2
                if track:
                    smin, smax = carry[at:at + 2]
                    at += 2
                ms = carry[-1] if moe else None
                pl, li = xs
                with jax.named_scope("attn_qkv"):
                    hn = _ln(h, pl["ln_s"], pl["ln_b"], cfg.epsilon)
                    q, k, v = _qkv(cfg, pl, hn[None], lora_oh=lora_oh)
                    q, k, v = q[0], k[0], v[0]              # [T, H, Dh]
                with jax.named_scope("kv_write"):
                    if quant:
                        # quantize-on-append: int8/fp8 payload + per-
                        # entry scales land at the same (block, offset)
                        # coords
                        kq, ks_new = quantize(k)
                        vq, vs_new = quantize(v)
                        kp = kp.at[li, wb, wo].set(kq)
                        vp = vp.at[li, wb, wo].set(vq)
                        ksc = ksc.at[li, wb, wo].set(ks_new)
                        vsc = vsc.at[li, wb, wo].set(vs_new)
                    else:
                        kp = kp.at[li, wb, wo].set(k.astype(kp.dtype))
                        vp = vp.at[li, wb, wo].set(v.astype(vp.dtype))
                    if track:
                        # summary update on append: the offset-0 write
                        # of a block RESETS its row first (non-first
                        # tokens aim the reset at the NULL row), then
                        # one scatter-min/max folds every appended key
                        # in — well-defined even when one prefill chunk
                        # writes many entries of the same block, and a
                        # freed-then-reused block can never leak its
                        # previous owner's statistics
                        ksf = k.astype(jnp.float32)
                        rb = jnp.where(valid & (wo == 0), wb, 0)
                        smin = smin.at[li, rb].set(SUMMARY_INIT)
                        smax = smax.at[li, rb].set(-SUMMARY_INIT)
                        wbs = jnp.where(valid, wb, 0)
                        smin = smin.at[li, wbs].min(ksf)
                        smax = smax.at[li, wbs].max(ksf)
                with jax.named_scope("attn_full"):
                    attn = attention(q, kp, vp, ksc, vsc, smin, smax, li)
                with jax.named_scope("attn_out"):
                    attn = attn.reshape(T, cfg.num_heads * cfg.head_dim)
                    out = _mm(cfg, attn, pl["out_w"], pl.get("out_s"))
                    if lora_oh is not None:
                        # row-parallel LoRA: A holds this shard's head
                        # slice of the in axis, so the delta is a
                        # partial product that joins the psum right
                        # below
                        out = out + _lora_delta(
                            attn, pl["lora_out_a"], pl["lora_out_b"],
                            lora_oh)
                    # row-parallel reduction under TP (no-op when
                    # cfg.mp_axis is None): each shard holds the partial
                    # product of its own head slice; _ffn_dense below
                    # does the same for its row-parallel ffn2
                    out = _maybe_psum(cfg, out)
                    out = out + pl["out_b"].astype(out.dtype)
                    h = h + out
                # the capacity-routed experts of a GPT-MoE stack (router
                # included) read `moe_experts`; their norm and residual,
                # like a dense FFN's, `mlp`
                with jax.named_scope("mlp"):
                    hn = _ln(h, pl["ffn_ln_s"], pl["ffn_ln_b"],
                             cfg.epsilon)
                    if moe:
                        # per-token top-k routing into fixed capacity
                        # slots (padding tokens masked out by `valid`);
                        # overflow rides the residual — shapes never
                        # change, so the one-compile rule holds with MoE
                        # exactly as dense
                        with jax.named_scope("moe_experts"):
                            f, st = _ffn_moe_tokens(cfg, pl, hn, valid)
                            ms = jax.tree.map(jnp.add, ms, st)
                        h = h + f
                    else:
                        h = h + _ffn_dense(cfg, pl, hn, lora_oh=lora_oh)
                new_carry = (h, kp, vp)
                if quant:
                    new_carry += (ksc, vsc)
                if track:
                    new_carry += (smin, smax)
                if moe:
                    new_carry += (ms,)
                return new_carry, None

            carry0 = (x, k_pool, v_pool)
            if quant:
                carry0 += (k_scale, v_scale)
            if track:
                carry0 += (k_sum_min, k_sum_max)
            if moe:
                carry0 += ({"counts": jnp.zeros((cfg.num_experts,),
                                                jnp.float32),
                            "dropped": jnp.zeros((), jnp.float32),
                            "aux": jnp.zeros((), jnp.float32)},)
            # what the scan itself does (a layer's matrices sliced out
            # of the stacked weights, the loop's counter) reads
            # `attn_qkv`: on the chip the slice that costs is the qkv
            # matrix's; the layer's operations keep their inner scopes
            with jax.named_scope("attn_qkv"):
                carry, _ = jax.lax.scan(layer, carry0,
                                        (params, jnp.arange(L)))
            moe_stats = carry[-1] if moe else None
            if moe:
                # aux reported as the per-layer mean balance loss
                moe_stats = dict(moe_stats,
                                 aux=moe_stats["aux"] / float(L))
            n_pool = 2 + (2 if quant else 0) + (2 if track else 0)
            x = carry[0]
            pools = tuple(carry[1:1 + n_pool])
            if moe:
                pools += (moe_stats,)
            with jax.named_scope("head"):
                xf = _ln(x, lnw, lnb, cfg.epsilon)
                h_last = xf[sidx]                      # [max_slots, D]
                logits = jnp.matmul(h_last, head.astype(h_last.dtype))
            with jax.named_scope("sample"):
                rngs = None
                if spec_sampling:
                    rng, *rngs = jax.random.split(rng, 4)
                tok = select_token(logits, rng, sc, counts=counts)
            if K == 1:
                return (tok,) + pools + (key,)
            with jax.named_scope("head"):
                hv = xf[:R].reshape(S, K, -1)
                logits_v = jnp.matmul(hv, head.astype(hv.dtype))
                lv = logits_v.astype(jnp.float32)
            with jax.named_scope("sample"):
                return verify_tokens(lv, tok, pools, key, token_ids,
                                     counts, rngs)


        return step

    def _build_multitick(self, base_step):
        """Wrap the one-tick mixed step in a `lax.while_loop` that runs
        up to `n_ticks` decode ticks per host dispatch (docs/SERVING.md
        "Device-resident decode").

        Call signature = the one-tick step's, with the control tail
        appended AFTER the key (params stay arg 0, donated pools stay
        1..n, so donation and the AOT export path are untouched):

            ..., plan, [counts,] key, n_ticks, eos [S], remain [S],
            cap [S][, slot_ad]

        `key` is the CHAIN key, as in the one-tick program: the base
        step splits it once per executed tick and hands the advanced
        chain back, the loop carries it, so an N-tick dispatch consumes
        the identical subkey sequence N one-tick steps would
        (seeded-sampling token identity).

        Tick 0 consumes the host-packed plan verbatim (bit-identity
        with the single-tick dispatch); ticks >= 1 rebuild the
        pure-decode inputs in the packed buffer (`plan_layout.replace`:
        the flat tokens change, the tables stay) by scattering each
        live slot's previous token at its pack-time anchor
        (`sample_index` — the dense layout's packed index, the sparse
        region's own slot index),
        which reproduces exactly what the host packer would have built
        for the next step. The loop exits at the FIRST per-slot event
        so scheduling decisions (admission, preemption, expiry) happen
        at the same sequence boundaries a 1-tick engine would see.

        With speculation (`draft_k > 0`, ISSUE 19) the tail further
        appends the per-slot token RING (`ring [S, draft_ring]`,
        `rcnt [S]` — circular, token t at column t % draft_ring) and
        every tick widens to a verify group: the `jnp` n-gram drafter
        (`serving.draft.ngram_propose_device`) proposes from the ring,
        the verify head scores the group, the accept-length roll +
        bonus/residual token and the ring/count updates all happen
        in-loop — the multiplicative win (accept length x ticks per
        host round-trip) without a single host escape. Penalized
        sampling threads its `[S, penalty_vocab_bins]` count histogram
        through the carry the same way.

        Outputs replace the token head with the control block
        `(staged [S, N*K], counts [S], events [S], ticks[,
        spec_proposed, spec_accepted, accept_hist [K]])`:
        `staged` is the -1-padded token staging buffer, `events` the
        per-slot bitmask (1 = finish: EOS or horizon; 2 = overflow:
        next tick would exceed the preallocated block capacity `cap`).
        Pools (and summed MoE stats) follow as before, and the
        advanced key comes last, where the one-tick program has it."""
        import jax
        import jax.numpy as jnp

        from .draft import ngram_propose_device, ring_chronological

        S = self.kv.max_slots
        T = self.token_budget
        N = self.ticks_per_dispatch
        K = self.draft_k + 1
        NG = self.draft_ngram
        Wr = self.draft_ring
        Vb = self._penalty_bins
        use_hist = batcher.needs_history(self.sampling)
        spec_sampling = self.spec_sampling
        lora = self.adapters is not None
        moe = self.num_experts > 0
        n_pools = len(self.kv._pools())
        n_ad = len(self.adapters.array_names) if lora else 0
        E = self.num_experts
        layout = self.plan_layout

        def multitick(arrays, *rest):
            rest = list(rest)
            pools0 = tuple(rest[:n_pools])
            rest = rest[n_pools:]
            ad_arrays = tuple(rest[:n_ad])
            rest = rest[n_ad:]
            plan0 = rest.pop(0)
            f = layout.unpack(plan0)
            token_ids, slot_ids, positions, sample_index = (
                f["token_ids"], f["slot_ids"], f["positions"],
                f["sample_index"])
            adapter_ids = f["adapter_ids"] if lora else None
            cnt0 = rest.pop(0) if use_hist else None
            rng0 = rest.pop(0)
            n_ticks = rest.pop(0)
            eos = rest.pop(0)
            remain = rest.pop(0)
            cap = rest.pop(0)
            slot_ad = rest.pop(0) if lora else None
            ring0 = rest.pop(0) if K > 1 else None
            rcnt0 = rest.pop(0) if K > 1 else None

            slot_iota = jnp.arange(S, dtype=jnp.int32)
            iota_k = jnp.arange(K, dtype=jnp.int32)[None, :]
            anchors = sample_index                       # [S]
            if K == 1:
                live0 = anchors >= 0
                dec0 = live0
                pos0 = jnp.where(
                    live0, positions[jnp.clip(anchors, 0, T - 1)], 0)
                last0 = jnp.zeros((S,), jnp.int32)
            else:
                # region layout: slot s owns flat [s*K, (s+1)*K); the
                # host packs only [last] there — decode membership,
                # last token and position read straight off the base
                # column. Prefill completions sample through the tok
                # head (anchors) and carry exactly one token.
                base_idx = slot_iota * K
                dec0 = slot_ids[base_idx] == slot_iota
                live0 = dec0 | (anchors >= 0)
                pos0 = jnp.where(dec0, positions[base_idx], 0)
                last0 = token_ids[base_idx]
                rows2d = base_idx[:, None] + iota_k      # [S, K]
            mstats0 = None
            if moe:
                mstats0 = {"counts": jnp.zeros((E,), jnp.float32),
                           "dropped": jnp.zeros((), jnp.float32),
                           "aux": jnp.zeros((), jnp.float32)}

            def cond(state):
                t, _rng, _pools, _staged, _counts, events, live = \
                    state[:7]
                return (t < n_ticks) & (
                    (t == 0)
                    | (~jnp.any(events > 0) & jnp.any(live)))

            def tick(state):
                (t, rng, pools_c, staged, counts, events, live,
                 prev_tok, cur_pos, mstats, cnt, ring, rcnt,
                 spec_prop, spec_acc, spec_hist) = state
                first = t == 0
                live_dec = live & dec0
                if K == 1:
                    # scatter rebuild at the pack-time anchors; dead
                    # slots aim at T and are dropped
                    sa = jnp.where(live, anchors, T).astype(jnp.int32)
                    tid = jnp.where(
                        first, token_ids,
                        jnp.zeros((T,), jnp.int32)
                        .at[sa].set(prev_tok, mode="drop"))
                    sid = jnp.where(
                        first, slot_ids,
                        jnp.full((T,), -1, jnp.int32)
                        .at[sa].set(slot_iota, mode="drop"))
                    pid = jnp.where(
                        first, positions,
                        jnp.zeros((T,), jnp.int32)
                        .at[sa].set(cur_pos, mode="drop"))
                    si = jnp.where(first, sample_index,
                                   jnp.where(live, anchors, -1))
                    aid = None
                    if lora:
                        aid = jnp.where(
                            first, adapter_ids,
                            jnp.zeros((T,), jnp.int32)
                            .at[sa].set(slot_ad, mode="drop"))
                    fed = None
                    k_eff = None
                else:
                    # ---- on-device draft: widen each live decode to
                    # a verify group [last, d_1..d_{K-1}] proposed by
                    # the traced n-gram scan over the token ring.
                    # EVERY tick rebuilds the region (tick 0 included:
                    # the host packed only the base column), while
                    # tick 0 keeps the packed prefill chunks past it.
                    view = ring_chronological(ring, rcnt)
                    drafts = ngram_propose_device(view, rcnt, K - 1,
                                                  max_ngram=NG)
                    fed = jnp.concatenate(
                        [prev_tok[:, None], drafts], axis=1)  # [S, K]
                    rows = jnp.where(live_dec[:, None], rows2d, T)
                    tid = jnp.where(first, token_ids,
                                    jnp.zeros((T,), jnp.int32))
                    tid = tid.at[rows].set(fed, mode="drop")
                    sid = jnp.where(first, slot_ids,
                                    jnp.full((T,), -1, jnp.int32))
                    sid = sid.at[rows].set(
                        jnp.broadcast_to(slot_iota[:, None], (S, K)),
                        mode="drop")
                    pid = jnp.where(first, positions,
                                    jnp.zeros((T,), jnp.int32))
                    pid = pid.at[rows].set(
                        cur_pos[:, None] + iota_k, mode="drop")
                    si = jnp.where(first, sample_index,
                                   jnp.full((S,), -1, jnp.int32))
                    aid = None
                    if lora:
                        aid = jnp.where(first, adapter_ids,
                                        jnp.zeros((T,), jnp.int32))
                        aid = aid.at[rows].set(
                            jnp.broadcast_to(slot_ad[:, None],
                                             (S, K)), mode="drop")
                    # per-tick draft clamp, mirroring the host
                    # drafter's horizon/capacity shrink: never past
                    # the request's remaining budget, never past the
                    # preallocated block frontier
                    k_eff = jnp.clip(
                        jnp.minimum(jnp.minimum(K - 1,
                                                remain - counts - 1),
                                    cap - cur_pos - 1), 0, K - 1)
                fields = dict(token_ids=tid, slot_ids=sid, positions=pid,
                              sample_index=si)
                if lora:
                    fields["adapter_ids"] = aid
                call = [arrays] + list(pools_c) + list(ad_arrays)
                call.append(layout.replace(plan0, **fields))
                if use_hist:
                    call.append(cnt)
                call.append(rng)
                *res, rng = base_step(*call)
                out0 = res[0]
                new_pools = res[1:]
                if moe:
                    mstats = jax.tree.map(jnp.add, mstats,
                                          new_pools[-1])
                    new_pools = new_pools[:-1]
                if K == 1:
                    tok = out0
                    emitted = tok[:, None]               # [S, 1]
                    e = jnp.where(live, 1, 0)
                    m = jnp.zeros((S,), jnp.int32)
                else:
                    if spec_sampling:
                        tok, tok_v, tok_res, acc = out0
                        flags = acc[:, :K - 1] & (
                            iota_k[:, :K - 1] < k_eff[:, None])
                        m = jnp.sum(jnp.cumprod(
                            flags.astype(jnp.int32), axis=1), axis=1)
                        # accepted drafts re-emit the fed tokens, then
                        # the bonus sample (all k_eff accepted) or the
                        # residual resample at the rejection
                        fin = jnp.where(
                            (m == k_eff)[:, None],
                            jnp.take_along_axis(tok_v, m[:, None], 1),
                            jnp.take_along_axis(tok_res, m[:, None],
                                                1))[:, 0]
                        emitted = jnp.concatenate(
                            [fed[:, 1:], jnp.zeros((S, 1), jnp.int32)],
                            axis=1)
                        emitted = jnp.where(iota_k == m[:, None],
                                            fin[:, None], emitted)
                    else:
                        tok, tok_v = out0
                        eq = (fed[:, 1:] == tok_v[:, :K - 1]) & (
                            iota_k[:, :K - 1] < k_eff[:, None])
                        m = jnp.sum(jnp.cumprod(
                            eq.astype(jnp.int32), axis=1), axis=1)
                        emitted = tok_v
                    e = m + 1
                    # prefill completions emit their single sampled
                    # token through the tok head, like a 1-wide group
                    is_anch = live & ~dec0
                    e = jnp.where(is_anch, 1,
                                  jnp.where(live, e, 0))
                    emitted = jnp.where(
                        is_anch[:, None],
                        jnp.where(iota_k == 0, tok[:, None], -1),
                        emitted)
                # EOS cut: the FIRST matching token inside the
                # emitted prefix truncates it and finishes the slot —
                # the host emit() replay lands on the same token
                val = iota_k < e[:, None]
                hit = val & (eos[:, None] >= 0) & (
                    emitted == eos[:, None])
                any_hit = jnp.any(hit, axis=1)
                e = jnp.where(any_hit,
                              jnp.argmax(hit, axis=1).astype(
                                  jnp.int32) + 1, e)
                if K == 1:
                    staged = staged.at[:, t].set(
                        jnp.where(live, emitted[:, 0], -1))
                else:
                    cols = jnp.where(
                        live[:, None] & (iota_k < e[:, None]),
                        counts[:, None] + iota_k, N * K)
                    staged = staged.at[
                        slot_iota[:, None], cols].set(
                        emitted, mode="drop")
                counts = counts + jnp.where(live, e, 0)
                finish = live & (any_hit | (counts >= remain))
                nxt = cur_pos + jnp.where(live_dec, e, 0)
                overflow = live & ~finish & (nxt >= cap)
                events = (events
                          | jnp.where(finish, 1, 0)
                          | jnp.where(overflow, 2, 0))
                if use_hist:
                    # fold the emitted tokens into the count
                    # histogram so the NEXT tick's penalties see them
                    # (exactly the host's per-step history rebuild)
                    if K == 1:
                        brow = jnp.where(live, slot_iota, S)
                        cnt = cnt.at[brow, emitted[:, 0] % Vb].add(
                            1.0, mode="drop")
                    else:
                        bcol = jnp.where(
                            live[:, None] & (iota_k < e[:, None]),
                            emitted % Vb, Vb)
                        cnt = cnt.at[slot_iota[:, None], bcol].add(
                            1.0, mode="drop")
                if K == 1:
                    prev_tok = emitted[:, 0]
                else:
                    prev_tok = jnp.where(
                        live_dec,
                        jnp.take_along_axis(
                            emitted,
                            jnp.maximum(e - 1, 0)[:, None],
                            axis=1)[:, 0],
                        prev_tok)
                    ridx = jnp.where(
                        live_dec[:, None] & (iota_k < e[:, None]),
                        (rcnt[:, None] + iota_k) % Wr, Wr)
                    ring = ring.at[slot_iota[:, None], ridx].set(
                        emitted, mode="drop")
                    rcnt = rcnt + jnp.where(live_dec, e, 0)
                    ld = live_dec.astype(jnp.int32)
                    spec_prop = spec_prop + jnp.sum(k_eff * ld)
                    spec_acc = spec_acc + jnp.sum(m * ld)
                    spec_hist = spec_hist + jnp.sum(
                        jax.nn.one_hot(jnp.clip(m, 0, K - 1), K,
                                       dtype=jnp.int32)
                        * ld[:, None], axis=0)
                live = live & ~finish & ~overflow
                return (t + 1, rng, tuple(new_pools), staged, counts,
                        events, live, prev_tok, nxt, mstats, cnt,
                        ring, rcnt, spec_prop, spec_acc, spec_hist)

            zi = jnp.zeros((), jnp.int32)
            state = (zi, rng0, pools0,
                     jnp.full((S, N * K), -1, jnp.int32),
                     jnp.zeros((S,), jnp.int32),
                     jnp.zeros((S,), jnp.int32), live0,
                     last0, pos0, mstats0, cnt0, ring0, rcnt0,
                     zi, zi,
                     jnp.zeros((K,), jnp.int32) if K > 1 else zi)
            state = jax.lax.while_loop(cond, tick, state)
            (t, rng, pools_f, staged, counts, events, _live, _tok,
             _pos, mstats, _cnt, _ring, _rcnt, spec_prop, spec_acc,
             spec_hist) = state
            ctrl = (staged, counts, events, t)
            if K > 1:
                ctrl += (spec_prop, spec_acc, spec_hist)
            out = (ctrl,) + tuple(pools_f)
            if moe:
                out += (mstats,)
            return out + (rng,)

        def scoped(*args):
            # what the loop does beside the base step (staging, counts,
            # events, the draft ring) reads `tick_control`; the base
            # step's operations keep their own, inner scopes
            with jax.named_scope("tick_control"):
                return multitick(*args)

        return scoped

    # ------------------------------------------------------------ intake
    def register_adapter(self, adapter_id, weights):
        """Register a LoRA finetune's host weights (see
        `serving.adapters.AdapterCache.register`); device slots are
        claimed lazily at admission."""
        if self.adapters is None:
            raise ValueError(
                "this engine was built without adapter support "
                "(ServingEngine(max_adapters=...))")
        return self.adapters.register(adapter_id, weights)

    def submit(self, prompt_ids, max_new_tokens=32, deadline=None,
               tenant="default", adapter_id=None, trace_id=None):
        """Queue one request. Returns the scheduler's Request handle
        (read `.output` / `.state` as the engine advances).
        `adapter_id` selects a registered LoRA adapter (None = base
        model, token-identical to an adapter-free engine)."""
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        maxpos = self.model.max_position_embeddings
        if len(prompt) + max_new_tokens > maxpos:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_position_embeddings "
                f"({maxpos})")
        if adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    "request names an adapter but this engine was "
                    "built without adapter support (max_adapters=0)")
            if not self.adapters.known(adapter_id):
                raise ValueError(
                    f"adapter {adapter_id!r} is not registered on "
                    "this engine (register_adapter first)")
        req = self.scheduler.submit(prompt, max_new_tokens,
                                    eos_token_id=self.eos_token_id,
                                    deadline=deadline, tenant=tenant,
                                    adapter_id=adapter_id,
                                    trace_id=trace_id)
        if _pmetrics._enabled:
            smetrics.SERVING_QUEUE_DEPTH.set(len(self.scheduler.queue))
        return req

    def cancel(self, req):
        """Abort a request (frontend cancellation). Blocks and prefix
        locks are reclaimed immediately. A token of it in flight is read
        back first: it was computed before the cancellation."""
        if req.in_flight:
            self.drain()
        ok = self.scheduler.cancel(req)
        if ok and _pmetrics._enabled:
            smetrics.SERVING_REQUESTS.labels("cancelled").inc()
        return ok

    # -------------------------------------------- migration (disagg)
    def _slot_chunk(self, req, first_block, last_block):
        """Export `req`'s table blocks [first_block, last_block) as one
        transport chunk (None when the range is empty)."""
        row = self.kv.slot_blocks(req.slot)
        ids = row[first_block:last_block]
        if not ids:
            return None
        from .distributed.transport import BlockChunk
        return BlockChunk(start=int(first_block), count=len(ids),
                          arrays=self.kv.export_blocks(ids))

    def export_unshipped(self, req):
        """Stream-ahead export for a prefill in flight: the FULL blocks
        written since the last call (a full block's contents are final
        — later chunks write later blocks, and decode writes land past
        the prompt), so the decode side holds most of the KV before
        the handoff ticket even exists. Returns a BlockChunk or None."""
        if req.slot < 0:
            return None
        full = int(self.kv.slot_lens[req.slot]) // self.block_size
        chunk = self._slot_chunk(req, req.shipped_blocks, full)
        if chunk is not None:
            req.shipped_blocks = full
        return chunk

    def extract_request(self, req):
        """Pull a resident request out of this engine for migration:
        export the blocks not yet streamed ahead (all of them for a
        decode shed), capture the host state, then free the slot.
        Returns the `MigrationTicket` the destination's
        `submit_migrated` consumes. Greedy parity contract: the ticket
        carries bit-exact KV (scales included) and the full token
        history, so the destination continues the stream exactly as
        this engine would have (docs/SERVING.md). A step in flight is
        read back first: the ticket carries every token computed."""
        self.drain()
        if req.slot < 0 or req.state not in ("decode", "handoff"):
            raise ValueError(
                f"request {req.req_id} not extractable "
                f"(state={req.state!r}, slot={req.slot})")
        from .distributed.transport import MigrationTicket
        slot_len = int(self.kv.slot_lens[req.slot])
        total = self.kv.blocks_for(slot_len)
        chunks = []
        tail = self._slot_chunk(req, req.shipped_blocks, total)
        if tail is not None:
            chunks.append(tail)
        ticket = MigrationTicket(
            prompt=list(req.prompt), output=list(req.output),
            max_new_tokens=req.max_new_tokens,
            eos_token_id=req.eos_token_id, deadline=req.deadline,
            tenant=req.tenant, slot_len=slot_len, total_blocks=total,
            kv_meta=self.kv.kv_meta(), chunks=chunks,
            submit_time=req.submit_time,
            first_token_time=req.first_token_time,
            cache_hit_tokens=req.cache_hit_tokens,
            preemptions=req.preemptions, created_at=self.clock(),
            adapter_id=req.adapter_id, trace_id=req.trace_id)
        if _tracing._enabled:
            _tracing.on_extracted(req, ticket, self.name)
        self.scheduler.extract(req)
        if _pmetrics._enabled:
            smetrics.SERVING_REQUESTS.labels("migrated").inc()
        return ticket

    def submit_migrated(self, ticket):
        """Admit a migrated request: validates the transported pool
        geometry against this engine's, then queues the ticket — the
        scheduler imports its blocks into a slot at the next plan (so
        the mixed step's shapes, and its one-compile contract, are
        untouched by the admission). Returns the Request handle. A
        step in flight is read back first."""
        self.drain()
        mine = self.kv.kv_meta()
        theirs = dict(ticket.kv_meta or {})
        if theirs != mine:
            raise ValueError(
                f"migrated KV geometry {theirs} does not match this "
                f"engine's {mine} — disaggregated replicas must share "
                "block_size/kv_dtype/layer geometry")
        covered = sum(c.count for c in ticket.chunks)
        if covered != ticket.total_blocks:
            raise ValueError(
                f"ticket carries {covered} blocks but declares "
                f"{ticket.total_blocks} — transport lost a chunk")
        aid = getattr(ticket, "adapter_id", None)
        if aid is not None and (self.adapters is None
                                or not self.adapters.known(aid)):
            raise ValueError(
                f"migrated request needs adapter {aid!r}, which is "
                "not registered on this engine — register every "
                "adapter on every replica of a migrating fleet "
                "(ReplicaRouter.register_adapter does)")
        req = self.scheduler.submit_migrated(ticket)
        if _pmetrics._enabled:
            smetrics.SERVING_QUEUE_DEPTH.set(len(self.scheduler.queue))
        return req

    def _slot_adapters(self):
        """Each slot's pinned adapter SLOT id (0: none, or the base
        model)."""
        slot_ad = np.zeros(self.kv.max_slots, np.int32)
        for s, req in enumerate(self.scheduler.slots):
            if req is not None:
                slot_ad[s] = req.adapter_slot
        return slot_ad

    def _adapter_token_ids(self, sp):
        """Per-token adapter SLOT ids for one packed step, riding the
        flat token axis exactly like the sampling params do: each
        token inherits its owning slot's pinned adapter slot; padding
        (and base-model) tokens carry the null slot 0. Rebuilt
        host-side per step, so compiled shapes never depend on which
        adapters are resident."""
        slot_ad = self._slot_adapters()
        return np.where(sp.slot_ids >= 0,
                        slot_ad[np.clip(sp.slot_ids, 0, None)],
                        0).astype(np.int32)

    def _penalty_counts(self):
        """Fixed `[max_slots, penalty_vocab_bins]` float32 token-count
        histogram for the in-step logit processors: each resident
        slot's last W (prompt + generated) tokens bucketed by
        `token % bins` — the device-updatable form of the old per-step
        history window (ISSUE 19). Rebuilt host-side per dispatch so
        the compiled shapes never depend on generation progress; the
        multi-tick loop then scatter-adds each accepted token in-loop
        so later ticks penalize earlier ticks' output without a host
        round-trip."""
        W = int(self.sampling.penalty_window)
        Vb = self._penalty_bins
        cnt = np.zeros((self.kv.max_slots, Vb), np.float32)
        for slot, req in enumerate(self.scheduler.slots):
            if req is None:
                continue
            toks = req.runtime_prompt[-W:]
            if toks:
                np.add.at(cnt[slot],
                          np.asarray(toks, np.int64) % Vb, 1.0)
        return cnt

    def _draft_ring_state(self):
        """Per-slot device token ring feeding the in-loop n-gram
        drafter: `ring [max_slots, draft_ring]` int32 with token t of
        each resident sequence at column t % draft_ring, plus
        `rcnt [max_slots]` total sequence lengths
        (`serving.draft.ring_chronological` layout). Reseeded host-side
        per dispatch — cheap, it is one window copy per resident slot —
        and advanced ON DEVICE inside the dispatch as ticks emit."""
        Wr = self.draft_ring
        S = self.kv.max_slots
        ring = np.zeros((S, Wr), np.int32)
        rcnt = np.zeros(S, np.int32)
        for slot, req in enumerate(self.scheduler.slots):
            if req is None:
                continue
            toks = req.runtime_prompt
            L = len(toks)
            w = min(L, Wr)
            if w:
                ring[slot, np.arange(L - w, L) % Wr] = toks[-w:]
            rcnt[slot] = L
        return ring, rcnt

    def sparse_skip_ratio(self):
        """Fraction of candidate KV blocks the sparse decode path
        SKIPPED (0.0 = dense, or sparsity off) — the long-context
        smoke's measured-sparsity contract."""
        if not self.sparse_candidate_blocks:
            return 0.0
        return 1.0 - (self.sparse_selected_blocks
                      / self.sparse_candidate_blocks)

    def moe_utilization_entropy(self):
        """Normalized entropy of the cumulative per-expert token
        distribution (1.0 = balanced; 0.0 = degenerate/no MoE)."""
        return _pmetrics.moe_utilization_entropy(self.moe_expert_counts)

    def _note_moe_stats(self, moe_stats):
        """Fold one step's device-side routing stats into the host
        mirrors + metrics (per-expert token counters, dropped-token
        counter, aux-loss gauge, utilization-entropy gauge)."""
        st = {k: np.asarray(v) for k, v in moe_stats.items()}
        counts = st["counts"].astype(np.float64)
        dropped = float(st["dropped"])
        self.moe_expert_counts += counts
        self.moe_dropped_total += dropped
        self.moe_last_aux = float(st["aux"])
        if _pmetrics._enabled:
            _pmetrics.record_moe_stats(
                "serving", counts, dropped, self.moe_last_aux,
                utilization=self.moe_utilization_entropy())

    # -------------------------------------------------------------- run
    def _pack(self, decode, prefills):
        """Pack a plan into the next of the two plan buffers, whole:
        the flat tokens (`pack_step`), then a COPY of the block tables
        as they stand now, and the per-token adapter ids. The step reads
        this buffer and never the KV manager's live tables, so what the
        host does to them after this cannot reach a dispatched step."""
        if self.kv.linear_layers:
            # a linear layer's runs must touch distinct slots: the
            # scheduler gives a slot one decode token or one prefill
            # chunk a step, and the recurrence depends on it
            fed = [e[0] for e in decode] + [e[0] for e in prefills]
            if len(set(fed)) != len(fed):
                raise AssertionError(
                    f"a plan feeds a slot twice in one step ({fed}): "
                    "the linear layers take one run a slot")
        if self._causal_block:
            # under the block-causal mask a run sees no key past its own
            # end: it must end on a block boundary, or where the
            # sequence does (the scheduler cuts so; held here, where the
            # runs are made)
            L = self._causal_block
            for _, chunk, start, completes in prefills:
                if start % L or (len(chunk) % L and not completes):
                    raise AssertionError(
                        f"a prefill chunk of {len(chunk)} tokens from "
                        f"{start} ends inside a block of {L}")
        buf = self._plan_buffers[self._plan_flip]
        self._plan_flip ^= 1
        sp = pack_step(self.token_budget, self.kv.max_slots, decode,
                       prefills, verify_width=self.draft_k + 1,
                       reserve_region=self._sparse, buffers=buf)
        for name, table in zip(self.plan_layout.tables, self.kv.tables()):
            np.copyto(getattr(buf, name), table)
        if self.adapters is not None:
            np.copyto(buf.adapter_ids, self._adapter_token_ids(sp))
        return sp

    def _step_args(self, sp, tail=(), note=None):
        """The compiled mixed step's arguments, assembled HERE and
        nowhere else: weights, pools, adapter arrays, the packed plan
        (ONE buffer: flat tokens, sample index, block tables, adapter
        ids), the tokens the step before sampled (an engine that can
        dispatch ahead: `_prev_tokens`, which never left the device),
        penalty counts, the key, the device loop's `tail`. What
        the host made goes up in one `device_put`: the plan alone
        unless logit processors or the device loop are on. Live steps
        and `example_step_args()` (what the kernel check traces and the
        fleet bundle compiles) both come through. `note` (a traced
        step's) takes how many host arrays went up, and their bytes."""
        host = [sp.buffers.flat]
        if batcher.needs_history(self.sampling):
            host.append(self._penalty_counts())
        head = len(host)
        host += tail
        if note is not None:
            note.update(h2d_arrays=len(host),
                        h2d_bytes=sum(int(a.nbytes) for a in host))
        dev = self._upload(host)
        args = [self._arrays] + self.kv._pools()
        if self.adapters is not None:
            args += self.adapters.device_arrays()
        prev = [self._prev_tokens] if self._ahead else []
        return args + dev[:1] + prev + dev[1:head] + [self._rng] \
            + dev[head:]

    def _multitick_tail(self, decode, n):
        """The device loop's control tail for `n` ticks over a plan's
        `decode` entries, as host arrays: n / eos / remain / cap
        [/ per-slot adapter ids] [/ draft ring, ring counts]. It
        PREALLOCATES the ticks' blocks: build it before `_pack` copies
        the block tables."""
        sch = self.scheduler
        S, K = self.kv.max_slots, self.draft_k + 1
        eos = np.full(S, -1, np.int32)
        remain = np.zeros(S, np.int32)
        cap = np.zeros(S, np.int32)
        for slot, _tok, pos in decode:
            req = sch.slots[slot]
            if req is None:
                continue
            if req.eos_token_id is not None:
                eos[slot] = int(req.eos_token_id)
            remain[slot] = req.max_new_tokens - len(req.output)
            # FREE-block tick preallocation (scheduler.extend_for_ticks):
            # in-device appends of later ticks land in already-mapped
            # blocks. With speculation each tick may write up to K
            # tokens, so the preallocation horizon is n * K; the in-loop
            # draft clamp (k_eff <= cap - pos - 1) keeps accepted tokens
            # inside it, and anything past it lands in the reserved
            # null block and is never read back (attention stops at
            # cap, harvest truncates to the emitted count).
            cap[slot] = (sch.extend_for_ticks(slot, pos, n * K)
                         if n * K > 1 else pos + 1)
        tail = [np.int32(n), eos, remain, cap]
        if self.adapters is not None:
            tail.append(self._slot_adapters())
        if K > 1:
            tail += self._draft_ring_state()
        return tail

    def example_step_args(self):
        """Zero-filled arguments matching the compiled mixed step's
        call signature exactly: the packer on an EMPTY plan gives the
        same fixed shapes every real step uses, so `fleet/export.py`
        can lower + AOT-compile the step against these without the
        engine ever serving a request (and without advancing
        `self._rng`: only a step that runs does — boot stays
        deterministic)."""
        tail = self._multitick_tail([], 1) if self._multitick else ()
        sp = self._pack([], [])
        # the buffer goes back to the packer: the OTHER one may be a
        # dispatched step's, not read back yet
        self._plan_flip ^= 1
        return self._step_args(sp, tail)

    def step(self):
        """One engine iteration. Returns True when any work (tokens or
        expiries) happened, False when the engine is idle/starved.
        Pack, dispatch and readback are the device program's own
        (`_launch_tick` and `_land_tick`, `_run_block_tick`,
        `_run_multitick`); what they hand back is one host form, and
        from `engine.emit` on there is one loop.

        At depth 1 (`_depth`; docs/SERVING.md "Dispatching ahead") the
        call is: plan k+1 -> pack -> dispatch k+1 -> read back step k ->
        emit step k -> note. The step it dispatches stays in flight
        (`_inflight`) until the next call reads it back, so the host's
        work lies behind the chip; a call that finds nothing to plan
        reads the step in flight back and emits it. At depth 0 the call
        reads back what it dispatched, as it always did."""
        sch = self.scheduler
        # tracing state is sampled ONCE per step: recording stays
        # consistent across the step even if a monitor attaches midway
        trace_on = _tracing._enabled
        ph = self.phases
        t0 = ph.mark("engine.plan", self.steps_run) if trace_on else None
        if trace_on:
            # seconds of this step's summed phases spent in tracing code
            # (the flight field `trace_self`)
            self._trace_self = 0.0
        had = self._inflight is not None
        # (with nothing in flight `plan` is called as it always was:
        # callers wrap it, the benchmark's planted faults among them)
        plan = sch.plan(self.drain) if had else sch.plan()
        if _pmetrics._enabled and plan.expired:
            smetrics.SERVING_REQUESTS.labels("expired").inc(
                len(plan.expired))
        prev = self._inflight       # None where the plan had to drain
        if plan.empty:
            # nothing to dispatch: the step in flight is the work
            self.drain()
            self._flush_deferred()
            if trace_on:
                ph.close()
                _tracing.TRACER.flush()
            return had or bool(plan.expired)
        if trace_on:
            ph.mark("engine.pack")
        if self._multitick or self._diff:
            # loops of their own: dispatched AND read back in there
            cur = (self._run_multitick if self._multitick
                   else self._run_block_tick)(plan, trace_on)
            hold, landed = False, [cur]
        else:
            cur = self._launch_tick(plan, trace_on)
            # the wait: for the step BEFORE, where one is in flight (its
            # parked flight record takes its counters from the readback,
            # so it publishes after it); else for this one, with what
            # the last step parked published while the device runs
            hold = bool(self._depth)
            landed = [fl for fl in (prev, None if hold else cur)
                      if fl is not None]
            if prev is not None:
                self._land_tick(prev)
            self._flush_deferred()
            if not hold:
                self._land_tick(cur)
        self._hold(cur if hold else None)
        now = ph.mark("engine.emit") if trace_on else self.clock()
        if trace_on:
            t = self.clock()
            # one prefill_chunk span per planned chunk, by the requests
            # the step was dispatched for
            for slot, chunk, start, completes in plan.prefills:
                req = cur.reqs.get(slot)
                if req is not None:
                    _tracing.TRACER.queue(
                        req.trace_id, "prefill_chunk",
                        replica=self.name, ts=now, start=int(start),
                        tokens=len(chunk), completes=bool(completes))
            self._trace_self += self.clock() - t
        wasted = sum(self._emit_step(fl, now, trace_on) for fl in landed)
        if trace_on:
            ph.mark("engine.note")
        for fl in landed:
            self._note_landed(fl)
        got, sp = cur.got, cur.sp
        if prev is not None:
            self.steps_ahead += 1
            self.ahead_wasted_rows += wasted
        got["dispatch"].update(ahead=int(prev is not None),
                               ahead_wasted_rows=wasted)
        snap = record = None
        if _pmetrics._enabled:
            snap = self._snapshot(sp.prefill_tokens, got)
        if trace_on:
            # flight-recorder note: what has to be read NOW is (host
            # ints the loop already holds, the allocators' counts, the
            # clock; a block model's counters come with the tokens);
            # the record is made of it later. It describes the plan this
            # call DISPATCHED, and `dur` is this call's wall time
            record = self._step_record(t0, sp, got)
        if snap is not None and not self._multitick:
            # the registry's metrics are not tracing's: published now
            self._observe(snap, None)
            snap = None
        if sch.has_work and (snap is not None or record is not None):
            if self._deferred is not None:      # tracing went off and on
                self._flush_deferred()
            # deferred observability: every value was captured NOW (a
            # step in flight: its counters come with its tokens); it
            # publishes after the next dispatch launches (or at the
            # idle / flush points), behind the device
            self._deferred = (snap, record)
        else:
            self._observe(snap, record)
            if trace_on:
                _tracing.TRACER.flush()
        return True

    def _hold(self, flight):
        """`flight` (or None) is the step in flight from here on; the
        scheduler counts it as work (`has_work`)."""
        self._inflight = flight
        self.scheduler.step_in_flight = flight is not None

    def drain(self):
        """Read the step in flight back and emit its tokens, if there is
        one: after this the engine is where the synchronous loop would
        be. What needs a step's OUTCOME before it runs calls it first:
        a plan that has to preempt or to expire a request with a token
        in flight (`Scheduler.plan`), `cancel`, `extract_request`,
        `submit_migrated`, `swap_weights`, `close`, the end of `run`;
        `step` itself when it finds nothing to dispatch."""
        fl = self._inflight
        if fl is None:
            return
        self._hold(None)
        trace_on = _tracing._enabled
        ph = self.phases
        # the caller's phase (the plan's, the frontend's) goes on after
        was = ph.name
        if trace_on:
            ph.mark("engine.wait")
        self._land_tick(fl)
        self._flush_deferred()
        now = ph.mark("engine.emit") if trace_on else self.clock()
        self._emit_step(fl, now, trace_on)
        if trace_on:
            ph.mark("engine.note")
        self._note_landed(fl)
        if trace_on:
            if was is not None:
                ph.mark(was)
            else:
                ph.close()
            _tracing.TRACER.flush()

    def _emit_step(self, fl, now, trace_on):
        """Hand a step that was read back to its requests: the ones it
        was DISPATCHED for (`fl.reqs`), whatever the slots hold now. A
        request that has ended since (on EOS, one step before the host
        learned it) was fed a row too many: its token is dropped here.
        -> the rows of LATER dispatches that this emit found wasted, the
        same way: a request that ends here with a token still in
        flight."""
        sp, got, reqs = fl.sp, fl.got, fl.reqs
        if got["logits"] is not None:
            # after `step()` returns, `sample_logits` is what the tokens
            # just emitted were taken from
            self.sample_logits = got["logits"]
            self.sample_selection = got["selection"]
        wasted = 0
        for slot in sp.prefill_done:
            req = reqs.get(slot)
            if req is None or req.done:
                continue
            if got["first"] is None:
                # block decoding: a prefill samples nothing, and its
                # request feeds its first block from the next step on
                req.state = "decode"
            elif self.emit(req, [int(got["first"][slot])], now, trace_on):
                wasted += req.in_flight
            elif self.role == "prefill":
                # prefill-role handoff point: the first token is
                # sampled, every prompt token's K/V is written — the
                # request parks until the frontend extracts it toward a
                # decode replica (a request that finished AT its first
                # token never migrates)
                req.state = "handoff"
                if trace_on:
                    _tracing.TRACER.event(req.trace_id, "handoff",
                                          replica=self.name, ts=now)
        for slot, tokens, accepted in got["groups"]:
            req = reqs.get(slot)
            if req is None or req.done:
                continue
            if self.emit(req, tokens, now, trace_on, got["verify"]):
                wasted += req.in_flight
            elif accepted is not None:
                self._note_accept(slot, accepted)
        return wasted

    def _note_landed(self, fl):
        """The counters a step's readback brought with the tokens."""
        got = fl.got
        if got["moe_stats"] is not None:
            self._note_moe_stats(got["moe_stats"])
        self.spec_proposed_total += got["spec"][0]
        self.spec_accepted_total += got["spec"][1]

    def _fed_requests(self, plan):
        """{slot: request} of the slots `plan` feeds, as they stand at
        its dispatch."""
        slots = self.scheduler.slots
        return {e[0]: slots[e[0]] for e in plan.decode + plan.prefills}

    def _dispatch(self, plan, sp, tail, trace_on):
        """Run the compiled step on the packed `plan`, rebind the pools,
        the key and (dispatching ahead) the sampled tokens it returns,
        all of which stay on the device, and note the plan fed. -> (its
        head output, still on the device; the host form with what every
        program leaves alike)."""
        # spec: drafts proposed, drafts accepted, groups by accept length
        got = dict(verify=False, moe_stats=None, block_stats=None,
                   logits=None, selection=None, spec=(0, 0, ()),
                   work=None, dispatch={})
        args = self._step_args(sp, tail,
                               got["dispatch"] if trace_on else None)
        if trace_on:
            self.phases.mark("engine.dispatch")
        *res, self._rng = self._step_fn(*args)
        if trace_on:
            self.phases.mark("engine.wait")
        if self.num_experts:
            res, got["moe_stats"] = res[:-1], res[-1]
        elif self._block is not None:
            if self._select is not None:
                res, got["selection"] = res[:-1], res[-1]
            # the sample rows' logits stay on the device, a row a slot:
            # nothing reads them back but a caller who asks
            # (`sample_logits`, rebound when the step's tokens are
            # emitted)
            res, got["block_stats"], got["logits"] = \
                res[:-2], res[-2], res[-1]
        if self._ahead:
            self._prev_tokens = res[0]
        self.kv._set_pools(res[1:])
        self.scheduler.note_fed(plan)
        self.steps_run += 1
        return res[0], got

    def _launch_tick(self, plan, trace_on):
        """Pack and dispatch one tick of the mixed step; nothing is read
        back. -> the `_Flight`. The requests it will hand a token (a
        decode slot's, a completed prefill's) count it as in flight
        from here, and a completed prefill decodes from here: the next
        plan may be made before this step is read."""
        reqs = self._fed_requests(plan)
        sp = self._pack(plan.decode, plan.prefills)
        out, got = self._dispatch(plan, sp, (), trace_on)
        got.update(verify=bool(self.draft_k),
                   decode_tokens=sp.decode_tokens)
        owed = [reqs[slot] for slot in sp.decode_slots + sp.prefill_done]
        for req in owed:
            req.in_flight += 1
            if req.state == "prefill":
                req.state = "decode"
        if self._depth:
            # the copy to the host starts when the step ends, not when
            # the next call asks for it
            stats = got["block_stats"] if trace_on else None
            for a in (out,) if stats is None else (out, stats):
                a.copy_to_host_async()
        if self._sparse:
            self._note_sparse(pos + len(toks) - 1
                              for _, toks, pos in plan.decode)
        if trace_on:
            # the attention work of this step, counted while the device
            # does it: host arithmetic on the plan, no readback
            got["work"] = self._plan_work(plan)
        return _Flight(sp, got, out, reqs, trace_on, owed)

    def _land_tick(self, fl):
        """Read a dispatched tick back, into its host form (`fl.got`):
        `first` the sampled token a slot (what a completed prefill
        emits), `groups` a decode slot's (slot, tokens to emit, length
        to roll the slot back to or None), and the counters only this
        program has."""
        import jax
        sp, got, out = fl.sp, fl.got, fl.out
        for req in fl.owed:
            req.in_flight -= 1
        # the step has ended on EVERY device, not on the first alone
        # (`np.asarray` of a replicated array waits for one shard): on
        # the CPU backend a device reads its plan buffer in place, and
        # that buffer is packed again once this step has been read
        jax.block_until_ready(out)
        if not self.draft_k:
            if fl.traced and got["block_stats"] is not None:
                # a block model's counters with the tokens: one readback
                tok_np, got["block_stats"] = jax.device_get(
                    (out, got["block_stats"]))
            else:
                tok_np = np.asarray(out)
            got.update(first=tok_np, groups=[
                (slot, [int(tok_np[slot])], None)
                for slot in sp.decode_slots])
            return
        from .draft import accept_length, accept_length_sampled
        tok_np, tokv_np, *sampled = (np.asarray(t) for t in out)
        groups, prop, acc = [], 0, 0
        hist = [0] * (self.draft_k + 1)
        for slot, toks, pos in sp.decode_entries:
            g = tokv_np[slot]
            if self.spec_sampling:
                # rejection-sampling acceptance: accepted drafts
                # re-emit the fed tokens, then the device's residual
                # resample (rejection at m) or its bonus sample (every
                # draft accepted)
                tokres_np, acc_np = sampled
                m = accept_length_sampled(toks, acc_np[slot])
                emitted = [int(t) for t in toks[1:m + 1]]
                emitted.append(int(g[m]) if m == len(toks) - 1
                               else int(tokres_np[slot][m]))
            else:
                m = accept_length(toks, g)
                emitted = [int(t) for t in g[:m + 1]]
            prop += len(toks) - 1
            acc += m
            hist[m] += 1
            # unless it finishes, roll back the blocks whose only
            # contents were rejected-draft K/V columns
            groups.append((slot, emitted, pos + m + 1))
        got.update(first=tok_np, groups=groups, spec=(prop, acc, hist))

    def _run_block_tick(self, plan, trace_on):
        """`_launch_tick` and `_land_tick` in one, for a model that
        decodes by blocks (-> the `_Flight`, read back): a decode
        entry fed its slot's current block of L rows, and the step hands
        back every row's candidate and confidence. Here the block's
        state moves, by POSITION (an id equal to the mask id means
        nothing): a slot pass that fed masked rows is a DENOISE pass,
        the model's rule (`BlockDecoding.decide`) picks the masked
        positions that take their candidate for good, and the block's
        decided tokens that follow what the client already has go out,
        in position order (`groups`; none where an earlier position is
        still masked); a pass that fed no masked row was the COMMIT
        (`Scheduler.note_fed` grew the slot by the block and opened the
        next one): its outputs are unused. A pass yields 0..L tokens. A
        completed prefill samples nothing (`first` is None)."""
        bd, sch = self._diff, self.scheduler
        L = bd.block_length
        # the states the plan was made from: the dispatch notes the plan
        # fed, and a committed block's state is the next block's by then
        fed = [(slot, sch.slots[slot], list(sch.slots[slot].block_decided),
                toks, pos) for slot, toks, pos in plan.decode]
        reqs = self._fed_requests(plan)
        sp = self._pack(plan.decode, plan.prefills)
        out, got = self._dispatch(plan, sp, (), trace_on)
        self._flush_deferred()      # behind the device
        got.update(decode_tokens=sp.decode_tokens, first=None)
        if trace_on:
            import jax
            got["work"] = self._plan_work(plan)
            # the block's counters with the candidates: one readback
            both, got["block_stats"] = jax.device_get(
                (out, got["block_stats"]))
        else:
            both = np.asarray(out)              # [S, 2, L] int32
        cand, conf = both[:, 0], both[:, 1].view(np.float32)
        groups, masked_rows, decided, commits = [], 0, 0, 0
        for slot, req, was, toks, pos in fed:
            masked = [i for i in range(L) if not was[i]]
            take = bd.decide(masked, conf[slot], req.block_passes) \
                if masked else []
            if self.on_block_pass is not None:
                self.on_block_pass(req, pos, toks, was, take,
                                   [int(cand[slot, i]) for i in take])
            if not masked:
                commits += 1
                if trace_on:
                    _tracing.TRACER.queue(
                        req.trace_id, "block_committed", replica=self.name,
                        ts=self.clock(), start=int(pos))
                continue
            for i in take:
                req.block_tokens[i] = int(cand[slot, i])
                req.block_decided[i] = True
            req.block_passes += 1
            masked_rows += len(masked)
            decided += len(take)
            at = len(req.prompt) + len(req.output) - req.block_start
            # no further than the horizon, nor past an EOS: what the
            # client is handed is what `emit` appends
            stop = min(L, at + req.max_new_tokens - len(req.output))
            end = at
            while end < stop and req.block_decided[end]:
                end += 1
                if req.block_tokens[end - 1] == req.eos_token_id:
                    break
            if end > at:
                groups.append((slot, req.block_tokens[at:end], None))
        got["groups"] = groups
        if trace_on:
            got["work"].update(
                diff_block_len=L, diff_slot_passes=len(fed),
                diff_rows_masked=masked_rows, diff_tokens_decided=decided,
                diff_commits=commits, diff_blocks_committed=commits)
        return _Flight(sp, got, None, reqs, trace_on)

    def _run_multitick(self, plan, trace_on):
        """`_launch_tick` and `_land_tick` in one, for the device loop
        (-> the `_Flight`, read back): preallocate tick capacity,
        launch the while_loop dispatch, harvest the staging buffer into
        the same host form, so the emitted tokens replay through the
        host bookkeeping a 1-tick engine runs per step."""
        K = self.draft_k + 1
        t_launch = self.clock()
        if self._gap_ema is not None or self._last_harvest is not None:
            gap = max(t_launch - (self._last_harvest or t_launch), 0.0)
            self._gap_ema = (gap if self._gap_ema is None
                             else 0.7 * self._gap_ema + 0.3 * gap)
        # multi-tick only on pure-decode dispatches: a prefill chunk
        # needs the host packer next step anyway, and a prefill-role
        # engine's completions park in "handoff" — both pin n to 1
        n = self.ticks_per_dispatch if not plan.prefills else 1
        if n > 1 and self._ticks_auto:
            n = self._auto_ticks(self.ticks_per_dispatch)
        # the tail first: it preallocates the ticks' blocks, and the
        # pack copies the tables as they then stand
        tail = self._multitick_tail(plan.decode, n)
        reqs = self._fed_requests(plan)
        sp = self._pack(plan.decode, plan.prefills)
        ctrl, got = self._dispatch(plan, sp, tail, trace_on)
        # async device_get: start the control-output copies and flush
        # the PREVIOUS dispatch's deferred observability while this
        # dispatch still runs on device
        for a in ctrl:
            try:
                a.copy_to_host_async()
            except Exception:
                pass
        self._flush_deferred()
        hs0 = self.clock()
        staged_np, counts_np, events_np = (np.asarray(a)
                                           for a in ctrl[:3])
        ticks_run = int(ctrl[3])
        if K > 1:
            got["spec"] = (int(ctrl[4]), int(ctrl[5]),
                           [int(x) for x in np.asarray(ctrl[6])])
        host_stall = self.clock() - hs0
        self._last_harvest = self.clock()
        self.host_stall_total += host_stall
        if ticks_run > 0:
            d = (self._last_harvest - t_launch) / ticks_run
            self._tick_ema = (d if self._tick_ema is None
                              else 0.7 * self._tick_ema + 0.3 * d)
            if self._gap_ema is None:
                self._gap_ema = 0.0    # arm the gap EMA from now on
        self.dispatches_run += 1
        self.device_ticks_run += ticks_run
        # what the device emitted a slot: (slot, position fed, count)
        fed = [(slot, pos, max(int(counts_np[slot]), 1))
               for slot, _tok, pos in plan.decode]
        if n > 1 or K > 1:
            # advance each decode slot to what the device actually
            # emitted and release the preallocated tail — dispatch-
            # boundary block state matches a 1-tick engine's exactly.
            # With speculation the freed tail includes blocks whose
            # only contents were rejected-draft K/V: those count as
            # spec rollbacks, same taxonomy as the 1-tick host path.
            for slot, pos, c in fed:
                self._note_accept(slot, pos + c)
        if self._sparse or trace_on:
            # the first tick's attention work is the plan's; each
            # further token a slot emitted in the device loop is one
            # query at the next position. Exact without speculation;
            # with device drafting the rejected draft columns are work
            # the host never sees
            ticked = plan.decode + [(slot, [0], pos + j) for slot, pos, c
                                    in fed for j in range(1, c)]
            if self._sparse:
                self._note_sparse(pos for _, _, pos in ticked)
            if trace_on:
                got["work"] = self._plan_work(
                    Plan(ticked, plan.prefills, ()))
        ev_finish, ev_over = (int(np.sum((events_np & bit) > 0))
                              if n > 1 else 0 for bit in (1, 2))
        self.early_exit_counts["finish"] += ev_finish
        self.early_exit_counts["overflow"] += ev_over
        if got["moe_stats"] is not None:
            # counts/dropped are per-tick sums; aux reports the mean
            # balance loss over the executed ticks
            got["moe_stats"] = dict(
                got["moe_stats"],
                aux=got["moe_stats"]["aux"] / max(ticks_run, 1))
        groups = [(slot, [int(t) for t in staged_np[slot, :c]], None)
                  for slot, _, c in fed
                  if self.scheduler.slots[slot] is not None]
        got["dispatch"].update(
            ticks=ticks_run, host_stall=float(host_stall),
            early_exit_finish=ev_finish, early_exit_overflow=ev_over)
        got.update(first=staged_np[:, 0], groups=groups,
                   decode_tokens=sum(len(g[1]) for g in groups))
        return _Flight(sp, got, None, reqs, trace_on)

    def emit(self, req, tokens, now, trace_on, verify=False):
        """Append generated tokens; returns True when the request
        reached a terminal state (EOS / horizon: replaying a device
        loop's tokens lands on the token its finish event flagged)."""
        if req.state == "prefill":
            req.state = "decode"
        first = req.first_token_time is None
        gap = None
        if first:
            req.first_token_time = now
            if _pmetrics._enabled:
                smetrics.SERVING_TTFT_SECONDS.observe(
                    now - req.submit_time)
        elif req._last_token_time is not None:
            gap = now - req._last_token_time
            if _pmetrics._enabled:
                smetrics.SERVING_INTER_TOKEN_SECONDS.observe(gap)
        req._last_token_time = now
        # block decoding delivers several tokens at once: the first of
        # them carries the gap (or is the first token), the others
        # follow it at no distance
        inside = len(tokens) - 1 if self._diff else 0
        if inside and _pmetrics._enabled:
            for _ in range(inside):
                smetrics.SERVING_INTER_TOKEN_SECONDS.observe(0.0)
        if trace_on:
            # the span twins of the two histograms above: the
            # first_token event's ts minus the enqueued event's ts IS
            # `now - req.submit_time`, and decode/verify events carry
            # the same `gap` — tools/trace_smoke.py asserts the sums
            # match
            t = self.clock()
            if first:
                _tracing.on_first_token(req, self.name, ts=now,
                                        inside=inside)
            else:
                _tracing.on_tokens(req, self.name, ts=now,
                                   n=len(tokens), gap=gap, verify=verify,
                                   inside=inside)
            self._trace_self += self.clock() - t
        for t in tokens:
            req.output.append(t)
            if len(req.output) >= req.max_new_tokens or \
                    (req.eos_token_id is not None
                     and t == req.eos_token_id):
                self.scheduler.finish(req, now)
                if _pmetrics._enabled:
                    smetrics.SERVING_REQUESTS.labels("finished").inc()
                return True
        return False

    def _note_accept(self, slot, new_len):
        """`new_len` tokens of the slot are cached and valid: blocks
        past them (rejected drafts, unused tick preallocation) go back."""
        freed = self.scheduler.note_accept(slot, new_len)
        if freed and self.draft_k and _pmetrics._enabled:
            smetrics.SERVING_SPEC_ROLLBACKS.inc()
            smetrics.SERVING_SPEC_ROLLBACK_BLOCKS.inc(freed)

    def _note_sparse(self, last_positions):
        """Block-sparse skip accounting, a decode query (a verify
        group's last) an entry. Selection is deterministic on fixed
        geometry (min(allocated, table width) blocks attended a
        layer): pure host math, no device readback."""
        for p in last_positions:
            n_blk = p // self.block_size + 1
            self.sparse_candidate_blocks += n_blk
            self.sparse_selected_blocks += min(
                n_blk, self.sparse_table_width)

    def _plan_work(self, plan):
        groups = None
        if self._block is None:
            work = _attention_work(plan, self.block_size)
            layers = [(None, self.kv.num_layers, work["attn_pairs"])]
        else:
            sparse = {}
            if self._select is not None:
                # the `_full` fields count what the run kernel walks
                # under the causal rule: the chunk rows' groups
                sparse, groups = _sparse_work(
                    plan, self._select.topk,
                    min(self.token_budget, _BLOCK_MAX_RUN))
                sparse["idx_pool_bytes"] = self.kv.idx_bytes_per_token \
                    * self.kv.num_blocks * self.block_size
            work = _attention_work_by_kind(plan, self.kv.window,
                                           self._causal_block, groups)
            work.update(sparse)
            kinds = self._block.arch.layer_kinds
            layers = [(w, kinds.count(kind), work[f"attn_pairs_{name}"])
                      for kind, name, w in (
                          ("full", "full", None),
                          ("sparse", "full", None),
                          ("sliding", "window", self.kv.window))]
            if self.kv.linear_layers:
                work.update(
                    _linear_work(plan, self._block.arch.delta_chunk))
        work.update(self._logit_work(plan, layers, groups))
        return work

    def _logit_work(self, plan, layers, groups=None):
        """`attn_logits_useful` and `attn_logits_issued` of the step,
        summed over its attention layers: (query, key) pairs x the
        model's query heads, and the logits the paged kernel computes
        for the same runs by its own tiles
        (`paged_attention.logits_issued`), padded heads among them.
        `layers`: (window or None, layers of the kind, pairs a layer)."""
        if self._logits_issued is None:
            from ..ops.pallas import paged_attention as pa
            H, block = self.kv.num_heads, self._block
            # the query heads of the model, and those the kernel is
            # given: a block's, with the heads its pools were padded by
            heads = H if block is None else block.arch.num_heads
            Gq = 1 if block is None else (
                heads + H - block.arch.num_kv_heads) // H
            max_run = None if block is None else min(
                self.token_budget, _BLOCK_MAX_RUN)
            self._logits_issued = heads, functools.partial(
                pa.logits_issued, H=H, Gq=Gq, block_size=self.block_size,
                max_run=max_run, causal_block=self._causal_block,
                tiles=pa.kernel_tiles(
                    self.token_budget, H, Gq, self.kv.head_dim,
                    self.block_size, self.kv.max_blocks_per_slot,
                    kv_jnp_dtype(self.kv.kv_dtype),
                    quantized=self.kv.quantized, max_run=max_run))
        heads, issued_by = self._logits_issued
        groups = _plan_groups(plan) if groups is None else groups
        useful = issued = 0
        for window, n, pairs in layers:
            if n:
                useful += n * pairs * heads
                issued += n * issued_by(groups, window=window)
        return dict(attn_logits_useful=int(useful),
                    attn_logits_issued=int(issued))

    def _block_now(self):
        """What a block model's flight fields need of the cache NOW:
        the kinds of block from the allocators (no window allocator: the
        window fields read 0), what `window_held_tokens` will count,
        the slots whose recurrent state is live."""
        kv = self.kv
        released = kv.blocks_released_behind_window
        now = (kv.allocator.num_used,
               kv.window_allocator.num_used if kv.has_window else 0,
               released - self._released_seen, kv.window_held_state(),
               kv.state_slots_in_use if kv.linear_layers else None)
        self._released_seen = released
        return now

    def _block_fields(self, block_stats, now):
        """A block model's flight fields, of its own counters (read
        back with the tokens) and `_block_now()`."""
        full, window, released, held_at, state_slots = now
        held, ctx = self.kv.window_held_tokens(held_at)
        fields = dict(
            zip(self._block.stat_names, (int(v) for v in block_stats)),
            kv_blocks_in_use_full=int(full),
            kv_blocks_in_use_window=int(window),
            kv_blocks_released_behind_window=int(released),
            kv_tokens_held_window=held, kv_tokens_context=ctx)
        if state_slots is not None:
            fields["state_slots_in_use"] = state_slots
        return fields

    def _snapshot(self, prefill_tokens, got):
        """The step's counters as host values, read NOW: `_observe`
        publishes them at once, or (device loop) after the next
        dispatch has launched. `grown` is what the engine's cumulative
        counts grew by since the snapshot before."""
        sch, kv, pc = self.scheduler, self.kv, self.prefix_cache
        sel, cand = self.sparse_selected_blocks, \
            self.sparse_candidate_blocks
        gauges = [
            (smetrics.SERVING_QUEUE_DEPTH, len(sch.queue)),
            (smetrics.SERVING_ACTIVE_SLOTS, int(sch.num_active)),
            (smetrics.SERVING_KV_BLOCKS_IN_USE, int(kv.blocks_in_use)),
            (smetrics.SERVING_KV_BLOCK_UTILIZATION, float(kv.utilization)),
            (smetrics.SERVING_KV_BYTES_PER_TOKEN,
             float(kv.kv_bytes_per_token))]
        counts = {smetrics.SERVING_PREEMPTIONS: sch.preemption_count,
                  smetrics.SERVING_KV_BLOCKS_MIGRATED: kv.blocks_imported}
        if cand:
            gauges.append(
                (smetrics.SERVING_SPARSE_ATTENTION_RATIO, sel / cand))
            counts[smetrics.SERVING_KV_BLOCKS_SKIPPED] = cand - sel
        if pc is not None:
            counts[smetrics.SERVING_PREFIX_HIT_TOKENS] = pc.hit_tokens
            counts[smetrics.SERVING_PREFIX_MISS_TOKENS] = pc.miss_tokens
            counts[smetrics.SERVING_PREFIX_EVICTIONS] = pc.evictions
        snap = dict(
            got["dispatch"], spec=got["spec"], gauges=gauges,
            prefill_tokens=int(prefill_tokens),
            decode_tokens=int(got["decode_tokens"]),
            grown=[(c, n - self._counts_seen.get(c, 0))
                   for c, n in counts.items()])
        self._counts_seen = counts
        return snap

    def _observe(self, snap, record):
        """Publish one step: its snapshot to the serving metrics, its
        flight record to the recorder (either may be None)."""
        if snap is not None and _pmetrics._enabled:
            smetrics.SERVING_STEPS.inc()
            smetrics.SERVING_TOKENS.labels("prefill").inc(
                snap["prefill_tokens"])
            smetrics.SERVING_TOKENS.labels("decode").inc(
                snap["decode_tokens"])
            for gauge, value in snap["gauges"]:
                gauge.set(value)
            for counter, grown in snap["grown"]:
                if grown > 0:
                    counter.inc(grown)
            if "ticks" in snap:
                smetrics.SERVING_TICKS_PER_DISPATCH.observe(snap["ticks"])
                smetrics.SERVING_HOST_STALL_SECONDS.inc(
                    snap["host_stall"])
                for kind in ("finish", "overflow"):
                    if snap["early_exit_" + kind]:
                        smetrics.SERVING_EARLY_EXITS.labels(kind).inc(
                            snap["early_exit_" + kind])
            proposed, accepted, hist = snap["spec"]
            if proposed:
                smetrics.SERVING_DRAFT_TOKENS.labels("proposed").inc(
                    proposed)
                smetrics.SERVING_DRAFT_TOKENS.labels("accepted").inc(
                    accepted)
            # bin b holds the number of verify groups that accepted
            # exactly b drafts: an accept length of b + 1 each
            for b, groups in enumerate(hist):
                for _ in range(groups):
                    smetrics.SERVING_ACCEPT_LENGTH.observe(b + 1)
        if record is not None:
            self.flight.note(**record())

    def _step_record(self, t0, sp, got):
        """The flight record of the step that began at `t0`, in two
        halves. NOW, inside `engine.note`: the engine's state (host
        ints, the allocators' counts) and the clock; the step ends
        here, where its last phase does, so the `ph_*` fields of this
        step sum to `dur` (the frontend's ran in the gap before it),
        and `trace_self` is closed: the seconds of this step's summed
        phases (`tracing.SUMMED_PHASES`) that went into tracing code,
        this block and the marks among them. LATER (`_observe`, after
        the next dispatch has launched): -> the function that makes the
        record of it, every value a host int or float."""
        t = self.clock()
        sch, kv = self.scheduler, self.kv
        sel, cand = self.sparse_selected_blocks, \
            self.sparse_candidate_blocks
        state = (sch.num_active, len(sch.queue), kv.blocks_imported,
                 self.step_compile_count(), kv.blocks_in_use,
                 kv.blocks_total, sch.preemption_count)
        fed = int(sp.prefill_tokens), int(got["decode_tokens"])
        block = self._block_now() if got["block_stats"] is not None \
            else None
        extra = self._flight_extra()
        end = self.phases.close()
        phases = self.phases.take()
        gap, self._step_end = self._step_end, end
        trace_self = self._trace_self + end - t + self.phases.take_own(
            _tracing.SUMMED_PHASES)

        def record():
            fields = dict(got["work"], prefill_tokens=fed[0],
                          decode_tokens=fed[1], **got["dispatch"])
            if block is not None:
                fields.update(
                    self._block_fields(got["block_stats"], block))
            fields.update(zip(
                ("active_slots", "queue_depth", "blocks_imported",
                 "compile_cache_size", "kv_blocks_in_use",
                 "kv_blocks_total", "preemptions"), map(int, state)))
            fields.update(
                extra, sparse_skip_ratio=(
                    1.0 - sel / cand if self._sparse and cand else 0.0),
                ts=t0, dur=end - t0, trace_self=trace_self, **phases)
            if gap is not None:
                fields["gap_before"] = t0 - gap
            return fields

        return record

    # ------------------------------------- multi-tick dispatch (ISSUE 18)
    def _flush_deferred(self):
        """Publish what the last step parked (a multi-tick dispatch's
        metrics; a traced step's flight record, made here, and the span
        events the tracer queued). A step calls it once its own dispatch
        has launched, inside `engine.wait`: it hides behind the
        device."""
        parked, self._deferred = self._deferred, None
        if parked is not None:
            self._observe(*parked)
            if parked[1] is not None:
                _tracing.TRACER.flush()

    def flush_observability(self):
        """Flush the deferred observability of the LAST step (a
        multi-tick dispatch's metrics and a traced step's flight record
        normally publish after the NEXT dispatch launches, overlapping
        device execution). The frontend calls this when going idle."""
        self._flush_deferred()

    def _auto_ticks(self, n_max):
        """ticks_per_dispatch='auto': size the next dispatch from the
        measured per-tick device time `d` and inter-dispatch host time
        `h` (EMAs) — the smallest n that keeps the amortized host share
        under ~10% of a tick, ceil(h / (0.1 d)), clamped to the staging
        width. Cold EMAs run the full budget (the measurement itself)."""
        d, h = self._tick_ema, self._gap_ema
        if not d or not h:
            return n_max
        import math
        return max(1, min(n_max, math.ceil(h / max(0.1 * d, 1e-9))))

    def run(self, max_steps=None):
        """Drive until every submitted request reaches a terminal
        state (or max_steps)."""
        steps = 0
        while self.scheduler.has_work:
            if max_steps is not None and steps >= max_steps:
                break
            if not self.step():
                raise RuntimeError(
                    "serving engine stalled: requests remain but no "
                    "step can be planned — the KV block pool "
                    f"({self.kv.allocator.capacity} blocks of "
                    f"{self.block_size}) cannot cover the resident "
                    "working set; raise num_blocks or lower max_slots")
            steps += 1
        # a step still in flight (`max_steps` cut the loop) is read
        # back: `run` hands back what the steps it ran computed; the
        # last dispatch's observability may still be parked in the
        # deferred lane — publish before handing control back
        self.drain()
        self._flush_deferred()
        return steps

    def generate_batch(self, prompts, max_new_tokens=32):
        """Submit a batch and drive to completion. Returns one list of
        generated token ids per prompt (stops at EOS inclusive)."""
        reqs = [self.submit(p, max_new_tokens) for p in prompts]
        self.run()
        return [list(r.output) for r in reqs]

    # ------------------------------------------- fleet control plane
    def install_aot_step(self, fn):
        """Replace the instrumented mixed-step wrapper with a
        deserialized AOT executable (fleet/export.py). The replica
        then performs ZERO `serving_mixed_step` jit compiles — the
        property tools/fleet_smoke.py asserts with a budget-0
        watchdog, and `step_compile_count()` then reads 0 for good:
        an AOT executable can never compile."""
        self._step_fn = fn
        self._aot_step = True

    def step_devices(self):
        """The devices the mixed step runs on, in executable order (one
        for this engine; the mesh's for TPServingEngine) — what an AOT
        executable has to be loaded onto."""
        import jax
        return [self.device if self.device is not None
                else jax.devices()[0]]

    def step_compile_count(self):
        """How many executables jax has built for this engine's mixed
        step (`instrumented_jit`'s count; the contract is 1 after the
        first step and 1 for ever after)."""
        return 0 if self._aot_step else self._step_fn.compile_count()

    def step_op_scopes(self):
        """{HLO instruction name: scope of `tracing.DEVICE_SCOPES`, or
        `tracing.NO_SCOPE`} of the executable the mixed step runs: what
        names a device trace's events, which carry an instruction's
        name and no `op_name` (`benchmarks/harness/device_scopes.py`,
        `tools/parse_xplane.py --by-scope`).

        Built on the first call and kept: the step lowered at the
        engine's own shapes (`example_step_args()`: nothing is donated,
        no pool is read) and compiled, which hands back the executable
        a step that already ran holds (else the persistent cache's, else
        a new one). NEVER called from `step()`: nothing is built or
        loaded inside a measured window, and nothing here goes through
        the wrapper that `step_compile_count()` counts. An AOT step
        (`install_aot_step`) gives the text of the executable it
        loaded."""
        if self._op_scopes is None:
            from ..profiler.xplane import hlo_op_scopes
            if self._aot_step:
                text = self._step_fn.as_text()
            else:
                text = self._step_fn._jitted.lower(
                    *self.example_step_args()).compile().as_text()
            self._op_scopes = hlo_op_scopes(
                text, _tracing.scope_of, _tracing.NO_SCOPE)
        return self._op_scopes

    def _prep_swap_arrays(self, arrays):
        """Host-side staging for `swap_weights`. The base engine takes
        the canonical model-order checkpoint as-is — committed to the
        engine's own `device` when it has one, so the cast runs there
        and the new weights do not land on the process default;
        TPServingEngine overrides this with the shard-major QKV
        permute + sharded placement its step layout requires."""
        return [self._commit(np.asarray(a)) for a in arrays]

    def _swap_jit_kwargs(self):
        """Extra jit kwargs for the swap cast (TP: out_shardings)."""
        return {}

    def swap_weights(self, arrays, version):
        """Live weight swap between steps (fleet/upgrade.py): replace
        the parameter set with a new same-architecture checkpoint
        through ONE jitted budget-1 `serving_weight_swap` cast — the
        exact compute-dtype transform `__init__` applies, so a swapped
        engine is bit-identical to one constructed from the new
        checkpoint. Same shapes/dtypes out means the mixed step's
        compiled executable keys unchanged: no recompile, one
        `serving_mixed_step` compile per engine holds across any
        number of upgrades. Must be called with the engine idle
        (drained) — in-flight requests would otherwise mix versions
        mid-sequence."""
        import jax.numpy as jnp
        self.drain()
        if self._moe_weight_bits:
            raise ValueError(
                "live weight swap on an engine-side quantized MoE "
                "stack is unsupported: the quantization transform is "
                "not shape-preserving per tensor — export a new "
                "bundle and boot a fresh replica instead")
        if len(arrays) != len(self._arrays):
            raise ValueError(
                f"checkpoint has {len(arrays)} tensors, engine holds "
                f"{len(self._arrays)} — not the same architecture")
        prep = self._prep_swap_arrays(arrays)
        for new, old in zip(prep, self._arrays):
            if tuple(new.shape) != tuple(old.shape):
                raise ValueError(
                    f"weight shape {tuple(new.shape)} != engine "
                    f"shape {tuple(old.shape)}: live swap requires "
                    "an architecture-identical checkpoint")
        if self._swap_fn is None:
            dts = tuple(jnp.dtype(a.dtype) for a in self._arrays)

            def _load(new):
                return [a.astype(dt) for a, dt in zip(new, dts)]

            self._swap_fn = instrumented_jit(
                _load, SWAP_FN_NAME, **self._swap_jit_kwargs())
        self._arrays = list(self._swap_fn(prep))
        # cached prefix KV was computed under the OLD weights — serving
        # it to post-swap requests would silently mix versions
        if self.prefix_cache is not None:
            self.prefix_cache.evict_all()
        self.weights_version = str(version)

    def close(self, *, spill_prefix=None):
        """Release the engine's cached KV state; optionally spill the
        radix prefix cache (tree + exported block payloads) to
        `spill_prefix` first, so a future replica can warm-boot with a
        non-empty cache (`RadixPrefixCache.spill`/`restore`;
        docs/DEPLOYMENT.md). Returns the number of blocks spilled.
        Idempotent; the engine must be drained (no resident
        requests)."""
        self.drain()
        self._flush_deferred()
        spilled = 0
        if self.prefix_cache is not None:
            if spill_prefix is not None:
                spilled = self.prefix_cache.spill(spill_prefix)
            self.prefix_cache.evict_all()
        return spilled
