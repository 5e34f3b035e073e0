"""Block-paged KV cache for continuous batching.

The fixed-shape backbone of the serving engine (the TPU translation of
vLLM-style PagedAttention, per the "Ragged Paged Attention" shape
discipline): one `[L, num_blocks, block_size, H, Dh]` pool per K and V
covers EVERY request; a request owns an ordered list of blocks and the
per-slot block table is padded to a fixed `max_blocks_per_slot` width,
so the compiled mixed step sees identical shapes no matter which
requests are resident.

Block 0 is reserved as the NULL block: padding entries in block tables
and the cache writes of padding tokens all land there, and the
attention mask (`key position <= query position`) guarantees it is
never read through. The allocator hands out blocks `1..num_blocks-1`
LIFO so tests can observe free-list reuse directly.
"""
from __future__ import annotations

import numpy as np

NULL_BLOCK = 0

#: supported pool dtypes -> (HBM bytes per element, whether the pool
#: stores quantized payloads needing per-entry-per-head fp32 scales).
#: THE one list `Config(kv_dtype=)` and the constructor validate
#: against — an unknown dtype fails here with the supported set in
#: the message, never as a deep KeyError in the sizing math.
KV_DTYPES = {
    "float32": (4, False),
    "bfloat16": (2, False),
    "float16": (2, False),
    "int8": (1, True),
    # fp8 KV pools (ISSUE 15): e4m3 payloads under the SAME per-entry
    # per-head fp32 scale plumbing as int8 — quantize-on-append scales
    # amax to the e4m3 max (448) so the full mantissa range is used
    # per entry; CPU-testable via ml_dtypes
    "fp8_e4m3": (1, True),
}

#: fp8 format constants (ml_dtypes float8_e4m3fn): finite max 448;
#: values past it cast to NaN, so quantize clips first
FP8_MAX = 448.0

#: lanes a row of an indexer-key pool is padded to (a whole lane tile)
INDEXER_LANES = 128

#: "empty" sentinel for the min summary rows (max rows use the
#: negation): large but finite — far above any real key magnitude, far
#: enough below float32 max that score products stay finite — so a
#: never-written row scores a huge NEGATIVE upper bound (never
#: selected) without NaN-ing the scorer's arithmetic the way +/-inf
#: would
SUMMARY_INIT = 1e30


def kv_jnp_dtype(kv_dtype):
    """The jnp storage dtype for a `KV_DTYPES` name ("fp8_e4m3" is a
    serving-facing alias of ml_dtypes' float8_e4m3fn)."""
    import jax.numpy as jnp
    if kv_dtype == "fp8_e4m3":
        return jnp.float8_e4m3fn
    return jnp.dtype(kv_dtype)


class BlockAllocator:
    """LIFO free-list over block ids [reserved, num_blocks), with
    per-block reference counts so the prefix cache can SHARE a block
    between several slot tables (and its own radix tree): `alloc` hands
    a block out at refcount 1, `incref` adds an owner, and `free`
    decrements — the block returns to the free list only when its last
    owner lets go. Allocation is still all-or-nothing."""

    def __init__(self, num_blocks, reserved=1):
        if num_blocks <= reserved:
            raise ValueError(
                f"num_blocks={num_blocks} leaves no allocatable blocks "
                f"past the {reserved} reserved null block(s)")
        self.num_blocks = int(num_blocks)
        self.reserved = int(reserved)
        self._free = list(range(self.num_blocks - 1,
                                self.reserved - 1, -1))
        self._refs = {}                      # block id -> owner count

    @property
    def num_free(self):
        return len(self._free)

    @property
    def num_used(self):
        return len(self._refs)

    @property
    def capacity(self):
        return self.num_blocks - self.reserved

    def refcount(self, block):
        return self._refs.get(block, 0)

    @property
    def invariant_ok(self):
        """allocated + free + reserved == pool size, with no overlap —
        the ledger the prefix-cache meta-test asserts after random
        alloc/share/CoW/truncate/free sequences."""
        allocated = set(self._refs)
        free = set(self._free)
        return (not (allocated & free)
                and len(self._free) == len(free)
                and len(allocated) + len(free) + self.reserved
                == self.num_blocks
                and all(c > 0 for c in self._refs.values()))

    def alloc(self, n):
        """n blocks (each at refcount 1), or None when the pool can't
        cover the request — the caller decides whether to preempt
        (never partial)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def incref(self, blocks):
        """Add an owner to already-allocated blocks (prefix sharing)."""
        for b in blocks:
            if b not in self._refs:
                raise ValueError(f"incref of unallocated block {b}")
            self._refs[b] += 1

    def free(self, blocks):
        """Drop one owner per block; a block whose count hits zero goes
        back on the free list."""
        for b in blocks:
            c = self._refs.get(b, 0)
            if c <= 0:
                raise ValueError(f"double free of block {b}")
            if c == 1:
                del self._refs[b]
                self._free.append(b)
            else:
                self._refs[b] = c - 1


class PagedKVCache:
    """Paged pools + per-slot block tables + the slot length ledger.

    `kv_dtype="int8"` stores the pools quantized: int8 payloads plus
    fp32 scale pools `k_scale`/`v_scale` of shape `[L, NB, BS, H]` —
    one scale per pool ENTRY per head, riding exactly the same
    `(block, offset)` coordinates as the K/V bytes, so every consumer
    of a block id (slot tables, CoW, truncate, prefix-cache adoption)
    carries the scales for free. The granularity is deliberately
    per-entry rather than per-whole-block: blocks fill incrementally
    across steps and are SHARED between requests (radix prefix cache),
    so a whole-block scale would make already-written int8 values
    depend on later appends — per-entry scales keep quantization a
    pure function of the token's own fp K/V, which is what preserves
    the prefix-cache contract ("cached K/V is exactly what
    re-prefilling would write") and makes the int8 engine
    deterministic under chunking, preemption and sharing.

    `kv_dtype="fp8_e4m3"` rides the exact same plumbing with e4m3
    payloads (ml_dtypes), halving KV bytes again vs the int8 story's
    fp32 baseline and composing with sparsity, TP sharding, transport
    and the prefix cache for free.

    `summaries=True` (the block-sparse attention substrate, ISSUE 15)
    additionally keeps per-(pool-block, head) CHANNEL-WISE min/max
    key summaries `k_sum_min`/`k_sum_max` `[L, NB, H, Dh]` fp32,
    updated on append inside the jitted mixed step (the offset-0
    write of a block RESETS its row, so freed-then-reused blocks can
    never leak a previous owner's statistics). Summary rows ride the
    same block coordinates as the scale rows, so CoW, truncation,
    prefix adoption and migration transport carry them by
    construction."""

    def __init__(self, num_layers, num_heads, head_dim, *, num_blocks,
                 block_size, max_slots, max_blocks_per_slot,
                 dtype="float32", kv_dtype=None, summaries=False,
                 num_kv_heads=None, layer_kinds=None, window=None,
                 num_window_blocks=None, linear_state=None,
                 conv_tail=None, indexer_dim=None, latent_dim=None):
        import jax.numpy as jnp
        self.num_layers = num_layers
        # the heads the pools hold: the KV heads of a grouped-query
        # model, else the model's heads
        num_heads = int(num_kv_heads or num_heads)
        self.num_heads = num_heads
        self.head_dim = head_dim
        # layers of several kinds (`layer_kinds`: "full" / "sliding" /
        # "linear" / "sparse" a layer): a K/V pool of its own an
        # attention layer, one block table a slot for the full layers
        # and one for the window layers, which lets go of the blocks
        # behind the window; a linear layer holds no blocks but a
        # recurrent state and the convolution's last inputs a slot; a
        # sparse layer (attention through a learned selection) is a
        # full layer that keeps an INDEXER-KEY pool beside its K/V, on
        # the same block table; a latent layer keeps ONE pool of latent
        # rows on that table and no K/V (see `_init_layer_kinds`)
        self.layer_kinds = tuple(layer_kinds) if layer_kinds else None
        self.window = int(window) if window else None
        kinds = self.layer_kinds or ()
        self.has_window = "sliding" in kinds
        #: layers that keep K/V blocks, layers that keep a state
        self.linear_layers = [i for i, k in enumerate(kinds)
                              if k == "linear"]
        self.latent_layers = [i for i, k in enumerate(kinds)
                              if k == "latent"]
        self.attention_layers = [i for i in range(num_layers)
                                 if i not in self.linear_layers
                                 and i not in self.latent_layers]
        self.sparse_layers = [i for i, k in enumerate(kinds)
                              if k == "sparse"]
        self.idx_pools, self.latent_pools = [], []
        if self.layer_kinds is not None:
            if len(kinds) != num_layers or set(kinds) - {
                    "full", "sliding", "linear", "sparse", "latent"}:
                raise ValueError(
                    f"layer kinds {kinds}: one of 'full', 'sliding', "
                    f"'linear', 'sparse', 'latent' for each of the "
                    f"{num_layers} layers")
            if self.has_window and not self.window:
                raise ValueError("a 'sliding' layer needs a window")
            if self.linear_layers and not (linear_state and conv_tail):
                raise ValueError(
                    "a 'linear' layer needs the shapes of its state "
                    "(linear_state: heads, key dim, value dim) and of "
                    "its convolution's tail (conv_tail: rows, channels)")
            if self.sparse_layers and not indexer_dim:
                raise ValueError("a 'sparse' layer needs the width of "
                                 "its indexer key (indexer_dim)")
            if self.latent_layers and not latent_dim:
                raise ValueError("a 'latent' layer needs the lanes of "
                                 "its latent row (latent_dim)")
            if summaries or KV_DTYPES.get(
                    str(kv_dtype or dtype), (0, False))[1]:
                raise ValueError(
                    "quantized pools and block summaries are not built "
                    "for a cache with layer kinds")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.max_slots = int(max_slots)
        self.max_blocks_per_slot = int(max_blocks_per_slot)
        self.dtype = str(dtype)
        self.kv_dtype = str(kv_dtype) if kv_dtype else self.dtype
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r} not supported; pick one "
                f"of {sorted(KV_DTYPES)} ('int8'/'fp8_e4m3' store "
                "per-entry-per-head scaled quantized pools)")
        self.summaries = bool(summaries)
        self.window_allocator = None
        self.blocks_released_behind_window = 0
        self.states, self.conv_tails = [], []
        if self.layer_kinds is not None:
            self._init_layer_kinds(num_window_blocks, linear_state,
                                   conv_tail, indexer_dim, latent_dim)
        else:
            shape = (num_layers, self.num_blocks, self.block_size,
                     num_heads, head_dim)
            self.k_pool = jnp.zeros(shape, kv_jnp_dtype(self.kv_dtype))
            self.v_pool = jnp.zeros(shape, kv_jnp_dtype(self.kv_dtype))
        self.k_scale = self.v_scale = None
        if self.quantized:
            sshape = shape[:-1]                      # [L, NB, BS, H]
            self.k_scale = jnp.zeros(sshape, jnp.float32)
            self.v_scale = jnp.zeros(sshape, jnp.float32)
        self.k_sum_min = self.k_sum_max = None
        if self.summaries:
            # min starts high / max starts low so the first append of
            # a block's offset-0 entry (which resets the row anyway)
            # and an unwritten row alike can never look attractive to
            # the block scorer
            mshape = (num_layers, self.num_blocks, num_heads, head_dim)
            self.k_sum_min = jnp.full(mshape, SUMMARY_INIT, jnp.float32)
            self.k_sum_max = jnp.full(mshape, -SUMMARY_INIT,
                                      jnp.float32)
        self.allocator = BlockAllocator(self.num_blocks)
        self.block_tables = np.zeros(
            (self.max_slots, self.max_blocks_per_slot), np.int32)
        self._slot_blocks = [[] for _ in range(self.max_slots)]
        # tokens cached a slot. Under block decoding (a model that
        # generates by diffusion over blocks) these are COMMITTED tokens
        # only: the current block's L rows lie past them, written every
        # pass (provisional, overwritten in place: a block never
        # straddles a page, the block size being a multiple of L) and
        # counted once the commit pass has fed the block with nothing
        # masked (`Scheduler.note_fed`)
        self.slot_lens = np.zeros(self.max_slots, np.int32)
        # optional radix prefix cache (serving.prefix_cache): when the
        # free list runs dry, refcount-0 cached leaves are evicted
        # before an allocation is refused
        self.prefix_cache = None
        self._copy_fn = None
        # block transport (serving.distributed.transport): jitted
        # gather/scatter executables per pow2 id-width, raw transfer
        # counters (the engine mirrors them into the metrics registry),
        # and an optional re-placement hook a sharded engine installs
        # so imported pools return to their canonical mesh sharding
        # (a spec drift here would silently recompile the mixed step)
        self._transfer_fns = {}
        self.place_pools = None
        self.blocks_exported = 0
        self.blocks_imported = 0

    # ------------------------------------------------------ window layers
    def _init_layer_kinds(self, num_window_blocks, linear_state,
                          conv_tail, indexer_dim=None, latent_dim=None):
        """A cache whose layers are of several kinds. Each ATTENTION
        layer has its own K and V pool `[NB_kind, BS, H, Dh]`
        (`k_pools[i]`, i counting `attention_layers`): the step updates
        and reads a layer's pool in place, no slice of a stacked array.
        A slot has a block table a kind, `[max_blocks_per_slot]` wide
        with column c holding positions `[c * BS, (c + 1) * BS)`:
        `block_tables` for the full layers, drawn from `allocator`
        (`num_blocks` blocks a full layer), and, only if a layer is
        sliding, `window_tables` for the window layers, drawn from
        `window_allocator` (`num_window_blocks` a window layer; all
        window layers of a slot share the table). A window table gives
        a block back once every token in it is `window` or more behind
        the slot's next position (`release_behind_windows`, at the head
        of the scheduler's next `plan()`): its column reads NULL again
        and no query reaches it.

        Each LINEAR layer holds no blocks and no table: a float32
        recurrent state `[max_slots, *linear_state]` (`states[i]`, i
        counting `linear_layers`) and the last inputs of its short
        convolution `[max_slots, *conv_tail]`, float32 too
        (`conv_tails[i]`), whatever the context. The host never writes
        them: the step starts a run whose first position is 0 from
        zeros and any other from what the slot holds, so a reused slot
        and a request preempted back to position 0 see no earlier
        owner's state. Admission and preemption count full-layer
        blocks; the state is there for every slot always.

        Each SPARSE layer is a full layer (K/V pool of `num_blocks`
        blocks, the full layers' table) that also keeps the INDEXER key
        of every cached token: `idx_pools[i]` (i counting
        `sparse_layers`), `[num_blocks, BS, INDEXER_LANES]` in the
        pools' dtype, addressed by the SAME (block, offset) as the
        layer's K/V: no table and no allocator of its own, so whatever
        moves table entries (`truncate_slot`, `release_slot`,
        preemption) carries it. A key is `indexer_dim` numbers; a row
        of the pool is a whole lane tile (128), the rest zeros, so that
        XLA keeps the pool in the layout the step reads (a last dim of
        64 is half a tile; the zeros add nothing to a score). A block
        that is freed and reused keeps its old keys: positions past a
        slot's length are never candidates of a selection, the rule
        that already holds for K/V, so nothing is written at reuse.

        Each LATENT layer (multi-head latent attention) keeps no K/V:
        ONE pool `latent_pools[i]` (i counting `latent_layers`),
        `[num_blocks, BS, latent_dim]` in the pools' dtype, a row a
        token that every query head reads (the latent c and the
        rotary key, zeros to a whole number of lane tiles), on the
        full layers' block table and allocator, so that whatever moves
        table entries carries it as it carries K/V."""
        import jax.numpy as jnp
        if self.has_window and not num_window_blocks:
            raise ValueError("a cache with window layers is told its "
                             "window pools' size (num_window_blocks)")
        self.num_window_blocks = int(num_window_blocks) \
            if self.has_window else 0
        dt = kv_jnp_dtype(self.kv_dtype)
        tail = (self.block_size, self.num_heads, self.head_dim)
        nb = {"full": self.num_blocks, "sparse": self.num_blocks,
              "sliding": self.num_window_blocks}
        attention = [self.layer_kinds[i] for i in self.attention_layers]
        self.k_pools = [jnp.zeros((nb[k],) + tail, dt) for k in attention]
        self.v_pools = [jnp.zeros((nb[k],) + tail, dt) for k in attention]
        self.k_pool = self.v_pool = None
        if self.sparse_layers:
            self.indexer_dim = int(indexer_dim)
            lanes = -(-self.indexer_dim // INDEXER_LANES) * INDEXER_LANES
            self.idx_pools = [
                jnp.zeros((self.num_blocks, self.block_size, lanes), dt)
                for _ in self.sparse_layers]
        self.latent_dim = int(latent_dim or 0)
        self.latent_pools = [
            jnp.zeros((self.num_blocks, self.block_size, self.latent_dim),
                      dt) for _ in self.latent_layers]
        for _ in self.linear_layers:
            self.states.append(jnp.zeros(
                (self.max_slots,) + tuple(linear_state), jnp.float32))
            # float32 like the state: what one step hands the next is
            # not rounded, so a token reads the same inputs whether its
            # predecessors came in this step or in the one before
            self.conv_tails.append(jnp.zeros(
                (self.max_slots,) + tuple(conv_tail), jnp.float32))
        if not self.has_window:
            return
        self.window_allocator = BlockAllocator(self.num_window_blocks)
        self.window_tables = np.zeros(
            (self.max_slots, self.max_blocks_per_slot), np.int32)
        # the slot's held window blocks, columns [first, first + len)
        self._slot_wblocks = [[] for _ in range(self.max_slots)]
        self._slot_wfirst = [0] * self.max_slots

    def _window_first_col(self, next_pos):
        """The first table column a query at `next_pos` (or later) can
        reach in a window layer."""
        return max(int(next_pos) - self.window + 1, 0) // self.block_size

    def release_behind_window(self, slot):
        """Give back the window blocks of `slot` that lie wholly behind
        the window of its next position. Returns how many."""
        keep_from = self._window_first_col(self.slot_lens[slot])
        row, first = self._slot_wblocks[slot], self._slot_wfirst[slot]
        n = min(max(keep_from - first, 0), len(row))
        if n:
            self.window_allocator.free(row[:n])
            self.window_tables[slot, first:first + n] = NULL_BLOCK
            self._slot_wblocks[slot] = row[n:]
            self._slot_wfirst[slot] = first + n
            self.blocks_released_behind_window += n
        return n

    def release_behind_windows(self):
        """`release_behind_window` for every slot that holds tokens
        (nothing to do in a cache without window layers). The scheduler
        calls it at the head of `plan()`: the tables are a dispatched
        step's inputs and change only between steps."""
        if self.has_window:
            for slot in np.flatnonzero(self.slot_lens):
                self.release_behind_window(int(slot))

    def tables(self):
        """The block tables the step takes, as `[max_slots, MB]` int32
        arrays: one, or (full, window) with window layers."""
        if not self.has_window:
            return [self.block_tables]
        return [self.block_tables, self.window_tables]

    def fit_tokens(self, slot):
        """The longest length `ensure_capacity(slot, ...)` could cover
        from the slot's blocks and the FREE ones (no eviction)."""
        fit = (len(self._slot_blocks[slot]) + self.allocator.num_free) \
            * self.block_size
        if self.has_window:
            held_to = self._slot_wfirst[slot] + len(self._slot_wblocks[slot])
            fit = min(fit, (held_to + self.window_allocator.num_free)
                      * self.block_size)
        return fit

    def window_held_tokens(self, at=None):
        """(tokens of contexts held in window-layer blocks, tokens the
        same slots' contexts hold): what the window allocator keeps
        against what a table with no window would. `at`: the
        `window_held_state()` of an earlier moment, counted now."""
        if not self.has_window:
            return 0, 0
        lens, wfirst = at or (self.slot_lens, self._slot_wfirst)
        held = ctx = 0
        for slot in range(self.max_slots):
            n = int(lens[slot])
            if n:
                ctx += n
                held += n - min(n, wfirst[slot] * self.block_size)
        return held, ctx

    def window_held_state(self):
        """What `window_held_tokens` reads, copied: two short rows."""
        return (self.slot_lens.copy(), list(self._slot_wfirst)) \
            if self.has_window else None

    # ------------------------------------------------------------ sizing
    @property
    def quantized(self):
        return KV_DTYPES[self.kv_dtype][1]

    @property
    def kv_bytes_per_token(self):
        """HBM bytes one cached token costs across K+V and all layers,
        including the quantization scales and (amortized per token)
        the block-summary rows — the number the
        `paddle_tpu_serving_kv_bytes_per_token` gauge publishes and
        `tools/kv_smoke.py`/`tools/longctx_smoke.py` budget with. Read
        per engine step for the gauge, so it is pure host arithmetic
        on fixed geometry (the explicit `KV_DTYPES` itemsize map —
        np.dtype only knows "bfloat16"/fp8 after jax registers
        ml_dtypes, an import-order dependency not worth having)."""
        itemsize = KV_DTYPES[self.kv_dtype][0]
        per = self.num_heads * self.head_dim * itemsize
        if self.quantized:
            per += self.num_heads * 4            # fp32 scale per head
        per *= 2                                 # K and V
        if self.summaries:
            # one fp32 min + max K-summary row per BLOCK, spread over
            # its block_size tokens (K only — the scorer never needs V)
            per += (2 * self.num_heads * self.head_dim * 4
                    ) // self.block_size
        return len(self.attention_layers) * per \
            + self.idx_bytes_per_token + self.latent_bytes_per_token

    @property
    def idx_bytes_per_token(self):
        """HBM bytes the indexer keys of one cached token take over the
        sparse layers (a padded row a layer)."""
        return sum(int(p.shape[-1]) * p.dtype.itemsize
                   for p in self.idx_pools)

    @property
    def latent_bytes_per_token(self):
        """HBM bytes the latent rows of one cached token take over the
        latent layers (a padded row a layer)."""
        return sum(int(p.shape[-1]) * p.dtype.itemsize
                   for p in self.latent_pools)

    @property
    def state_bytes(self):
        """HBM bytes the linear layers' recurrent states and convolution
        tails occupy, for every slot, whatever the context."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in self.states + self.conv_tails)

    @property
    def state_slots_in_use(self):
        """Slots whose recurrent state is live: a slot that holds
        tokens, in a cache with a linear layer. `release_slot` marks it
        dead by the slot's length alone: nothing is written."""
        return int(np.count_nonzero(self.slot_lens)) \
            if self.linear_layers else 0

    def _refuse_with_state(self, what):
        if self.linear_layers:
            raise ValueError(
                f"{what} is not built for a cache with a linear layer: "
                "a recurrent state can be neither truncated nor shared "
                "by blocks")

    def _refuse_with_indexer(self, what):
        for layers, kind, pool in (
                (self.sparse_layers, "sparse", "indexer-key pool beside "
                 "its K/V"),
                (self.latent_layers, "latent", "latent pool in place of "
                 "K/V")):
            if layers:
                raise ValueError(
                    f"{what} is not built for a cache with a {kind} "
                    "layer: the copy and transport executables index "
                    f"STACKED pools at axis 1, and a layer's {pool} "
                    "would have to ride every frame of the codec")

    @property
    def block_bytes(self):
        """HBM bytes one K+V block (all layers) occupies, incl scales."""
        return self.kv_bytes_per_token * self.block_size

    @property
    def max_slot_tokens(self):
        return self.max_blocks_per_slot * self.block_size

    def blocks_for(self, n_tokens):
        return -(-int(n_tokens) // self.block_size)

    def blocks_missing(self, slot, new_len):
        return max(0, self.blocks_for(new_len)
                   - len(self._slot_blocks[slot]))

    def slot_num_blocks(self, slot):
        return len(self._slot_blocks[slot])

    def slot_blocks(self, slot):
        """The slot's ordered block list (a copy)."""
        return list(self._slot_blocks[slot])

    # --------------------------------------------------------- lifecycle
    def _alloc(self, n):
        """Allocator alloc with the prefix-cache backstop: a dry free
        list first evicts LRU refcount-0 cached leaves, then retries —
        so cached-but-idle blocks never cause a preemption the pool
        could have absorbed."""
        got = self.allocator.alloc(n)
        if got is None and self.prefix_cache is not None:
            self.prefix_cache.evict(n - self.allocator.num_free)
            got = self.allocator.alloc(n)
        return got

    def ensure_capacity(self, slot, new_len) -> bool:
        """Grow `slot`'s block table to cover `new_len` tokens. False
        (state unchanged) when the free list can't supply the blocks."""
        if new_len > self.max_slot_tokens:
            raise ValueError(
                f"slot needs {new_len} tokens but max_blocks_per_slot="
                f"{self.max_blocks_per_slot} x block_size="
                f"{self.block_size} caps it at {self.max_slot_tokens}")
        need = self.blocks_missing(slot, new_len)
        wneed = 0
        if self.has_window:
            # columns from the first the slot's next query can reach
            wrow = self._slot_wblocks[slot]
            if not wrow:
                self._slot_wfirst[slot] = max(
                    self._slot_wfirst[slot],
                    self._window_first_col(self.slot_lens[slot]))
            wneed = max(0, self.blocks_for(new_len)
                        - self._slot_wfirst[slot] - len(wrow))
            if wneed > self.window_allocator.num_free:
                return False
        if need:
            got = self._alloc(need)
            if got is None:
                return False
            row = self._slot_blocks[slot]
            for b in got:
                self.block_tables[slot, len(row)] = b
                row.append(b)
        if wneed:
            col = self._slot_wfirst[slot] + len(wrow)
            for b in self.window_allocator.alloc(wneed):
                self.window_tables[slot, col] = b
                wrow.append(b)
                col += 1
        return True

    # ---------------------------------------------------- prefix sharing
    def adopt_blocks(self, slot, blocks):
        """Append already-allocated (cached) blocks to `slot`'s table,
        taking one reference per block. Used at admission when the
        prefix cache matched the head of the prompt — the slot reads
        these blocks but never writes them (its first uncached token
        lands in the next, privately-allocated block)."""
        row = self._slot_blocks[slot]
        if len(row) + len(blocks) > self.max_blocks_per_slot:
            raise ValueError("adopted prefix exceeds max_blocks_per_slot")
        self.allocator.incref(blocks)
        for b in blocks:
            self.block_tables[slot, len(row)] = b
            row.append(b)

    def cow_block(self, slot, index):
        """Copy-on-write `slot`'s table entry at `index`: allocate a
        private block, device-copy the shared block's K/V columns into
        it, swap the table entry and drop the slot's reference on the
        original. Returns True on success, False (state unchanged) when
        no block could be allocated even after cache eviction.

        This is how a request EXTENDS a shared block: the matched
        prefix may end mid-block (e.g. the prompt's last token falls
        inside a fully-cached block, and the last prompt token must
        always be re-fed to sample the first output). Writing there
        would corrupt every other reader, so the writer gets its own
        copy first."""
        self._refuse_with_state("cow_block")
        self._refuse_with_indexer("cow_block (prefix sharing)")
        row = self._slot_blocks[slot]
        src = row[index]
        got = self._alloc(1)
        if got is None:
            return False
        dst = got[0]
        self._copy_block_data(src, dst)
        row[index] = dst
        self.block_tables[slot, index] = dst
        self.allocator.free([src])
        return True

    def _copy_block_data(self, src, dst):
        """pool[:, dst] = pool[:, src] for every pool array, as ONE
        jitted fixed-shape copy (block ids ride as traced scalars, so
        every CoW reuses the same executable; pools are donated in
        place). Quantized pools copy the per-entry scale columns and
        summary-tracking pools the block-summary rows in the SAME
        executable — every array indexes its block at axis 1, so a
        CoW'd block dequantizes AND scores identically to its
        source."""
        import jax.numpy as jnp

        if self._copy_fn is None:
            from ..jit.functional import instrumented_jit
            n = len(self._pools())

            def copy(*args):
                pools, src, dst = args[:n], args[n], args[n + 1]
                return tuple(p.at[:, dst].set(p[:, src]) for p in pools)

            self._copy_fn = instrumented_jit(
                copy, "serving_prefix_cow",
                donate_argnums=tuple(range(n)))
        out = self._copy_fn(*self._pools(), jnp.int32(src),
                            jnp.int32(dst))
        self._set_pools(out)

    # ------------------------------------------------- block transport
    def kv_meta(self):
        """The pool geometry a KV transfer must agree on end to end —
        shipped in every codec frame so a mismatched fleet is refused
        at import instead of corrupting a pool."""
        return {"num_layers": self.num_layers,
                "num_heads": self.num_heads,
                "head_dim": self.head_dim,
                "block_size": self.block_size,
                "dtype": self.dtype,
                "kv_dtype": self.kv_dtype,
                "summaries": self.summaries}

    def _transfer_fn(self, kind, width):
        """Jitted gather ("export") / donated scatter ("import") over
        the pools for a `[width]` block-id vector. One instrumented
        instance per (kind, pow2 width): ids ride as traced values, so
        every transfer of up to `width` blocks reuses the same
        executable — no per-block (or per-count) compile. Every pool
        array (payloads, scales, summaries) indexes its block at axis
        1, so one generic gather/scatter covers them all."""
        fn = self._transfer_fns.get((kind, width))
        if fn is not None:
            return fn
        import jax.numpy as jnp

        from ..jit.functional import instrumented_jit
        n = len(self._pools())

        if kind == "export":
            def gather(*args):
                pools, ids = args[:n], args[n]
                return tuple(jnp.moveaxis(p[:, ids], 1, 0)
                             for p in pools)

            fn = instrumented_jit(gather, "serving_kv_export")
        elif kind == "import":
            def scatter(*args):
                pools, ids, payload = args[:n], args[n], args[n + 1:]
                return tuple(
                    p.at[:, ids].set(jnp.moveaxis(a, 0, 1))
                    for p, a in zip(pools, payload))

            fn = instrumented_jit(scatter, "serving_kv_import",
                                  donate_argnums=tuple(range(n)))
        else:
            raise ValueError(f"unknown transfer kind {kind!r}")
        self._transfer_fns[(kind, width)] = fn
        return fn

    def _pools(self):
        if self.layer_kinds is not None:
            # k0, v0, k1, v1, ...: a pool an attention layer; then a
            # state and a convolution tail a linear layer
            return [p for kv in zip(self.k_pools, self.v_pools)
                    for p in kv] + \
                [a for st in zip(self.states, self.conv_tails)
                 for a in st] + list(self.idx_pools) \
                + list(self.latent_pools)
        out = [self.k_pool, self.v_pool]
        if self.quantized:
            out += [self.k_scale, self.v_scale]
        if self.summaries:
            out += [self.k_sum_min, self.k_sum_max]
        return out

    def _set_pools(self, arrays):
        """Inverse of `_pools()`: rebind the pool attributes from a
        jitted executable's output tuple (same fixed order)."""
        arrays = list(arrays)
        if self.layer_kinds is not None:
            n = 2 * len(self.attention_layers)
            self.k_pools, self.v_pools = arrays[0:n:2], arrays[1:n:2]
            m = n + 2 * len(self.linear_layers)
            self.states, self.conv_tails = arrays[n:m:2], \
                arrays[n + 1:m:2]
            i = m + len(self.sparse_layers)
            self.idx_pools, self.latent_pools = arrays[m:i], arrays[i:]
            return
        self.k_pool, self.v_pool = arrays[:2]
        arrays = arrays[2:]
        if self.quantized:
            self.k_scale, self.v_scale = arrays[:2]
            arrays = arrays[2:]
        if self.summaries:
            self.k_sum_min, self.k_sum_max = arrays[:2]

    def export_blocks(self, block_ids):
        """Read `block_ids`' pool columns out to host arrays: a tuple
        `(k, v)` — plus `(k_scale, v_scale)` for quantized pools and
        `(k_sum_min, k_sum_max)` for summary-tracking ones — each
        `[n, L, ...]` (block-major, so one block's bytes are
        contiguous for the wire codec). One jitted fixed-shape gather
        per pow2 id-width; ids need not be contiguous or ordered. The
        scale and summary rows ride the same block coordinates by
        construction, so an exported block dequantizes AND scores
        identically wherever it lands."""
        import jax.numpy as jnp

        from .batcher import next_pow2
        self._refuse_with_state("export_blocks (a MigrationTicket)")
        self._refuse_with_indexer("export_blocks (a MigrationTicket)")
        ids = [int(b) for b in block_ids]
        if not ids:
            raise ValueError("export_blocks needs at least one block")
        n = len(ids)
        width = next_pow2(n, lo=1)
        padded = np.zeros(width, np.int32)     # pad with the NULL block
        padded[:n] = ids
        out = self._transfer_fn("export", width)(
            *self._pools(), jnp.asarray(padded))
        self.blocks_exported += n
        return tuple(np.asarray(a)[:n] for a in out)

    def import_blocks(self, block_ids, arrays):
        """Scatter transported block payloads into `block_ids` (already
        allocated by the caller): the donated-pool inverse of
        `export_blocks`, one jitted fixed-shape scatter per pow2
        id-width. Payload dtypes/shapes are validated against the pool
        geometry first — a mismatched fleet is refused, never written.
        Padding entries land in the reserved NULL block, which is never
        read through."""
        import jax.numpy as jnp

        from .batcher import next_pow2
        ids = [int(b) for b in block_ids]
        if not ids:
            raise ValueError("import_blocks needs at least one block")
        n = len(ids)
        pools = self._pools()
        if len(arrays) != len(pools):
            raise ValueError(
                f"expected {len(pools)} payload arrays for "
                f"kv_dtype={self.kv_dtype!r}, got {len(arrays)}")
        for a, p in zip(arrays, pools):
            expect = (n, p.shape[0]) + tuple(p.shape[2:])
            if tuple(a.shape) != expect or str(a.dtype) != str(p.dtype):
                raise ValueError(
                    f"payload {tuple(a.shape)}/{a.dtype} does not match "
                    f"pool geometry {expect}/{p.dtype}")
        width = next_pow2(n, lo=1)
        padded_ids = np.zeros(width, np.int32)
        padded_ids[:n] = ids
        payload = []
        for a in arrays:
            a = np.asarray(a)
            if width > n:
                a = np.concatenate(
                    [a, np.zeros((width - n,) + a.shape[1:], a.dtype)],
                    axis=0)
            payload.append(jnp.asarray(a))
        out = self._transfer_fn("import", width)(
            *pools, jnp.asarray(padded_ids), *payload)
        self._set_pools(out)
        self.blocks_imported += n
        if self.place_pools is not None:
            # sharded engines re-pin the canonical pool sharding so the
            # next mixed step's input specs are byte-identical (the
            # PR 8/PR 10 silent-recompile lesson)
            self.place_pools(self)

    def import_into_slot(self, slot, slot_len, chunks):
        """Admit a migrated request's KV: allocate destination blocks
        covering `slot_len` tokens, scatter the transported chunks into
        them, and wire up `slot`'s table. Chunk coverage is validated
        to be exactly blocks [0, blocks_for(slot_len)) with no gaps
        BEFORE any allocation. Returns False (state unchanged) when the
        free list — after the prefix-cache eviction backstop — cannot
        supply the blocks; the scheduler leaves the request queued and
        retries next plan."""
        if slot_len <= 0:
            raise ValueError(f"import_into_slot needs slot_len >= 1, "
                             f"got {slot_len}")
        need = self.blocks_for(slot_len)
        ordered = sorted(chunks, key=lambda c: c.start)
        at = 0
        for c in ordered:
            if c.start != at:
                raise ValueError(
                    f"migration chunks leave a gap at block {at} "
                    f"(next chunk starts at {c.start})")
            at += c.count
        if at != need:
            raise ValueError(
                f"migration chunks cover {at} blocks but slot_len="
                f"{slot_len} needs {need}")
        if self._slot_blocks[slot]:
            raise ValueError(f"slot {slot} is not empty")
        got = self._alloc(need)
        if got is None:
            return False
        try:
            for c in ordered:
                self.import_blocks(got[c.start:c.start + c.count],
                                   c.arrays)
        except Exception:
            self.allocator.free(got)
            raise
        self._slot_blocks[slot] = list(got)
        self.block_tables[slot, :need] = got
        self.block_tables[slot, need:] = NULL_BLOCK
        self.slot_lens[slot] = slot_len
        return True

    def truncate_slot(self, slot, new_len):
        """Roll back `slot` to cover only `new_len` tokens: blocks past
        `blocks_for(new_len)` go back to the free list and their table
        entries reset to NULL. Returns the number of blocks freed.

        This is the speculative-decode rollback: rejected draft tokens
        may have forced block allocations their K/V never ended up
        needing; the garbage they DID write into still-owned blocks
        needs no cleanup (the position mask hides it and the next
        accepted tokens overwrite it). A sparse layer's indexer keys and
        a latent layer's rows follow: they lie at the K/V's (block,
        offset), and a position past the slot's length is attended by
        no query."""
        self._refuse_with_state("truncate_slot")
        keep = self.blocks_for(new_len)
        row = self._slot_blocks[slot]
        if len(row) <= keep:
            return 0
        extra = row[keep:]
        self.allocator.free(extra)
        self._slot_blocks[slot] = row[:keep]
        self.block_tables[slot, keep:] = NULL_BLOCK
        return len(extra)

    def release_slot(self, slot):
        row = self._slot_blocks[slot]
        if row:
            self.allocator.free(row)
        self._slot_blocks[slot] = []
        self.block_tables[slot, :] = NULL_BLOCK
        self.slot_lens[slot] = 0
        if self.has_window:
            if self._slot_wblocks[slot]:
                self.window_allocator.free(self._slot_wblocks[slot])
            self._slot_wblocks[slot] = []
            self._slot_wfirst[slot] = 0
            self.window_tables[slot, :] = NULL_BLOCK

    # ----------------------------------------------------------- metrics
    @property
    def blocks_in_use(self):
        """Blocks that hold a request's tokens, of both kinds."""
        used = self.allocator.num_used
        if self.window_allocator is not None:
            used += self.window_allocator.num_used
        return used

    @property
    def blocks_total(self):
        """Blocks the tables can draw on, of both kinds, NULL included."""
        return self.num_blocks + (self.num_window_blocks
                                  if self.window_allocator else 0)

    @property
    def utilization(self):
        cap = self.allocator.capacity
        if self.window_allocator is not None:
            cap += self.window_allocator.capacity
        return self.blocks_in_use / max(1, cap)
