"""Block-table-native Pallas TPU kernel for paged attention.

The serving engine's three attention shapes — ragged chunked prefill,
K-wide speculative verify and K=1 decode — are ONE pattern: a **query
run**, consecutive flat tokens that share a slot and carry consecutive
positions (a decode token is a run of 1, a prefill chunk of n tokens
one run of n, a verify group a run of K), attends its slot's paged K/V
at key positions `<= each query's own`. The pure-XLA paths in
`ops.pallas.flash_attention` gather the slot's whole block list into a
contiguous `[S_max, H, Dh]` copy per query before attending; this
kernel never materializes that copy, and walks each run's slot ONCE:

* one invocation a layer loops over the step's runs (`paged_runs`,
  derived from `slot_ids` / `positions` with fixed shapes); run
  metadata and the `[max_slots, max_blocks]` block tables ride in
  **scalar memory** (`pltpu.PrefetchScalarGridSpec`), the pools stay
  in HBM;
* per run, `cdiv(last_pos // BS + 1, G)` **double-buffered fetches**
  of G KV blocks (`make_async_copy` from `block_tables[slot, col]`),
  the next fetch — of this run or the next — flying while the current
  group is attended: the work follows the contexts, nothing is sized
  by `max_blocks`;
* every q tile of the run attends each fetched group while it sits in
  VMEM, a PLANE of KV heads at a time. The fetched rows are (key,
  head) pairs, as the `[BS, H, Dh]` pool tiles lie; a plane's rows are
  read out of them strided, with no relayout pass (`plane_heads`: one
  head of a 32-bit pool, two adjacent heads of a 16-bit pool through
  the buffer's uint32 view), and meet the (token, query head) rows of
  the plane's heads in one `[rows, Dh] x [Dh, cols]` MXU product in the
  pools' precision (fp32 accumulation). At most half of a product is
  masked (the other head of a pair), where one product for all H heads
  masked `(H - 1) / H`; one int32 compare a tile applies the
  head-diagonal within the plane and the causal mask together;
* **online softmax** (running max / denominator / weighted accumulator
  in fp32 VMEM scratch) per run; **context-length masking** hides the
  unwritten tail of the newest block, blocks past the run's last
  position are never fetched, padding rows leave as zeros.

Quantized pools: with `k_scale`/`v_scale` (`[NB, BS, H]` fp32,
per-pool-entry-per-head — see `serving.kv_cache.PagedKVCache`), the
K/V tiles arrive int8 / fp8 and each block's `BS * H` scales arrive
as one lane row beside them: K's scales multiply the logits' (key,
head) columns, V's the probabilities' — the tile's dequantisation,
applied where that axis is the lane axis. The scales lie beside ALL the
(key, head) columns, so a quantized pool is one plane of H heads: the
one product for every head, as before planes.

Stacked pools: a layer scan carries `[L, NB, BS, H, Dh]` pools and
passes them whole with `layer=li`; `layer_blocks` views them flat and
offsets the block table, so the same kernel body fetches layer `li`'s
blocks where they lie and no op takes the layer's pool out first.

The XLA gather paths stay the CPU parity oracles and the
`PADDLE_TPU_PAGED_PALLAS=0` fallback; `tests/test_paged_kernels.py`
runs every (shape x dtype) cell of this module against them in
interpret mode.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune

# finite mask value: -inf would NaN the running-max rescale on fully
# masked tiles (exp(-inf - -inf)); matches jax's paged kernel choice
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max) / 1e6  # ~-3.4e32/1e6

# Set by tests to run the kernels in Pallas interpret mode on the CPU
# mesh (exercises the real block-table/scalar-prefetch plumbing
# without a TPU).
_INTERPRET = False


def _on_tpu_backend() -> bool:
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


def pallas_killed() -> bool:
    """True when `PADDLE_TPU_PAGED_PALLAS=0` is set: the operator asked
    for the pure-XLA gather reference on EVERY paged-attention entry —
    including jax's library decode kernel, not just these kernels — so
    a Pallas miscompile can be ruled out with one env var."""
    return os.environ.get("PADDLE_TPU_PAGED_PALLAS", "1") == "0"


def paged_pallas_enabled(head_dim, block_size, heads=None,
                         quantized=False) -> bool:
    """Dispatch gate for the block-table-native kernels.

    Env kill-switch first (`PADDLE_TPU_PAGED_PALLAS=0` restores the
    XLA gather paths everywhere), then backend/shape: on a TPU backend
    the kernels want a lane-aligned head_dim and a sublane-aligned
    block size so KV tiles hit full (8/32 x 128) registers; under
    `_INTERPRET` (tests) any shape runs. The alignment predicate is
    `autotune.paged_alignment_ok` — the SAME source of truth the
    kernel tuner's candidate filters use, so a tuned candidate the
    serve-time gate would refuse cannot exist (ISSUE 11). Quantized
    pools add one condition: a block's `block_size * heads` scales
    ride the lane axis beside the logits' (key, head) columns, so
    they must fill whole 128-lane tiles."""
    if pallas_killed():
        return False
    if _INTERPRET:
        return True
    if quantized and heads is not None \
            and (int(block_size) * int(heads)) % autotune.LANE_ALIGN:
        return False
    return (_on_tpu_backend()
            and autotune.paged_alignment_ok(head_dim, block_size))


def paged_runs(slot_ids, positions, max_run=None):
    """The query runs of one flat-token step: maximal runs of
    consecutive flat tokens that share a slot and carry consecutive
    positions — a decode token is a run of 1, a prefill chunk of n
    tokens (or a verify group of K) one run of n. Padding tokens
    (`slot_ids == -1`) belong to no run. With `max_run`, a longer run
    is cut into runs of at most that many tokens (each walks its slot
    again): the kernel's softmax state is sized by the longest run.

    slot_ids, positions [T] int32 -> (n_runs [1], start [T], length
    [T], slot [T], first_pos [T]) int32; entries past `n_runs` hold
    length 0. Fixed shapes, a handful of [T]-sized ops: the serving
    step derives them once, outside its layer scan."""
    T = slot_ids.shape[0]
    slot = slot_ids.astype(jnp.int32)
    pos = positions.astype(jnp.int32)
    valid = slot >= 0
    off = jnp.full((1,), -2, jnp.int32)
    cont = (valid & (slot == jnp.concatenate([off, slot[:-1]]))
            & (pos == jnp.concatenate([off, pos[:-1]]) + 1))
    is_start = valid & ~cont
    # a run ends where the next token starts one, pads, or the axis ends
    is_end = valid & ~jnp.concatenate([cont[1:], jnp.zeros((1,), bool)])
    idx = jnp.arange(T, dtype=jnp.int32)
    if max_run is not None and max_run < T:
        # tokens since the run's start; a new run every `max_run`
        off = idx - jax.lax.cummax(jnp.where(is_start, idx, 0))
        is_start = is_start | (valid & (off % max_run == 0))
        is_end = is_end | (valid & ((off + 1) % max_run == 0))
    start = jnp.sort(jnp.where(is_start, idx, T))
    end = jnp.sort(jnp.where(is_end, idx, T))
    length = jnp.where(start < T, end - start + 1, 0)
    first = jnp.minimum(start, T - 1)
    n_runs = jnp.sum(is_start, dtype=jnp.int32).reshape(1)
    return n_runs, start, length, slot[first], pos[first]


def blocks_walked(runs, block_size):
    """KV blocks the kernel fetches for `runs`, (first position, tokens)
    pairs: each run walks its slot's table once, up to its last
    position, whatever q tiles its tokens are split into."""
    return sum((pos + n - 1) // block_size + 1 for pos, n in runs)


def block_end(p, L):
    """The last position of the block of L positions that holds `p`
    (blocks start at multiples of L; L a power of two, so that the
    kernel needs no vector division): the last key a query at `p`
    attends under the block-causal mask."""
    return p | (L - 1)


def check_causal_block(L):
    """`causal_block` as the kernel and its fallback take it: None
    (causal), or a power of two."""
    if L is None:
        return None
    L = int(L)
    if L < 1 or L & (L - 1):
        raise ValueError(f"causal_block={L} must be a power of two")
    return None if L == 1 else L


def plane_heads(H, pool_dtype, quantized=False):
    """KV heads one product attends, P: the fetched buffer's rows are
    (key, head) pairs, and a PLANE of P adjacent heads is read out of
    it with no relayout pass — 32-bit pools a head at a time (a
    sublane-strided read, stride H); 16-bit pools two adjacent heads at
    a time (Mosaic has no 16-bit strided read: the buffer viewed as
    uint32 words pairs rows (key, 2i) and (key, 2i + 1), a strided read
    of the words, stride H / 2, bitcast back, is the plane's (key,
    head-in-pair) rows). Everything else — an odd head count in 16
    bits, 8-bit pools, quantized pools, whose scales ride the lane axis
    beside ALL the (key, head) columns — takes the buffer as it lies:
    one plane of H heads."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    if quantized:
        return H
    if itemsize == 4:
        return 1
    if itemsize == 2 and H % 2 == 0:
        return 2
    return H


def run_tiles(H, BS, MB, Gq=1, P=None):
    """(G, TQ) from the shapes alone, for planes of P KV heads
    (`plane_heads`; None = all H in one plane). A product's columns are
    the plane's (key, head) pairs of one fetched group, `G * BS * P`,
    and its rows the (token, query head) pairs of one q tile that meet
    them, `TQ * P * Gq`:

    * G KV blocks a fetch: at most 16 (256 keys at BS = 16: 2-4 MB a
      fetch at 16-32 heads; 8 where all H heads are one plane, as
      before planes) and a column tile at most 2048 wide. What a
      group costs beside its keys — every plane's state loaded and
      stored, its products' latencies, the fetch's descriptors — does
      not shrink with the plane, so a plane of two heads wants more
      keys a group than a plane of all H did (8 blocks, or 2048 // (BS
      * H)): measured on the chip, PERF.md section 6, PR 32. More would
      still be faster; the last group's overhang, which the mask pays
      for, grows with it;
    * TQ query tokens a tile, so that the rows fill the MXU (up to 256)
      and the float32 logit tile stays under 1 MB (`rows * columns <=
      2**18`), at most 128 tokens, a multiple of 8 where it can be (a
      tile's state rows then start on a sublane tile).

    The masked share of a product is `1 - 1/P`: nothing at P = 1, half
    at P = 2, `(H - 1) / H` where the whole buffer is one plane."""
    P = H if P is None else P
    RT = P * Gq                           # rows a token in a plane
    G = max(1, min(2048 // (BS * P), 16 if P < H else 8, MB))
    TQ = min(256, 2 ** 18 // (G * BS * P)) // RT
    TQ = max(1, min(TQ, 128))
    if TQ >= 8:
        TQ -= TQ % 8
    else:
        TQ = 1 << (TQ.bit_length() - 1)
    return G, TQ


def tile_tokens(TQ):
    """The q tile heights of a kernel whose tallest is TQ tokens, in
    ascending order: a run takes the first that holds it (`run_tile`),
    its tallest else. 1 for a decode token; 8, 32 and 64 for the short
    runs between (a verify group, the chunks a scheduler cuts a prompt
    into), which a tile of 128 tokens would attend at up to 16 times
    their rows (on the chip, PR 32: 64-token chunks in 128-token tiles
    held the GPT cells' useful share of the logits at 20%); TQ. A row's
    arithmetic is the same in each: the height only says which rows
    share a product."""
    return tuple(t for t in (1, 8, 32, 64) if t < TQ) + (TQ,)


def run_tile(n, TQ):
    """Tokens a q tile of a run of n tokens."""
    return next((t for t in tile_tokens(TQ) if n <= t), TQ)


def _plane_batch(NPL, R, CP):
    """Planes one straight-line body attends: a plane's product, its
    softmax and its second product wait for one another, so U planes'
    loads, products and stores are laid out together for the scheduler
    to overlap — four at the least, and for a q tile of few rows (a
    decode token's `P * Gq`) as many as keep the logit tiles within ~32
    vregs. U divides NPL."""
    cap = max(4, 2 ** 15 // (-(-R // 8) * 8 * CP))
    return max(u for u in range(1, NPL + 1) if NPL % u == 0 and u <= cap)


def _run_kernel(nruns_ref, rstart_ref, rlen_ref, rslot_ref, rpos_ref,
                bt_ref, q_ref, dmat_ref, rowtok_ref, k_hbm, v_hbm, *rest,
                BS, H, P, G, TQ, quantized, mxu_dtype, Gq=1, window=None,
                causal_block=None, select_words=0):
    """The whole step in one invocation: for every run, walk the run's
    slot once — `cdiv(last_pos // BS + 1, G)` double-buffered fetches
    of G KV blocks — and let every q tile of the run attend each
    fetched group while it sits in VMEM, a PLANE of P KV heads at a
    time (`plane_heads`).

    The fetched buffer's rows are (key, head) pairs, as the pool's
    `[BS, H, Dh]` tiles lie; plane p's `G * BS * P` rows (key, head in
    plane) are read out of it strided, with no relayout pass. The rows
    that meet them are (token, query head of the plane's heads),
    `RT = P * Gq` a token, which the wrapper lays plane-major: q, the
    output and the softmax state are `[H / P, rows, ...]`. One `[R, Dh]
    x [Dh, G * BS * P]` MXU product a plane gives its heads' logits;
    `dmat` (column key index where the row's KV head is the column's, a
    huge value elsewhere) folds the head-diagonal within the plane and
    the causal mask into one compare, made once a (tile, group) and
    shared by the planes. With a `window`, a query at p attends keys
    `p - window < j <= p`: a run's walk starts at the first block its
    first query reaches, and a second compare masks inside it. With a
    `causal_block` L the mask is block-causal (`block_end`): a query
    at p attends the keys up to the END of its block of L positions,
    `j <= p | (L - 1)`, but none past its run's last token (a run ends
    on a block boundary, or where the sequence does: what lies behind
    it in the pool is not the sequence's). With a selection
    (`select_words` NW > 0: `select_bits`) a query attends, of the keys
    the rules above allow, those whose bit is set in its row of packed
    words: the run's rows of words come in once a run, and a (tile,
    group) reads its word plane, takes its bit and lays it over the
    tile's rows. The bits are data: nothing of the selection is
    computed here.

    A row's arithmetic does not depend on its run: the columns of a
    product and their order are the shapes' (G, BS, P), and the tile
    height (`tile_tokens`) and the planes laid out together
    (`_plane_batch`) only choose which independent rows and planes
    share an instruction stream.

    Refs: scalar prefetch (run count, start, length, slot, first
    position; block tables [S, MB]); q [H/P, T*RT + pad, Dh] fp32,
    pre-scaled; dmat [TQ*RT, G*BS*P] int32; rowtok [TQ*RT, 1] int32
    (row -> token of its tile); pools in HBM as [NB, BS*H, Dh] (+
    scales [NB, 1, BS*H]); out [H/P, T*RT + pad, Dh] fp32; scratch: two
    KV buffers, DMA semaphores and the runs' online-softmax state
    [H/P, state rows, ...]."""
    dup_ref = sel_hbm = selbuf = selsem = None
    if select_words:
        dup_ref, sel_hbm, *rest = rest
        *rest, selbuf, selsem = rest
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem,
         m_ref, l_ref, acc_ref) = rest
    else:
        o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref = rest
        ks_hbm = vs_hbm = ksbuf = vsbuf = None
    BH = BS * H
    NPL = H // P                          # planes
    RT = P * Gq                           # rows a token in a plane
    GK = G * BS                           # keys a group
    CP = GK * P                           # columns of a plane's product
    Dh = acc_ref.shape[-1]
    n_runs = nruns_ref[0]
    last_run = rstart_ref.shape[0] - 1

    def reach(p, end):
        """The last key position a query at `p` attends, in a run
        whose last token lies at `end`."""
        if causal_block is None:
            return p
        return jnp.minimum(block_end(p, causal_block), end)

    def copies(buf, slot, col, j):
        blk = bt_ref[slot, col]
        out = [pltpu.make_async_copy(
                   k_hbm.at[blk], kbuf.at[buf, pl.ds(j * BH, BH)],
                   sem.at[buf, 0]),
               pltpu.make_async_copy(
                   v_hbm.at[blk], vbuf.at[buf, pl.ds(j * BH, BH)],
                   sem.at[buf, 1])]
        if quantized:
            out += [pltpu.make_async_copy(
                        ks_hbm.at[blk],
                        ksbuf.at[buf, :, pl.ds(j * BH, BH)],
                        sem.at[buf, 2]),
                    pltpu.make_async_copy(
                        vs_hbm.at[blk],
                        vsbuf.at[buf, :, pl.ds(j * BH, BH)],
                        sem.at[buf, 3])]
        return out

    def fetch(buf, slot, g, nblk, wait, lo=None):
        """Start (or wait for) the copies of group g of `slot`: only
        the blocks the run needs (from block `lo` on, under a window),
        a loop over them; the rest of the buffer keeps an older group's
        contents, which the mask hides."""
        def one(j, c):
            for cp in copies(buf, slot, g * G + j, j):
                cp.wait() if wait else cp.start()
            return c
        first = 0 if lo is None else jnp.maximum(lo - g * G, 0)
        jax.lax.fori_loop(first, jnp.minimum(nblk - g * G, G), one, 0)

    def plane(ref, buf, p):
        """Plane p's [CP, Dh] rows (key, head in plane) of a buffer."""
        if P == H:
            return ref[buf]
        if ref.dtype.itemsize == 4:
            return ref[buf, pl.ds(p, GK, stride=H)]
        words = ref.bitcast(jnp.uint32)[buf, pl.ds(p, GK, stride=H // 2)]
        return pltpu.bitcast(words, ref.dtype)

    # stale buffer contents are masked, never trusted. A stale key only
    # reaches a logit the mask replaces; a stale value would meet a
    # probability of 0 in the product, and 0 x NaN is NaN: V (and its
    # scales) start finite
    vbuf[...] = jnp.zeros_like(vbuf)
    if quantized:
        vsbuf[...] = jnp.zeros_like(vsbuf)
    # padding rows are never attended: they leave as zeros
    o_ref[...] = jnp.zeros_like(o_ref)

    def run_blocks(r):
        # at least one, so every run is an item of the fetch chain
        return jnp.maximum(rpos_ref[r] + rlen_ref[r] - 1, 0) // BS + 1

    def run_first(r):
        """(first block, first group) of run r's walk: block 0 with no
        window, else the block of the first key its first query sees."""
        if window is None:
            return None, 0
        lo = jnp.maximum(rpos_ref[r] - (window - 1), 0) // BS
        return lo, lo // G

    @pl.when(n_runs > 0)
    def _prime():
        lo, g0 = run_first(0)
        fetch(0, rslot_ref[0], g0, run_blocks(0), wait=False, lo=lo)

    def attend(tq, buf, g, first, last, start, n, pos0):
        """Every q tile (tq tokens, R = tq * RT rows a plane) of the
        run against the group in buffer `buf`, plane by plane."""
        R = tq * RT
        U = _plane_batch(NPL, R, CP)
        short = R <= 64                   # a few vregs a plane: unroll
        base = g * GK                     # first key position of group

        def tile(j, carry):
            off = j * tq
            rs = off * RT                 # state rows of this tile
            rq = (start + off) * RT       # its rows in q / out
            # causal skip: the group lies past the tile's last query;
            # window skip: it lies behind the window of its first
            live = base <= reach(pos0 + off + tq - 1, pos0 + n - 1)
            if window is not None:
                live &= base + GK - 1 > pos0 + off - window

            @pl.when(first)
            def _init():
                def one(p, c):
                    m_ref[p, pl.ds(rs, R)] = jnp.full(
                        (R, 1), MASK_VALUE, jnp.float32)
                    l_ref[p, pl.ds(rs, R)] = jnp.zeros((R, 1),
                                                       jnp.float32)
                    acc_ref[p, pl.ds(rs, R)] = jnp.zeros((R, Dh),
                                                         jnp.float32)
                    return c
                jax.lax.fori_loop(0, NPL, one, 0, unroll=short)

            @pl.when(live)
            def _accumulate():
                # key index (heads agreeing) <= the last key the query
                # attends (its own position; its block's end) - base
                tok = rowtok_ref[0:R] + off                   # [R, 1]
                if causal_block is None:
                    thr = jnp.where(tok < n, tok + (pos0 - base), -1)
                else:
                    thr = jnp.where(
                        tok < n,
                        reach(tok + pos0, pos0 + n - 1) - base, -1)
                keep = dmat_ref[0:R] <= thr                   # [R, CP]
                if window is not None:
                    keep &= dmat_ref[0:R] > thr - window
                if select_words:
                    # the tile's tokens' words of this group's plane,
                    # a row a token; the group's bit, over the token's
                    # RT rows
                    # (a strided read wants 128 lanes a row: a plane's
                    # GK lanes, one a key, lie as GK / 128 rows)
                    Q = GK // selbuf.shape[1]
                    row = (off * select_words + g % select_words) * Q
                    words = jnp.concatenate([
                        selbuf[pl.ds(row + c, tq, stride=select_words * Q)]
                        for c in range(Q)], axis=1)
                    bit = (words >> (g // select_words)) & 1  # [tq, GK]
                    if P > 1:
                        # a key's bit onto its P columns (key, head in
                        # plane): a product with the 0 / 1 table, exact
                        bit = jnp.broadcast_to(
                            bit.astype(jnp.float32)[None],
                            (-(-8 // tq), tq, GK)).reshape(-1, GK)
                        bit = jnp.dot(
                            bit, dup_ref[...],
                            preferred_element_type=jnp.float32)[:tq]
                    keep &= jnp.broadcast_to(
                        bit[:, None, :], (tq, RT, CP)).reshape(R, CP) != 0

                def planes(i, c):
                    """U planes from plane i * U on: every load of the
                    state before any store, so that nothing orders the
                    planes' products and softmaxes among themselves."""
                    p0 = i * U
                    st = (pl.ds(p0, U), pl.ds(rs, R))
                    qs = q_ref[pl.ds(p0, U), pl.ds(rq, R)]    # [U, R, Dh]
                    ms, ls, accs = m_ref[st], l_ref[st], acc_ref[st]
                    for u in range(U):
                        k = plane(kbuf, buf, p0 + u)          # [CP, Dh]
                        v = plane(vbuf, buf, p0 + u)
                        if quantized:
                            k = k.astype(jnp.float32)
                            v = v.astype(jnp.float32)
                        s = jax.lax.dot_general(
                            qs[u].astype(mxu_dtype), k.astype(mxu_dtype),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        if quantized:
                            s = s * ksbuf[buf]                # [1, CP]
                        s = jnp.where(keep, s, MASK_VALUE)    # [R, CP]
                        m_new = jnp.maximum(
                            ms[u], jnp.max(s, axis=-1, keepdims=True))
                        alpha = jnp.exp(ms[u] - m_new)
                        # rows past the run's end in its last tile keep
                        # no key and would count the mask as
                        # probability 1: zero them
                        pr = jnp.where(keep, jnp.exp(s - m_new), 0.0)
                        l_new = ls[u] * alpha + jnp.sum(
                            pr, axis=-1, keepdims=True)
                        if quantized:
                            pr = pr * vsbuf[buf]
                        acc_new = accs[u] * alpha + jax.lax.dot_general(
                            pr.astype(mxu_dtype), v.astype(mxu_dtype),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        m_ref[p0 + u, pl.ds(rs, R)] = m_new
                        l_ref[p0 + u, pl.ds(rs, R)] = l_new
                        acc_ref[p0 + u, pl.ds(rs, R)] = acc_new
                    return c

                if U == NPL:
                    planes(0, 0)
                else:
                    jax.lax.fori_loop(0, NPL // U, planes, 0)

            @pl.when(last)
            def _finalize():
                # ascending runs: rows this tile writes past the run's
                # end (zeros, l == 0) are the next runs' rows, rewritten
                # when their turn comes
                def one(p, c):
                    l = jnp.maximum(l_ref[p, pl.ds(rs, R)], 1e-30)
                    o_ref[p, pl.ds(rq, R)] = (
                        acc_ref[p, pl.ds(rs, R)] / l).astype(o_ref.dtype)
                    return c
                jax.lax.fori_loop(0, NPL, one, 0, unroll=short)
            return carry

        jax.lax.fori_loop(0, (n + tq - 1) // tq, tile, 0)

    def run_body(r, it):
        start, n = rstart_ref[r], rlen_ref[r]
        slot, pos0 = rslot_ref[r], rpos_ref[r]
        nblk = run_blocks(r)
        ngroups = (nblk + G - 1) // G
        lo, g0 = run_first(r)
        nxt = jnp.minimum(r + 1, last_run)
        more_runs = r + 1 < n_runs
        if select_words:
            # the run's rows of selection words (a whole number of
            # sublane tiles a token), for all its tiles and groups
            rows, lanes = selbuf.shape
            cp = pltpu.make_async_copy(
                sel_hbm.at[pl.ds(start * select_words * (GK // lanes),
                                 rows)], selbuf, selsem.at[0])
            cp.start()
            cp.wait()

        def group_body(g, it):
            buf = it % 2
            fetch(buf, slot, g, nblk, wait=True, lo=lo)
            last = g == ngroups - 1

            # the next item's fetch flies while this group is attended
            @pl.when(jnp.logical_not(last))
            def _next_group():
                fetch(1 - buf, slot, g + 1, nblk, wait=False, lo=lo)

            @pl.when(last & more_runs)
            def _next_run():
                lo_n, g0_n = run_first(nxt)
                fetch(1 - buf, rslot_ref[nxt], g0_n, run_blocks(nxt),
                      wait=False, lo=lo_n)

            # one body a tile height: the run's is the first that holds it
            heights = tile_tokens(TQ)
            for lo_n, tq in zip((0,) + heights, heights):
                fits = n > lo_n
                if tq != TQ:
                    fits &= n <= tq

                @pl.when(fits)
                def _(tq=tq):
                    attend(tq, buf, g, g == g0, last, start, n, pos0)
            return it + 1

        return jax.lax.fori_loop(g0, ngroups, group_body, it)

    jax.lax.fori_loop(0, n_runs, run_body, 0)


def _mask_tables(P, BS, G, TQ, Gq=1):
    """For planes of P KV heads. dmat [TQ*P*Gq, G*BS*P]: the column's
    key index within its group where the row's query head belongs to
    the column's KV head, a value no threshold reaches elsewhere (no
    such column at P = 1); rowtok [TQ*P*Gq, 1]: the row's token within
    its q tile."""
    import numpy as np
    RT = P * Gq
    rows = np.arange(TQ * RT)
    cols = np.arange(G * BS * P)
    same = ((rows[:, None] % RT) // Gq) == (cols[None, :] % P)
    dmat = np.where(same, cols[None, :] // P, np.int32(2 ** 30))
    return (jnp.asarray(dmat, jnp.int32),
            jnp.asarray(rows[:, None] // RT, jnp.int32))


def select_bits(select, T, MB, BS, G, longest):
    """The selection `select [T, >= MB * BS] bool` (may a query attend
    the key at this position, beside what the mask allows) as the
    kernel reads it: int32 words, NW word planes a token (a multiple of
    8: a token's planes are whole sublane tiles), a plane the `G * BS`
    keys of a fetched group, a lane a key, laid as rows of 128 lanes
    (`[(T + longest) * NW * GK / 128, 128]`; one row a plane where GK
    is no multiple of 128). A fetched group g of G blocks is bit `g //
    NW` of plane `g % NW`: lane c of that plane holds the selection of
    key `g * G * BS + c`. -> (words, NW)."""
    GK = G * BS
    NG = -(-MB // G)
    NW = 8 * -(-NG // 256)
    nbits = -(-NG // NW)
    x = select[:, :MB * BS]
    x = jnp.pad(x, ((0, longest), (0, nbits * NW * GK - x.shape[1])))
    x = x.reshape(T + longest, nbits, NW, GK)
    # bit by bit, elementwise: a reduction over the bits would have XLA
    # lay them along the lanes, padded to 128
    words = sum(x[:, b].astype(jnp.uint32) << jnp.uint32(b)
                for b in range(nbits))
    words = jax.lax.bitcast_convert_type(words, jnp.int32)
    return words.reshape(-1, GK if GK % 128 else 128), NW


def layer_blocks(block_tables, layer, *pools):
    """The ONE rule by which a layer's blocks are addressed in STACKED
    pools `[L, NB, ...]`: the pools viewed flat, `[L * NB, ...]`
    (merging the two leading axes moves nothing), and layer `layer`'s
    block b is flat block `layer * NB + b` — so the offset goes onto the
    table, and a reader fetches the layer's blocks where they lie, with
    no slice of the layer's pool taken out first. Table entry 0 lands
    on flat block `layer * NB`, the layer's own NULL block. `layer`
    None: the pools are one layer's already, and everything passes
    through. `pools` may hold None (no scales).

    -> (block_tables, pools)"""
    if layer is None:
        return block_tables, pools
    nb = next(p for p in pools if p is not None).shape[1]
    flat = tuple(None if p is None else p.reshape((-1,) + p.shape[2:])
                 for p in pools)
    return block_tables.astype(jnp.int32) + layer * nb, flat


def _paged_attend_runs(q, k_pool, v_pool, block_tables, slot_ids,
                       positions, k_scale=None, v_scale=None, *,
                       scale=None, kernel_name="paged_ragged",
                       tuning=None, runs=None, groups=None, window=None,
                       max_run=None, layer=None, causal_block=None,
                       select=None):
    """Run-major block-table-native attention — ONE walk per (slot,
    step).

    q [T, Hq, Dh]; k_pool/v_pool [NB, BS, H, Dh] with Hq a multiple of
    H (query head i reads KV head `i // (Hq // H)`); block_tables
    [S, MB] int32; slot_ids [T] int32 (-1 = padding); positions [T]
    int32. Optional k_scale/v_scale [NB, BS, H] fp32 dequantize int8 /
    fp8 pools inside the tile. Returns [T, Hq, Dh] in q.dtype (padding
    rows zero). `runs` takes a precomputed `paged_runs(slot_ids,
    positions, max_run)` so a layer scan derives it once. `window`
    (None = full attention): a query at p attends keys
    `p - window < j <= p`, and table columns behind a run's window are
    never fetched (the cache manager may have released them).
    `max_run` bounds the tokens of one run, and with them the softmax
    state the kernel keeps in VMEM (None = the whole token axis).
    `causal_block` (None = causal; a power of two L): the mask is
    block-causal, a query at p attends keys `j <= p | (L - 1)`, the end
    of its block of L positions, and none past its run's last token:
    every run (a cut of `max_run` too, so `max_run` a multiple of L
    and runs from multiples of L) must end on a block boundary or at
    its sequence's end; the caller that makes the runs sees to it.
    `layer` (None = the pools are one layer's): the pools and scales
    are STACKED, `[L, NB, ...]`, and the kernel reads layer `layer`'s
    blocks in place (`layer_blocks`); it may be a traced scalar, a
    scan's layer index. `select` (None = every key the mask allows;
    bool `[T, >= MB * BS]`): a query attends only the keys whose entry
    is True, of those the mask allows: a per-(query row, key) selection
    made elsewhere and applied here as data (`select_bits`). None
    traces the kernel without it.

    `kernel_name` names the Mosaic call (what a device trace and the
    benchmark's kernel check read) and keys the autotuner lookup: the
    one tunable is `kv_blocks`, the KV blocks per compute step,
    resolved HERE at trace time — a cached winner costs one dict probe
    inside the one compile and nothing per step; absent, `run_tiles`
    picks it from the shapes. The block-sparse decode entry
    ("paged_sparse", ISSUE 15) is this same kernel fed a SHORTENED
    per-slot block table — the table width IS the sparsity budget, so
    its cache bucket carries MB where the dense entries' buckets do
    not."""
    T, HQ, Dh = q.shape
    # one layer's blocks: what the cost estimate counts, stacked or not
    NB1 = k_pool.shape[0 if layer is None else 1]
    block_tables, (k_pool, v_pool, k_scale, v_scale) = layer_blocks(
        block_tables, layer, k_pool, v_pool, k_scale, v_scale)
    NB, BS, H = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    if HQ % H:
        raise ValueError(f"{HQ} query heads do not divide into groups "
                         f"over {H} KV heads")
    Gq = HQ // H
    S, MB = block_tables.shape
    quantized = k_scale is not None
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    P, G, TQ = kernel_tiles(
        T, H, Gq, Dh, BS, MB, k_pool.dtype, quantized=quantized,
        max_run=max_run, kernel_name=kernel_name, tuning=tuning,
        groups=groups)
    NPL, RT = H // P, P * Gq
    BH, C, CP = BS * H, G * BS * H, G * BS * P
    # MXU operands in the pools' / queries' own precision (bf16 x bf16
    # on a bf16 deployment), fp32 accumulation and softmax state
    kv_float = q.dtype if quantized else k_pool.dtype
    mxu_dtype = (jnp.bfloat16 if q.dtype == kv_float == jnp.bfloat16
                 else jnp.float32)
    out_dtype = q.dtype if q.dtype != jnp.float64 else jnp.float32
    longest = T if max_run is None else min(int(max_run), T)
    if runs is None:
        runs = paged_runs(slot_ids, positions, max_run)
    rows = -(-longest // TQ) * TQ * RT    # state rows: the longest run
    pad = TQ * RT                         # a tile may overhang the axis

    def planes(x):
        """[T, HQ, Dh] -> [NPL, T * RT, Dh]: a plane's rows (token,
        query head of its KV heads) as the kernel reads them."""
        return x.reshape(T, NPL, RT, Dh).transpose(1, 0, 2, 3).reshape(
            NPL, T * RT, Dh)

    # pre-scaled in fp32; the kernel rounds each tile to the MXU dtype.
    # fp32 in VMEM, out too: a token's RT rows start on any multiple of
    # RT, which a packed dtype's tiles do not allow
    q2 = jnp.pad(planes(q.astype(jnp.float32) * scale),
                 ((0, 0), (0, pad), (0, 0)))
    dmat, rowtok = _mask_tables(P, BS, G, TQ, Gq)
    args = [q2, dmat, rowtok,
            k_pool.reshape(NB, BH, Dh), v_pool.reshape(NB, BH, Dh)]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [vmem, vmem, vmem, hbm, hbm]
    scratch = [pltpu.VMEM((2, C, Dh), k_pool.dtype),
               pltpu.VMEM((2, C, Dh), v_pool.dtype)]
    NW = 0
    if select is not None:
        words, NW = select_bits(select, T, MB, BS, G, longest)
        # key -> its P columns (key, head in plane) of a product
        dup = (jnp.arange(CP)[None, :] // P
               == jnp.arange(G * BS)[:, None]).astype(jnp.float32)
        args += [dup, words]
        in_specs += [vmem, hbm]
    if quantized:
        args += [k_scale.astype(jnp.float32).reshape(NB, 1, BH),
                 v_scale.astype(jnp.float32).reshape(NB, 1, BH)]
        in_specs += [hbm, hbm]
        scratch += [pltpu.VMEM((2, 1, C), jnp.float32),
                    pltpu.VMEM((2, 1, C), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((2, 4 if quantized else 2)),
                pltpu.VMEM((NPL, rows, 1), jnp.float32),
                pltpu.VMEM((NPL, rows, 1), jnp.float32),
                pltpu.VMEM((NPL, rows, Dh), jnp.float32)]
    if NW:
        scratch += [pltpu.VMEM((longest * NW * G * BS // words.shape[1],
                                words.shape[1]), jnp.int32),
                    pltpu.SemaphoreType.DMA((1,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(), in_specs=in_specs,
        out_specs=vmem, scratch_shapes=scratch)
    kernel = functools.partial(
        _run_kernel, BS=BS, H=H, P=P, G=G, TQ=TQ, quantized=quantized,
        mxu_dtype=mxu_dtype, Gq=Gq,
        window=None if window is None else int(window),
        causal_block=check_causal_block(causal_block), select_words=NW)
    # a full pool read once, every query against a mean slot's share
    # of it: the work follows the contexts, not the table's width
    kv_tokens = min(NB1, S * MB) * BS
    ctx = min(kv_tokens // max(S, 1) + 1, MB * BS)
    if window is not None:
        ctx = min(ctx, int(window))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NPL, T * RT + pad, Dh),
                                       jnp.float32),
        interpret=_INTERPRET, name=kernel_name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(NPL * (T * RT + pad),
                                         NPL * rows, TQ * RT, C, CP, Dh,
                                         k_pool.dtype.itemsize,
                                         (longest * NW + CP) * G * BS
                                         * 4)),
        cost_estimate=pl.CostEstimate(
            flops=4 * T * HQ * Dh * ctx,
            bytes_accessed=(2 * kv_tokens * H * Dh
                            * k_pool.dtype.itemsize
                            + 2 * T * HQ * Dh * q.dtype.itemsize),
            transcendentals=T * HQ * ctx),
    )(*runs, block_tables.astype(jnp.int32), *args)
    out = out[:, :T * RT].reshape(NPL, T, RT, Dh).transpose(1, 0, 2, 3)
    return out.reshape(T, HQ, Dh).astype(out_dtype)


def kernel_tiles(T, H, Gq, Dh, BS, MB, pool_dtype, *, quantized=False,
                 max_run=None, kernel_name="paged_ragged", tuning=None,
                 groups=None):
    """(P, G, TQ) of the compiled kernel for these shapes: the heads a
    plane (`plane_heads`), the KV blocks a fetch — the tuner's cached
    `kv_blocks` for the bucket if there is one, else `run_tiles`' — and
    the tokens a q tile, no taller than the longest run. What the call
    traces with and what `logits_issued` counts by."""
    P = plane_heads(H, pool_dtype, quantized)
    N, K = groups or (T, 1)               # the tuner's bucket: groups
    if kernel_name == "paged_sparse":
        bucket = autotune.shape_bucket(N, K, H, Dh, BS, MB)
    else:
        bucket = autotune.shape_bucket(N, K, H, Dh, BS)
    tuned = tuning if tuning is not None else autotune.kernel_config(
        kernel_name, bucket, jnp.dtype(pool_dtype), default=None) or {}
    G, TQ = run_tiles(H, BS, MB, Gq, P)
    G = max(1, min(int(tuned.get("kv_blocks", G)), MB))
    longest = T if max_run is None else min(int(max_run), T)
    return P, G, min(TQ, -(-longest // 8) * 8)


def logits_issued(runs, tiles, H, Gq, block_size, window=None,
                  max_run=None, causal_block=None):
    """Logits the kernel computes (and exponentiates) for `runs`,
    (first position, tokens) pairs, with `tiles` = `kernel_tiles(...)`:
    products x rows x columns as `_run_kernel` issues them — a run's
    tiles by `run_tile`, runs cut at `max_run`; a tile meets a fetched
    group unless the group lies past its last query (past the end of
    that query's block, with a `causal_block`; never past the run's
    own end) or (window) behind its first query's window; H / P products
    of `tq * P * Gq` rows by `G * BS * P` columns each time. The
    useful ones among them are the (query, key) pairs x H x Gq."""
    P, G, TQ = tiles
    GK = G * block_size
    issued = 0
    for pos, n in runs:
        for cut in range(0, n, max_run or n):
            p0, m = pos + cut, min((max_run or n), n - cut)
            tq = run_tile(m, TQ)
            g0 = 0 if window is None \
                else max(p0 - (window - 1), 0) // block_size // G
            ngroups = -(-((p0 + m - 1) // block_size + 1) // G)
            for off in range(0, m, tq):
                top = p0 + off + tq - 1
                if causal_block:
                    top = min(block_end(top, causal_block), p0 + m - 1)
                hi = min(ngroups - 1, top // GK)
                lo = g0 if window is None \
                    else max(g0, (p0 + off - window + 1) // GK)
                issued += max(hi - lo + 1, 0) * tq
    return issued * H * Gq * GK * P


def _vmem_limit(q_rows, state_rows, tile_rows, C, CP, Dh, kv_itemsize,
                select_bytes=0):
    """Scoped-VMEM ask of the run kernel: what it keeps resident
    (queries and output in fp32, the mask table, two K and V buffers,
    the softmax state) plus the mask, logits and probabilities of one
    tile, with headroom for Mosaic's own temporaries."""
    lanes = max(Dh, 128)
    resident = (2 * q_rows * lanes * 4 + tile_rows * CP * 4
                + 4 * C * lanes * kv_itemsize
                + state_rows * (2 * 128 + lanes) * 4 + select_bytes)
    ask = 2 * (resident + 5 * tile_rows * CP * 4)
    return int(min(100 * 2 ** 20, max(32 * 2 ** 20, ask)))


# --------------------------------------------------------------- entries


def ragged_attend(q, k_pool, v_pool, block_tables, slot_ids, positions,
                  k_scale=None, v_scale=None, *, scale=None,
                  kernel_name="paged_ragged", runs=None, window=None,
                  max_run=None, layer=None, causal_block=None,
                  select=None):
    """Flat-token ragged paged attention (chunked prefill + plain
    decode): q [T, H, Dh]. Signature mirrors
    `flash_attention.ragged_paged_attention`. The sparse decode region
    passes `kernel_name="paged_sparse"` with its shortened tables so
    tuned configs resolve under the sparse key."""
    return _paged_attend_runs(
        q, k_pool, v_pool, block_tables, slot_ids, positions,
        k_scale, v_scale, scale=scale, kernel_name=kernel_name,
        runs=runs, window=window, max_run=max_run, layer=layer,
        causal_block=causal_block, select=select)


def verify_attend(q, k_pool, v_pool, block_tables, slot_ids, positions,
                  k_scale=None, v_scale=None, *, scale=None,
                  kernel_name="paged_verify", tuning=None, layer=None):
    """K-wide speculative verify: q [B, K, H, Dh], positions [B, K] —
    the groups laid flat, so a group of consecutive positions is one
    run of K and ONE block-table walk."""
    B, K, H, Dh = q.shape
    out = _paged_attend_runs(
        q.reshape(B * K, H, Dh), k_pool, v_pool, block_tables,
        jnp.repeat(slot_ids.astype(jnp.int32), K),
        positions.reshape(B * K), k_scale, v_scale, scale=scale,
        kernel_name=kernel_name, tuning=tuning, groups=(B, K),
        layer=layer)
    return out.reshape(B, K, H, Dh)


def decode_attend(q, k_pool, v_pool, block_tables, context_lens,
                  k_scale=None, v_scale=None, *, scale=None):
    """K=1 decode: q [B, H, Dh], one query per slot attending its first
    `context_lens[b]` cached tokens — B runs of 1."""
    B = q.shape[0]
    return _paged_attend_runs(
        q, k_pool, v_pool, block_tables,
        jnp.arange(B, dtype=jnp.int32),
        context_lens.astype(jnp.int32) - 1, k_scale, v_scale,
        scale=scale, kernel_name="paged_decode")


# ----------------------------------------------------------- autotuning


def _synth_paged_inputs(N, G, H, Dh, BS, context_len, dtype, seed):
    """Deterministic synthetic pools/tables/queries for one paged
    shape bucket (the tuner's measurement workload). `dtype` is the
    POOL dtype: int8/float8_e4m3fn build quantized pools with
    per-entry-per-head fp32 scales (the `kv_dtype="int8"`/"fp8_e4m3"
    serving layouts) under fp32 queries; otherwise scales are None."""
    import numpy as np
    rng = np.random.RandomState(seed)
    mb = -(-int(context_len) // BS)
    NB = N * mb + 1
    dtype = np.dtype(dtype)
    quant = dtype.itemsize == 1       # int8 or a scaled fp8 format
    qdt = np.float32 if quant else dtype
    q = jnp.asarray(rng.randn(N, G, H, Dh).astype(qdt))
    if quant:
        if dtype == np.int8:
            kp = jnp.asarray(rng.randint(-127, 128, (NB, BS, H, Dh))
                             .astype(np.int8))
            vp = jnp.asarray(rng.randint(-127, 128, (NB, BS, H, Dh))
                             .astype(np.int8))
        else:
            # fp8: stay inside the e4m3 finite range (casts past 448
            # produce NaN, which would poison the parity oracle)
            kp = jnp.asarray(np.clip(rng.randn(NB, BS, H, Dh) * 100,
                                     -440, 440).astype(np.float32)
                             ).astype(dtype)
            vp = jnp.asarray(np.clip(rng.randn(NB, BS, H, Dh) * 100,
                                     -440, 440).astype(np.float32)
                             ).astype(dtype)
        ks = jnp.asarray((np.abs(rng.randn(NB, BS, H)) * 0.02
                          + 0.005).astype(np.float32))
        vs = jnp.asarray((np.abs(rng.randn(NB, BS, H)) * 0.02
                          + 0.005).astype(np.float32))
    else:
        kp = jnp.asarray(rng.randn(NB, BS, H, Dh).astype(dtype))
        vp = jnp.asarray(rng.randn(NB, BS, H, Dh).astype(dtype))
        ks = vs = None
    bt = jnp.asarray(
        1 + np.arange(N * mb, dtype=np.int32).reshape(N, mb))
    slots = jnp.arange(N, dtype=jnp.int32)
    pos = jnp.asarray(
        np.clip(context_len - 1 - np.arange(G)[::-1], 0,
                context_len - 1).astype(np.int32)[None].repeat(N, 0))
    return q, kp, vp, bt, slots, pos, ks, vs


def tune_paged_kernel(kernel_name, N, G, H, Dh, BS, *,
                      context_len=None, dtype="float32", seed=0,
                      budget_s=None, timer=None, persist=True):
    """Search the `kv_blocks` axis of one paged-attention bucket.

    Candidates run the REAL block-table kernel (interpret mode off-TPU
    — the same plumbing tier-1 parity uses) against the XLA gather
    oracle; the winner lands in the persistent cache under
    `(kernel_name, shape_bucket(N, G, H, Dh, BS), dtype, backend)` so
    the serving engine's next trace picks it up for free."""
    import numpy as np
    from . import flash_attention as fa

    global _INTERPRET
    dtype = np.dtype(dtype)
    context_len = int(context_len or 4 * BS)
    args = _synth_paged_inputs(N, G, H, Dh, BS, context_len,
                               dtype, seed)

    def oracle(q, kp, vp, bt, slots, pos, ks, vs):
        if G == 1:
            return fa.ragged_gather_reference(q[:, 0], kp, vp, bt,
                                              slots, pos[:, 0], ks, vs)
        return fa.verify_gather_reference(q, kp, vp, bt, slots, pos,
                                          ks, vs)

    def build(cfg):
        def run(q, kp, vp, bt, slots, pos, ks, vs):
            out = verify_attend(q, kp, vp, bt, slots, pos, ks, vs,
                                kernel_name=kernel_name, tuning=cfg)
            return out[:, 0] if G == 1 else out
        return run

    was = _INTERPRET
    if not _on_tpu_backend():
        _INTERPRET = True
    try:
        return autotune.search(
            kernel_name, autotune.shape_bucket(N, G, H, Dh, BS), dtype,
            autotune.paged_candidates(), build, args, oracle,
            rtol=2e-2, atol=2e-2, budget_s=budget_s, timer=timer,
            persist=persist,
            meta={"context_len": context_len, "seed": seed})
    finally:
        _INTERPRET = was


def tune_paged_sparse(N, G, H, Dh, BS, B, *, dtype="float32", seed=0,
                      budget_s=None, timer=None, persist=True):
    """Search the `kv_blocks` axis of the BLOCK-SPARSE decode bucket
    (ISSUE 15): the same grouped kernel fed a shortened `[N, B]` block
    table — the table width IS the sparsity budget, so the bucket key
    carries B (`shape_bucket(N, G, H, Dh, BS, B)`) and a tuned dense
    entry can never alias a sparse one. The measurement workload holds
    exactly B resident blocks per slot (context_len = B * BS), which
    is what the serving engine's compacted-position masking reduces
    the sparse region to."""
    import numpy as np
    from . import flash_attention as fa

    global _INTERPRET
    dtype = np.dtype(dtype)
    args = _synth_paged_inputs(N, G, H, Dh, BS, int(B) * BS,
                               dtype, seed)

    def oracle(q, kp, vp, bt, slots, pos, ks, vs):
        if G == 1:
            return fa.ragged_gather_reference(q[:, 0], kp, vp, bt,
                                              slots, pos[:, 0], ks, vs)
        return fa.verify_gather_reference(q, kp, vp, bt, slots, pos,
                                          ks, vs)

    def build(cfg):
        def run(q, kp, vp, bt, slots, pos, ks, vs):
            out = verify_attend(q, kp, vp, bt, slots, pos, ks, vs,
                                kernel_name="paged_sparse", tuning=cfg)
            return out[:, 0] if G == 1 else out
        return run

    was = _INTERPRET
    if not _on_tpu_backend():
        _INTERPRET = True
    try:
        return autotune.search(
            "paged_sparse", autotune.shape_bucket(N, G, H, Dh, BS, B),
            dtype, autotune.paged_candidates(), build, args, oracle,
            rtol=2e-2, atol=2e-2, budget_s=budget_s, timer=timer,
            persist=persist, meta={"sparse_blocks": int(B),
                                   "seed": seed})
    finally:
        _INTERPRET = was


def tune_block_size(max_slots, H, Dh, *, context_len=64,
                    dtype="float32", seed=0, budget_s=None,
                    timer=None, persist=True):
    """Search the ENGINE-level KV block-size axis: each candidate
    re-shapes the pools (`NB = slots * ceil(ctx / BS) + 1`) and times
    decode-shaped ragged attention over them; parity holds per
    candidate against the gather oracle on the candidate's own pools.
    Candidates come from `autotune.paged_block_size_candidates` — the
    SAME alignment predicate as the serve-time dispatch gate, so the
    cached winner is admissible wherever the kernels are
    (`ServingEngine(block_size="auto")` reads the result)."""
    import numpy as np
    from . import flash_attention as fa

    global _INTERPRET
    dtype = np.dtype(dtype)

    def oracle(q, kp, vp, bt, slots, pos, ks, vs):
        return fa.ragged_gather_reference(q[:, 0], kp, vp, bt, slots,
                                          pos[:, 0], ks, vs)

    def build(cfg):
        bs = int(cfg["block_size"])
        cand_args = _synth_paged_inputs(max_slots, 1, H, Dh, bs,
                                        context_len, dtype, seed)

        def run(q, kp, vp, bt, slots, pos, ks, vs):
            if paged_pallas_enabled(Dh, bs):
                return _paged_attend_runs(
                    q[:, 0], kp, vp, bt, slots, pos[:, 0], ks, vs,
                    kernel_name="paged_decode")
            return fa.ragged_gather_reference(q[:, 0], kp, vp, bt,
                                              slots, pos[:, 0], ks, vs)
        return run, cand_args

    was = _INTERPRET
    if not _on_tpu_backend():
        _INTERPRET = True
    try:
        return autotune.search(
            "paged_block_size", autotune.shape_bucket(max_slots, H, Dh),
            dtype,
            autotune.paged_block_size_candidates(Dh, context_len),
            build, None, oracle, rtol=2e-2, atol=2e-2,
            budget_s=budget_s, timer=timer, persist=persist,
            meta={"context_len": int(context_len), "seed": seed})
    finally:
        _INTERPRET = was
