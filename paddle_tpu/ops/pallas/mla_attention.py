"""Multi-head latent attention over a paged LATENT cache, in the
absorbed form (DeepSeek-V2, arXiv:2405.04434, section 2.1): `mla_attend`.

A latent layer caches ONE row a token, `[c (Dv) | k_pe (rope) | zeros]`
in `W` lanes (`serving.kv_cache.PagedKVCache`, kind "latent"), shared by
every query head. With the key up-projection folded into the query
(`qa[h] = [W_UK[h]^T q_nope[h] | q_pe[h]]`, the model's work) and the
value up-projection left for after it, attention is

    o[t, h] = softmax_s( scale * qa[t, h] . row_s ) . row_s[:Dv]

over the keys `s <= position(t)` of the token's slot: one product of
depth W against the cached rows gives the logits, one over the first
Dv lanes of the SAME rows the latent output. The rows are fetched once,
and nothing like a V pool exists.

The kernel walks the runs `paged_attention.paged_runs` makes (a decode
token is a run of 1, a prefill chunk a run of up to `max_run` tokens),
with the run tables and block tables in scalar memory, as
`paged_attention._run_kernel` does: per run, `cdiv(last_pos // BS + 1,
G)` double-buffered fetches of G blocks from the pool in HBM, the next
one flying while the current is attended; a run's query rows (T x H
rows of W lanes, token major) come in once a run, and every q tile of
the run (`tile_tokens` heights: 1 for a decode token) attends each
fetched group with an online softmax in float32 VMEM state. What
differs from the K/V kernel is the size of what a run holds: 64 query
heads of 640 lanes are 80 KB a token, so q and the output stay in HBM
and are copied a run at a time. The output rows of a run go back in
one copy, of the run's tile height class: a decode token's H rows, a
chunk's `max_run` tokens' rows, whose tail overhangs into the rows of
later runs; runs are walked in ascending order and every copy is
waited for, so a later run writes its rows after the overhang. Rows of
no run are zeroed by the wrapper.

`mla_attend_xla` computes the same thing with a gather of every
token's slot: the CPU path, and the kernel's oracle in the tests.

The rows of a long prefill chunk take the EXPANDED form instead
(`mla_expand_attend`): per-head keys `[c . W_UK[h] | k_pe]` and values
`c . W_UV[h]` made from the latent rows in VMEM, once a step for every
row of the slot, at `2 H (Dn + Dr + Dv)` FLOPs a causal pair against
the absorbed `2 H (2 Dc + Dr)`, for `2 Dc H (Dn + Dv)` a context token.
`ExpandRule` says which rows that pays for, from the widths alone;
`mla_expand_attend_xla` is its CPU path and oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import MASK_VALUE, paged_runs, run_tile, tile_tokens

_INTERPRET = False

#: the most keys one fetched group holds
GROUP_KEYS = 256
#: the most float32 logits one q tile's product gives (512 KB)
TILE_LOGITS = 2 ** 17
#: the expanded walk: keys a fetched group, query rows a tile, heads a
#: grid step (whose up-projection weights stay resident)
XGROUP_KEYS, XTILE_ROWS, XHEADS = 1024, 256, 8


def mla_kernel_enabled() -> bool:
    """The Mosaic kernel on a TPU backend (or interpreted, in tests);
    `mla_attend_xla` elsewhere."""
    if _INTERPRET:
        return True
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


def mla_tiles(H, BS, MB, longest):
    """(G, TQ, RMAX) from the shapes: G blocks a fetch (at most
    `GROUP_KEYS` keys, no more than the table holds); TQ tokens the
    tallest q tile, a power of two whose `TQ * H` rows meet the group's
    keys in at most `TILE_LOGITS` logits, and no taller than the
    longest run; RMAX the state rows of the longest run."""
    G = max(1, min(GROUP_KEYS // BS, MB))
    TQ = max(1, min(TILE_LOGITS // (G * BS * H), 128,
                    -(-longest // 8) * 8))
    TQ = 1 << (TQ.bit_length() - 1)
    return G, TQ, -(-longest // TQ) * TQ * H


def _mla_kernel(nruns_ref, rstart_ref, rlen_ref, rslot_ref, rpos_ref,
                bt_ref, rowtok_ref, q_hbm, pool_hbm, o_hbm, qbuf, kbuf,
                sem, iosem, m_ref, l_ref, acc_ref, *, BS, H, G, TQ, RMAX,
                Dv, scale, mxu_dtype):
    """Every run of the step: its query rows in, its slot's pages
    walked once in groups of G blocks, every q tile of it against each
    group, its output rows out. Refs: scalar prefetch (run count,
    start, length, slot, first position; block tables [S, MB]); rowtok
    [TQ * H, 1] int32 (a row's token within its tile); q [T * H + RMAX,
    W], pool [NB, BS, W], out [T * H + RMAX, Dv] float32, all in HBM;
    scratch: the run's query rows, two key buffers, DMA semaphores, the
    run's online-softmax state."""
    GK = G * BS
    n_runs = nruns_ref[0]
    last_run = rstart_ref.shape[0] - 1
    heights = tile_tokens(TQ)
    # a stale row of a buffer meets a probability of 0 in the value
    # product, and 0 x NaN is NaN: the buffers start finite
    kbuf[...] = jnp.zeros_like(kbuf)

    def run_blocks(r):
        return jnp.maximum(rpos_ref[r] + rlen_ref[r] - 1, 0) // BS + 1

    def fetch(buf, slot, g, nblk, wait):
        """Start (or wait for) the copies of group g of `slot`'s pages:
        only the blocks the run reaches; the rest of the buffer keeps
        an older group's rows, which the causal mask hides."""
        def one(j, c):
            cp = pltpu.make_async_copy(
                pool_hbm.at[bt_ref[slot, g * G + j]],
                kbuf.at[buf, pl.ds(j * BS, BS)], sem.at[buf])
            cp.wait() if wait else cp.start()
            return c
        jax.lax.fori_loop(0, jnp.minimum(nblk - g * G, G), one, 0)

    @pl.when(n_runs > 0)
    def _prime():
        fetch(0, rslot_ref[0], 0, run_blocks(0), wait=False)

    def attend(tq, buf, g, first, last, n, pos0):
        """Every q tile (tq tokens, R = tq * H rows) of the run against
        the group in buffer `buf`."""
        R = tq * H
        base = g * GK

        def tile(j, carry):
            off = j * tq
            rs = off * H

            @pl.when(first)
            def _init():
                m_ref[pl.ds(rs, R)] = jnp.full((R, 1), MASK_VALUE,
                                               jnp.float32)
                l_ref[pl.ds(rs, R)] = jnp.zeros((R, 1), jnp.float32)
                acc_ref[pl.ds(rs, R)] = jnp.zeros((R, Dv), jnp.float32)

            # causal skip: the group lies past the tile's last query
            @pl.when(base <= pos0 + off + tq - 1)
            def _accumulate():
                k = kbuf[buf]                                  # [GK, W]
                s = jax.lax.dot_general(
                    qbuf[pl.ds(rs, R)].astype(mxu_dtype),
                    k.astype(mxu_dtype), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                tok = rowtok_ref[0:R] + off                    # [R, 1]
                thr = jnp.where(tok < n, tok + (pos0 - base), -1)
                keep = jax.lax.broadcasted_iota(
                    jnp.int32, (R, GK), 1) <= thr
                s = jnp.where(keep, s, MASK_VALUE)
                m_old = m_ref[pl.ds(rs, R)]
                m_new = jnp.maximum(m_old, jnp.max(s, axis=-1,
                                                   keepdims=True))
                alpha = jnp.exp(m_old - m_new)
                # rows past the run's end keep no key: zero, not exp(0)
                p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
                l_ref[pl.ds(rs, R)] = l_ref[pl.ds(rs, R)] * alpha \
                    + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[pl.ds(rs, R)] = acc_ref[pl.ds(rs, R)] * alpha \
                    + jax.lax.dot_general(
                        p.astype(mxu_dtype),
                        kbuf[buf, :, pl.ds(0, Dv)].astype(mxu_dtype),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_ref[pl.ds(rs, R)] = m_new

            @pl.when(last)
            def _finalize():
                acc_ref[pl.ds(rs, R)] = acc_ref[pl.ds(rs, R)] / jnp.maximum(
                    l_ref[pl.ds(rs, R)], 1e-30)
            return carry

        jax.lax.fori_loop(0, (n + tq - 1) // tq, tile, 0)

    def run_body(r, it):
        start, n = rstart_ref[r], rlen_ref[r]
        slot, pos0 = rslot_ref[r], rpos_ref[r]
        nblk = run_blocks(r)
        ngroups = (nblk + G - 1) // G
        nxt = jnp.minimum(r + 1, last_run)
        more_runs = r + 1 < n_runs
        # a run's query and output copies move a decode token's H rows or
        # the state's RMAX: a static size each, chosen at run time
        sizes = ((H, n == 1), (RMAX, n > 1))
        for rows, cond in sizes:
            @pl.when(cond)
            def _q_in(rows=rows):
                cp = pltpu.make_async_copy(
                    q_hbm.at[pl.ds(start * H, rows)],
                    qbuf.at[pl.ds(0, rows)], iosem.at[0])
                cp.start()
                cp.wait()

        def group_body(g, it):
            buf = it % 2
            fetch(buf, slot, g, nblk, wait=True)
            last = g == ngroups - 1

            # the next item's fetch flies while this group is attended
            @pl.when(jnp.logical_not(last))
            def _next_group():
                fetch(1 - buf, slot, g + 1, nblk, wait=False)

            @pl.when(last & more_runs)
            def _next_run():
                fetch(1 - buf, rslot_ref[nxt], 0, run_blocks(nxt),
                      wait=False)

            # one body a tile height: the run's is the first that holds it
            for lo, tq in zip((0,) + heights, heights):
                fits = n > lo
                if tq != TQ:
                    fits &= n <= tq

                @pl.when(fits)
                def _(tq=tq):
                    attend(tq, buf, g, g == 0, last, n, pos0)
            return it + 1

        it = jax.lax.fori_loop(0, ngroups, group_body, it)
        for rows, cond in sizes:
            @pl.when(cond)
            def _out(rows=rows):
                cp = pltpu.make_async_copy(
                    acc_ref.at[pl.ds(0, rows)],
                    o_hbm.at[pl.ds(start * H, rows)], iosem.at[1])
                cp.start()
                cp.wait()
        return it

    jax.lax.fori_loop(0, n_runs, run_body, 0)


def mla_attend(q, pool, block_tables, slot_ids, positions, *, scale,
               value_dim, runs=None, max_run=None):
    """Absorbed latent attention of one step's flat tokens.

    q [T, H, W] absorbed query rows in the pool's lanes (`[W_UK^T q_nope
    | q_pe | zeros]`); pool [NB, BS, W] the layer's latent rows;
    block_tables [S, MB]; slot_ids [T] (-1: padding); positions [T];
    `scale` the softmax scale; `value_dim` Dv the lanes that are the
    value (the latent c). `runs` a precomputed `paged_runs(slot_ids,
    positions, max_run)`. -> [T, H, Dv] in q's dtype, padding rows
    zero. On a TPU backend the Mosaic kernel named `mla_attend`, else
    `mla_attend_xla`."""
    if not mla_kernel_enabled():
        return mla_attend_xla(q, pool, block_tables, slot_ids, positions,
                              scale=scale, value_dim=value_dim)
    T, H, W = q.shape
    NB, BS, _ = pool.shape
    S, MB = block_tables.shape
    Dv = int(value_dim)
    longest = T if max_run is None else min(int(max_run), T)
    if runs is None:
        runs = paged_runs(slot_ids, positions, max_run)
    G, TQ, RMAX = mla_tiles(H, BS, MB, longest)
    mxu_dtype = (jnp.bfloat16 if q.dtype == pool.dtype == jnp.bfloat16
                 else jnp.float32)
    q2 = jnp.pad(q.astype(pool.dtype).reshape(T * H, W),
                 ((0, RMAX), (0, 0)))
    rowtok = (jnp.arange(TQ * H, dtype=jnp.int32) // H)[:, None]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.VMEM((RMAX, W), pool.dtype),
               pltpu.VMEM((2, G * BS, W), pool.dtype),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.VMEM((RMAX, 1), jnp.float32),
               pltpu.VMEM((RMAX, 1), jnp.float32),
               pltpu.VMEM((RMAX, Dv), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(), in_specs=[vmem, hbm, hbm],
        out_specs=hbm, scratch_shapes=scratch)
    kernel = functools.partial(
        _mla_kernel, BS=BS, H=H, G=G, TQ=TQ, RMAX=RMAX, Dv=Dv,
        scale=float(scale), mxu_dtype=mxu_dtype)
    # a mean slot's share of the pool, read once a run
    ctx = min(min(NB, S * MB) * BS // max(S, 1) + 1, MB * BS)
    item = pool.dtype.itemsize
    resident = (RMAX * (W * item + 2 * 128 * 4 + Dv * 4)
                + 2 * G * BS * W * item)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T * H + RMAX, Dv), jnp.float32),
        interpret=_INTERPRET, name="mla_attend",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(min(110 * 2 ** 20,
                                     resident + 24 * 2 ** 20))),
        cost_estimate=pl.CostEstimate(
            flops=2 * T * H * (W + Dv) * ctx,
            bytes_accessed=(S * ctx * W * item
                            + T * H * (W * item + Dv * 4)),
            transcendentals=T * H * ctx),
    )(*runs, block_tables.astype(jnp.int32), rowtok, q2, pool)
    valid = (slot_ids >= 0)[:, None, None]
    return jnp.where(valid, out[:T * H].reshape(T, H, Dv), 0).astype(
        q.dtype)


def mla_attend_xla(q, pool, block_tables, slot_ids, positions, *, scale,
                   value_dim):
    """`mla_attend` in `jax.numpy`: every token gathers its slot's whole
    table of rows and attends the positions up to its own. Logits and
    sums in float32, the products' operands in q's dtype."""
    T, H, W = q.shape
    valid = slot_ids >= 0
    rows = pool[block_tables[jnp.where(valid, slot_ids, 0)]]
    rows = rows.reshape(T, -1, W).astype(q.dtype)              # [T, C, W]
    s = jnp.einsum("thw,tcw->thc", q, rows,
                   preferred_element_type=jnp.float32) * scale
    keys = jnp.arange(rows.shape[1], dtype=jnp.int32)
    keep = (keys[None, :] <= positions[:, None]) & valid[:, None]
    s = jnp.where(keep[:, None, :], s, MASK_VALUE)
    p = jnp.where(keep[:, None, :], jnp.exp(
        s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("thc,tcv->thv", p.astype(q.dtype),
                     rows[..., :value_dim],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class ExpandRule:
    """Which rows of a step attend in the expanded form. The absorbed
    form costs a walk of n rows over P causal pairs `P 2H (2 Dc + Dr) +
    n 2H Dc (Dn + Dv)` FLOPs, the expanded one `P 2H (Dn + Dr + Dv) + C
    2H Dc (Dn + Dv)` for its context of C tokens: from `Dc (Dn + Dv) //
    (2 Dc - Dn - Dv) + 1` rows a walk the expanded form is the cheaper
    at any context (171 at A.X-K1's widths), and the more rows share
    the up-projection the more it gains. A row's form must not depend
    on what else its step holds (a request served alone and in company
    gives the same tokens, bit for bit), so the rule is taken per BLOCK
    of `rows` positions from position 0: a slot's rows of a whole block
    go expanded, those of a part block (a prompt's last, a decode
    token) absorbed; the engine cuts prefill chunks into whole blocks
    (`prefill_align`, `batcher.fill_chunk`), so a block comes in one
    step, alone as in company. At most `entries` walks a step."""
    rows: int
    entries: int

    def walks(self, pos, n):
        """A slot's group of n rows from `pos`, as the step cuts it:
        [(first position, rows, expanded)], adjacent rows of one form
        merged."""
        segs = []
        for p in range(pos, pos + n):
            b = p - p % self.rows
            x = b >= pos and b + self.rows <= pos + n
            if segs and segs[-1][2] == x:
                segs[-1][1] += 1
            else:
                segs.append([p, 1, x])
        return [tuple(x) for x in segs]

    def table(self, slot_ids, positions):
        """The step's walks on the device: ((start, rows, slot, first
        position) [entries] int32 each, in ascending start, unused
        entries 0 rows at the end; [T] bool the rows they hold)."""
        T = slot_ids.shape[0]
        t = jnp.arange(T, dtype=jnp.int32)
        # the rows of one slot in one block: a run of its own
        blocks = jnp.where(slot_ids >= 0, slot_ids * 2 ** 20
                           + positions // self.rows, -1)
        _, start, length, _, _ = paged_runs(blocks, positions)
        whole = length == self.rows
        rows = jnp.any(whole[None, :] & (t[:, None] >= start[None, :])
                       & (t[:, None] < (start + length)[None, :]), axis=1)
        _, start, length, slot, first = paged_runs(
            jnp.where(rows, slot_ids, -1), positions)
        tab = tuple(a[:self.entries].astype(jnp.int32)
                    for a in (jnp.where(length > 0, start, 0), length, slot,
                              first))
        return tab, rows


def expand_rule(latent_dim, nope_dim, value_dim, token_budget, max_slots):
    """The `ExpandRule` of a latent layer of these widths in a step of
    `token_budget` tokens beside at most `max_slots` decode rows: as
    many blocks as that room holds of the rows from which the expanded
    form gains, each made as large as the room allows, in whole q tiles
    of 16 rows where that keeps it above the least (240 at A.X-K1's
    widths, budget 512 and 16 slots: two blocks feed 480 rows). None
    where the expanded form never gains or no block fits."""
    pair = 2 * latent_dim - nope_dim - value_dim
    room = token_budget - max_slots
    if pair <= 0:
        return None
    least = latent_dim * (nope_dim + value_dim) // pair + 1
    if room < least:
        return None
    rows = room // (room // least)
    if rows // 16 * 16 >= least:
        rows = rows // 16 * 16
    return ExpandRule(rows, token_budget // rows)


def mla_expand_tiles(BS, MB, T):
    """(G, TQ, RMAX) of the expanded walk: G blocks a fetch (at most
    `XGROUP_KEYS` keys), TQ query rows a tile (whole bf16 tiles, at
    most `XTILE_ROWS`), RMAX the rows an entry holds (T in tiles)."""
    G = max(1, min(XGROUP_KEYS // BS, MB))
    TQ = min(XTILE_ROWS, -(-T // 16) * 16)
    return G, TQ, -(-T // TQ) * TQ


def _expand_kernel(rows_ref, slot_ref, pos_ref, bt_ref, w_ref, q_hbm,
                   pool_hbm, o_hbm, qbuf, kbuf, kvs, m_ref, l_ref, acc_ref,
                   obuf, sem, iosem, *, BS, G, TQ, RMAX, hg, Dc, Dn, scale,
                   mxu_dtype):
    """Grid step (head group j, entry e): the entry's rows of the head
    group's queries in, its slot's latent rows walked once in groups of
    G blocks, each group up-projected into the group's keys and values
    of the hg heads (`w_ref`, the heads' columns of kv_b, resident
    while j holds), every (head, q tile) against them with an online
    softmax, the head outputs out. Refs: scalar prefetch (entry rows,
    slot, first position; block tables [S, MB]); w [Dc, hg (Dn +
    Dv)] VMEM; q [E, H, RMAX, Dq], pool [NB, BS, W], out [E, H, RMAX,
    Dv], all in HBM, an entry's rows from its first; scratch: the
    queries, two latent buffers, the up-projected keys and values, the
    online-softmax state, the outputs, DMA semaphores."""
    j, e = pl.program_id(0), pl.program_id(1)
    GK, D2 = G * BS, w_ref.shape[1] // hg
    n, slot, pos0 = rows_ref[e], slot_ref[e], pos_ref[e]
    nblk = (pos0 + n - 1) // BS + 1
    ngroups = (nblk + G - 1) // G
    ntiles = (n + TQ - 1) // TQ
    heads = pl.ds(j * hg, hg)

    # a stale row of a buffer meets a probability of 0: it starts finite
    @pl.when((j == 0) & (e == 0))
    def _zero():
        kbuf[...] = jnp.zeros_like(kbuf)

    def fetch(buf, g, wait):
        def one(i, c):
            cp = pltpu.make_async_copy(
                pool_hbm.at[bt_ref[slot, g * G + i]],
                kbuf.at[buf, pl.ds(i * BS, BS)], sem.at[buf])
            cp.wait() if wait else cp.start()
            return c
        jax.lax.fori_loop(0, jnp.minimum(nblk - g * G, G), one, 0)

    def accumulate(h, off, buf, keep):
        """Tile `off` of head h against the group in `buf`; `keep` the
        tile's causal mask over the group (None: every key is seen)."""
        rows = pl.ds(off, TQ)
        q = qbuf[h, rows]                                   # [TQ, Dq]
        kv = kvs[h]                                         # [GK, D2]
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q[:, :Dn].astype(mxu_dtype),
                                 kv[:, :Dn].astype(mxu_dtype), nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(
                 q[:, Dn:].astype(mxu_dtype),
                 kbuf[buf, :, Dc:].astype(mxu_dtype), nt,
                 preferred_element_type=jnp.float32)) * scale
        if keep is not None:
            s = jnp.where(keep, s, MASK_VALUE)
        m_old = m_ref[h, rows]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        if keep is not None:
            # a row that keeps no key of the group: zero, not exp(0)
            p = jnp.where(keep, p, 0.0)
        l_ref[h, rows] = l_ref[h, rows] * alpha + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_ref[h, rows] = acc_ref[h, rows] * alpha + jax.lax.dot_general(
            p.astype(mxu_dtype), kv[:, Dn:].astype(mxu_dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h, rows] = m_new

    @pl.when(n > 0)
    def _walk():
        q_in = pltpu.make_async_copy(q_hbm.at[e, heads], qbuf, iosem.at[0])
        q_in.start()
        fetch(0, 0, wait=False)
        m_ref[...] = jnp.full(m_ref.shape, MASK_VALUE, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        q_in.wait()

        def group(g, carry):
            buf = g % 2
            fetch(buf, g, wait=True)

            # the next group's fetch flies while this one is attended
            @pl.when(g + 1 < ngroups)
            def _next():
                fetch(1 - buf, g + 1, wait=False)

            c = kbuf[buf, :, :Dc].astype(mxu_dtype)
            for h in range(hg):
                kvs[h] = jax.lax.dot_general(
                    c, w_ref[:, h * D2:(h + 1) * D2].astype(mxu_dtype),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(kvs.dtype)
            base = g * GK

            def tile(t, carry):
                off = pl.multiple_of(t * TQ, TQ)
                first = pos0 + off          # the tile's first row's position

                def attend_heads(keep):
                    def head(h, c):
                        accumulate(h, off, buf, keep)
                        return c
                    jax.lax.fori_loop(0, hg, head, 0)

                @pl.when(base + GK - 1 <= first)
                def _seen():
                    attend_heads(None)

                # causal skip: the group past the tile's last row
                @pl.when((base + GK - 1 > first) & (base <= first + TQ - 1))
                def _cut():
                    attend_heads(jax.lax.broadcasted_iota(
                        jnp.int32, (TQ, GK), 1) <= jax.lax.broadcasted_iota(
                            jnp.int32, (TQ, GK), 0) + (first - base))
                return carry
            return jax.lax.fori_loop(0, ntiles, tile, carry)

        jax.lax.fori_loop(0, ngroups, group, 0)
        obuf[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            obuf.dtype)
        out = pltpu.make_async_copy(obuf, o_hbm.at[e, heads], iosem.at[1])
        out.start()
        out.wait()


def mla_expand_attend(q_nope, q_pe, pool, w_kv, block_tables, table, *,
                      scale):
    """Latent attention of the rows of `table` in the EXPANDED form.

    q_nope [T, H, Dn], q_pe [T, H, Dr] (rotated) the step's query heads;
    pool [NB, BS, W] the layer's latent rows `[c (Dc) | k_pe (Dr) |
    zeros]`; w_kv [Dc, H (Dn + Dv)] the layer's kv_b (a head's W_UK
    columns, then its W_UV); block_tables [S, MB]; `table` an
    `ExpandRule.table`'s (start, rows, slot, first position), each entry
    a run of consecutive rows of one slot. A row of an entry attends
    its slot's keys up to its own position: `o[t, h] = softmax_s(scale
    [q_nope | q_pe] . [c_s W_UK[h] | k_pe_s]) . c_s W_UV[h]`, logits and
    sums in float32, the keys and values in the pool's dtype. -> [T, H,
    Dv] in q's dtype; a row of no entry is the caller's to ignore. On a
    TPU backend the Mosaic kernel named `mla_expand_attend`, grid (head
    group, entry), an entry's rows gathered from its first into tiles
    of their own, else `mla_expand_attend_xla`."""
    if not mla_kernel_enabled():
        return mla_expand_attend_xla(q_nope, q_pe, pool, w_kv, block_tables,
                                     table, scale=scale)
    T, H, Dn = q_nope.shape
    NB, BS, W = pool.shape
    S, MB = block_tables.shape
    Dc, E = w_kv.shape[0], table[0].shape[0]
    D2 = w_kv.shape[1] // H
    Dv, Dq = D2 - Dn, Dn + W - Dc
    G, TQ, RMAX = mla_expand_tiles(BS, MB, T)
    hg = math.gcd(H, XHEADS)
    mxu_dtype = (jnp.bfloat16 if q_nope.dtype == pool.dtype == jnp.bfloat16
                 else jnp.float32)
    start, rows = table[0], table[1]
    # [q_nope | q_pe | zeros] a head, over the pool's [c | k_pe | zeros]
    # lanes past Dc; an entry's RMAX rows from its first, head major
    at = jnp.minimum(start[:, None] + jnp.arange(RMAX, dtype=jnp.int32),
                     T - 1)
    qx = jnp.concatenate(
        [q_nope, q_pe, jnp.zeros((T, H, Dq - Dn - q_pe.shape[-1]),
                                 q_pe.dtype)], -1).astype(pool.dtype)
    qx = qx[at].transpose(0, 2, 1, 3)                  # [E, H, RMAX, Dq]
    f32 = jnp.float32
    scratch = [pltpu.VMEM((hg, RMAX, Dq), pool.dtype),
               pltpu.VMEM((2, G * BS, W), pool.dtype),
               pltpu.VMEM((hg, G * BS, D2), pool.dtype),
               pltpu.VMEM((hg, RMAX, 1), f32),
               pltpu.VMEM((hg, RMAX, 1), f32),
               pltpu.VMEM((hg, RMAX, Dv), f32),
               pltpu.VMEM((hg, RMAX, Dv), q_nope.dtype),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.SemaphoreType.DMA((2,))]
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(H // hg, E),
        in_specs=[pl.BlockSpec((Dc, hg * D2), lambda j, e, *_: (0, j)),
                  hbm, hbm],
        out_specs=hbm, scratch_shapes=scratch)
    kernel = functools.partial(
        _expand_kernel, BS=BS, G=G, TQ=TQ, RMAX=RMAX, hg=hg, Dc=Dc, Dn=Dn,
        scale=float(scale), mxu_dtype=mxu_dtype)
    item = pool.dtype.itemsize
    GK = G * BS
    resident = (hg * RMAX * (Dq * item + 2 * 128 * 4 + Dv * (4 + item))
                + GK * (2 * W + hg * D2) * item
                + 2 * Dc * hg * D2 * w_kv.dtype.itemsize
                + 3 * TQ * GK * 4)
    # the entries' mean context, a table's width at most, read once a
    # head group; the pairs of T rows over it
    ctx = min(min(NB, S * MB) * BS // max(S, 1) + T, MB * BS)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, H, RMAX, Dv), q_nope.dtype),
        interpret=_INTERPRET, name="mla_expand_attend",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(110 * 2 ** 20,
                                     resident + 24 * 2 ** 20))),
        cost_estimate=pl.CostEstimate(
            flops=2 * H * (T * (Dq + Dv) + E * Dc * D2) * ctx,
            bytes_accessed=(E * (H // hg) * ctx * W * item
                            + E * H * RMAX * (Dq + Dv) * item
                            + Dc * H * D2 * w_kv.dtype.itemsize),
            transcendentals=T * H * ctx),
    )(*table[1:], block_tables.astype(jnp.int32), w_kv, qx, pool)
    # a row's entry (the first where it is in none) and its row there
    t = jnp.arange(T, dtype=jnp.int32)[:, None]
    mine = (t >= start) & (t < start + rows)                   # [T, E]
    e = jnp.argmax(mine, axis=1)
    r = jnp.clip(t[:, 0] - start[e], 0, RMAX - 1)
    return out[e, :, r]


def mla_expand_attend_xla(q_nope, q_pe, pool, w_kv, block_tables, table,
                          *, scale):
    """`mla_expand_attend` in `jax.numpy`: an entry's slot's whole table
    of rows gathered and up-projected, every row of the step attending
    it under the entry's rows and the causal rule. Rows of no entry are
    zero."""
    T, H, Dn = q_nope.shape
    W = pool.shape[-1]
    Dc = w_kv.shape[0]
    Dr = q_pe.shape[-1]
    t = jnp.arange(T, dtype=jnp.int32)
    kv_w = w_kv.reshape(Dc, H, -1)
    out = jnp.zeros((T, H, kv_w.shape[-1] - Dn), q_nope.dtype)
    for start, rows, slot, pos0 in zip(*table):
        ctx = pool[block_tables[slot]].reshape(-1, W)          # [C, W]
        kv = jnp.einsum("sc,chd->shd", ctx[:, :Dc].astype(q_nope.dtype),
                        kv_w.astype(q_nope.dtype),
                        preferred_element_type=jnp.float32).astype(
                            pool.dtype).astype(q_nope.dtype)
        s = (jnp.einsum("thn,shn->hts", q_nope, kv[..., :Dn],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("thr,sr->hts", q_pe,
                          ctx[:, Dc:Dc + Dr].astype(q_pe.dtype),
                          preferred_element_type=jnp.float32)) * scale
        keys = jnp.arange(ctx.shape[0], dtype=jnp.int32)
        mine = (t >= start) & (t < start + rows)
        keep = keys[None, :] <= (pos0 + t - start)[:, None]
        s = jnp.where(keep[None], s, MASK_VALUE)
        p = jnp.where(keep[None], jnp.exp(
            s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("hts,shv->thv", p.astype(q_nope.dtype), kv[..., Dn:],
                       preferred_element_type=jnp.float32)
        out = jnp.where(mine[:, None, None], o.astype(out.dtype), out)
    return out


def mla_work(runs, H, block_size, MB, max_run, expand=None, budget=None):
    """What the kernels do for `runs`, (first position, tokens) pairs of
    a step's slots, as flight-record counts of ONE layer. The rows
    `expand` (an `ExpandRule`) gives the expanded form are one walk of
    `mla_expand_attend` a slot; the rest are cut at `max_run` as the
    step cuts them for `mla_attend`. `mla_rows_decode` /
    `mla_rows_chunk` (rows of one-token runs / of longer ones),
    `mla_pairs_decode` / `mla_pairs_chunk` (causal (query, key) pairs of
    each), `mla_tokens_read` (the context a walk must read, once a walk:
    its last position + 1; an expanded walk is one), `mla_logits_issued`
    (logits the kernels' products give by their own tiles: a q tile of
    `run_tile` tokens x H rows, or of the expanded walk's TQ rows a
    head, meets every fetched group of keys that does not lie past its
    last query), `mla_rows_expanded` (the rows of the expanded walks).
    `budget`: the step's token budget, which sizes the expanded walk's
    tiles."""
    G, TQ, _ = mla_tiles(H, block_size, MB, max_run)
    GK = G * block_size
    out = dict(mla_rows_decode=0, mla_rows_chunk=0, mla_pairs_decode=0,
               mla_pairs_chunk=0, mla_tokens_read=0, mla_logits_issued=0,
               mla_rows_expanded=0)
    if expand is not None:
        XG, XTQ, _ = mla_expand_tiles(block_size, MB, budget)
        XGK = XG * block_size
    segments = [s for pos, n in runs
                for s in (expand.walks(pos, n) if expand is not None
                          else [(pos, n, False)])]
    for pos, n, expanded in segments:
        if expanded:
            out["mla_rows_chunk"] += n
            out["mla_rows_expanded"] += n
            out["mla_pairs_chunk"] += n * pos + n * (n + 1) // 2
            out["mla_tokens_read"] += pos + n
            last = (pos + n - 1) // XGK
            for off in range(0, n, XTQ):
                out["mla_logits_issued"] += (min(
                    last, (pos + off + XTQ - 1) // XGK) + 1) * XTQ * H * XGK
            continue
        for cut in range(0, n, max_run):
            p0, m = pos + cut, min(max_run, n - cut)
            kind = "decode" if m == 1 else "chunk"
            out[f"mla_rows_{kind}"] += m
            out[f"mla_pairs_{kind}"] += m * p0 + m * (m + 1) // 2
            out["mla_tokens_read"] += p0 + m
            tq = run_tile(m, TQ)
            last = (p0 + m - 1) // block_size // G    # the walk's last group
            for off in range(0, m, tq):
                out["mla_logits_issued"] += (min(
                    last, (p0 + off + tq - 1) // GK) + 1) * tq * H * GK
    return out
