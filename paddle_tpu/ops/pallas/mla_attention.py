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
"""
from __future__ import annotations

import functools

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


def mla_work(runs, H, block_size, MB, max_run):
    """What the kernel does for `runs`, (first position, tokens) pairs,
    cut at `max_run` as the step cuts them, as flight-record counts of
    ONE layer: `mla_rows_decode` / `mla_rows_chunk` (rows of one-token
    runs / of longer ones), `mla_pairs_decode` / `mla_pairs_chunk`
    (causal (query, key) pairs of each), `mla_tokens_read` (the context
    a run's walk must read, once a run: its last position + 1),
    `mla_logits_issued` (logits the kernel's products give by its own
    tiles: a q tile of `run_tile` tokens x H rows meets every fetched
    group of `G * BS` keys that does not lie past its last query)."""
    G, TQ, _ = mla_tiles(H, block_size, MB, max_run)
    GK = G * block_size
    out = dict(mla_rows_decode=0, mla_rows_chunk=0, mla_pairs_decode=0,
               mla_pairs_chunk=0, mla_tokens_read=0, mla_logits_issued=0)
    for pos, n in runs:
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
