"""Tile validation of the Pallas kernel families against their XLA
oracles, on the real device.

Tier-1 proves every Pallas kernel in INTERPRET mode on the CPU mesh —
the real scalar-prefetch/block-table plumbing, but not the real Mosaic
tiling. This tool is the device run: it replays the paged-attention
family (ragged / verify / decode / sparse short-table; fp32, bf16, int8
and fp8 pools; the same entries on pools stacked over layers, read in
place; the block-causal rule of a model that decodes by blocks), fused add+LayerNorm and splash attention (forward and
backward), the hand flash-forward kernel, the grouped-expert matmul
(fp32 / int8 / int4 weights) and the ragged chunked delta rule (a key
dim of 96) against their pure-XLA oracles with
interpret mode OFF. A
kernel that compiles and is wrong fails here.

Every `validate_*` takes its shapes as arguments and returns one
record per cell, so `chip_smoke.py` runs the same bodies at the serving
engine's and the trainer's real tile shapes; `main()` runs the matrix
below at small hardware-aligned shapes.

    python tools/tpu_tile_validate.py              # on a TPU host
    python tools/tpu_tile_validate.py --rehearse   # CPU, interpret mode

Without `--rehearse` a backend that is not a TPU is an error (exit 1):
nothing was validated, and saying "skipped, 0" would read as a pass.
Any parity failure exits 1 naming the (kernel, dtype, shape) cell.
"""
from __future__ import annotations

import argparse
import os
import sys
import typing

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Cell(typing.NamedTuple):
    """One (kernel, dtype, shape) comparison against its oracle."""
    name: str
    ok: bool
    max_err: float

    def __str__(self):
        return (f"{'PASS' if self.ok else 'FAIL'} {self.name} "
                f"max|err|={self.max_err:.3g}")


def _cell(name, out, ref, rtol, atol):
    """Compare `out` with `ref`. `atol` is relative to the size of the
    reference, max(1, max|ref|): the kernels' fp32 matmuls run as bf16
    passes on the MXU, so their absolute error grows with the operands
    (first chip run, PR 21: fp8 pools, outputs up to ~5, erred by 0.045
    against an exact oracle — precision, not a wrong tile)."""
    import jax
    import numpy as np
    outs = [np.asarray(o, np.float64) for o in jax.tree.leaves(out)]
    refs = [np.asarray(r, np.float64) for r in jax.tree.leaves(ref)]
    ok = len(outs) == len(refs)
    err = 0.0
    for o, r in zip(outs, refs):
        if o.shape != r.shape:
            ok = False
            continue
        err = max(err, float(np.max(np.abs(o - r))) if o.size else 0.0)
        scale = max(1.0, float(np.max(np.abs(r)))) if r.size else 1.0
        ok = ok and bool(np.allclose(o, r, rtol=rtol, atol=atol * scale))
    return Cell(name, ok, err)


def _exact(fn):
    """Run an XLA oracle with exact matmuls: the TPU's default matmul
    precision rounds fp32 operands to bf16, and the oracle's error must
    not be charged to the kernel."""
    import jax

    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


def _ragged_positions(n, g, width, BS, seed):
    """[n, g] int32 positions: group i's first query sits anywhere in a
    `width`-block table, the rest of its window follows, and group 0
    fills the table."""
    import numpy as np

    import jax.numpy as jnp
    first = np.random.RandomState(seed).randint(
        0, width * BS - g + 1, size=n)
    first[0] = width * BS - g
    return jnp.asarray((first[:, None] + np.arange(g)[None, :])
                       .astype(np.int32))


def validate_paged(*, H=8, Dh=128, BS=16, max_blocks=4, ragged_n=4,
                   slots=4, verify_width=3, sparse_blocks=3,
                   dtypes=("float32", "bfloat16", "int8",
                           "float8_e4m3fn")):
    """Every paged entry x pool dtype. `ragged_n` flat tokens ride the
    ragged entry, `slots` query groups the verify (`verify_width` wide)
    and decode entries, all over `[slots|ragged_n, max_blocks]` block
    tables with RAGGED per-group context lengths, so the kernel's
    block-skipping and position mask are both exercised.
    `sparse_blocks=0` leaves out the short-table entry. H = 8 is the
    fewest heads whose int8 / fp8 scales fill a lane tile at BS = 16
    (`paged_pallas_enabled`)."""
    import numpy as np

    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    ragged_ref = _exact(fa.ragged_gather_reference)
    verify_ref = _exact(fa.verify_gather_reference)
    cells = []

    def inputs(dtype, n, g, width, seed):
        q, kp, vp, bt, slot_ids, _, ks, vs = pa._synth_paged_inputs(
            n, g, H, Dh, BS, width * BS, np.dtype(dtype), seed=seed)
        pos = _ragged_positions(n, g, width, BS, seed)
        return q, kp, vp, bt, slot_ids, pos, ks, vs

    for dtype in dtypes:
        tol = 5e-2 if dtype == "bfloat16" else 2e-2
        shape = f"H={H} Dh={Dh} BS={BS} MB={max_blocks}"
        q, kp, vp, bt, sl, pos, ks, vs = inputs(dtype, ragged_n, 1,
                                                max_blocks, 3)
        cells.append(_cell(
            f"paged_ragged {dtype} N={ragged_n} {shape}",
            pa.ragged_attend(q[:, 0], kp, vp, bt, sl, pos[:, 0], ks, vs),
            ragged_ref(q[:, 0], kp, vp, bt, sl, pos[:, 0], ks, vs),
            tol, tol))
        q, kp, vp, bt, sl, pos, ks, vs = inputs(dtype, slots, 1,
                                                max_blocks, 4)
        cells.append(_cell(
            f"paged_decode {dtype} N={slots} {shape}",
            pa.decode_attend(q[:, 0], kp, vp, bt, pos[:, 0] + 1, ks, vs),
            ragged_ref(q[:, 0], kp, vp, bt, sl, pos[:, 0], ks, vs),
            tol, tol))
        q, kp, vp, bt, sl, pos, ks, vs = inputs(dtype, slots,
                                                verify_width,
                                                max_blocks, 5)
        cells.append(_cell(
            f"paged_verify {dtype} N={slots} G={verify_width} {shape}",
            pa.verify_attend(q, kp, vp, bt, sl, pos, ks, vs),
            verify_ref(q, kp, vp, bt, sl, pos, ks, vs), tol, tol))
        if sparse_blocks:
            # sparse short-table entry: same kernel, B-wide tables
            q, kp, vp, bt, sl, pos, ks, vs = inputs(dtype, slots, 1,
                                                    sparse_blocks, 6)
            cells.append(_cell(
                f"paged_sparse {dtype} N={slots} B={sparse_blocks} "
                f"H={H} Dh={Dh} BS={BS}",
                pa.ragged_attend(q[:, 0], kp, vp, bt, sl, pos[:, 0], ks,
                                 vs, kernel_name="paged_sparse"),
                ragged_ref(q[:, 0], kp, vp, bt, sl, pos[:, 0], ks, vs),
                tol, tol))
    return cells


def validate_paged_stacked(*, L=3, layer=2, H=16, Dh=128, BS=16,
                           max_blocks=128, slots=8, verify_width=3,
                           sparse_blocks=3,
                           dtypes=("bfloat16", "int8", "float8_e4m3fn")):
    """The paged entries on pools STACKED over `L` layers (scales too),
    reading layer `layer`'s blocks in place (`pa.layer_blocks`) — the
    form the GPT mixed step's layer scan calls — against the oracle on
    the slice `pool[layer]`, at the GPT serve cells' tiles (16 heads x
    128, block 16, 128-block tables). Every layer holds different
    contents, so a block fetched from another layer fails."""
    import numpy as np

    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    ragged_ref = _exact(fa.ragged_gather_reference)
    verify_ref = _exact(fa.verify_gather_reference)
    cells = []

    def inputs(dtype, g, width, seed):
        """q, the STACKED (kp, vp, ks, vs), table, slots, positions."""
        layers = [pa._synth_paged_inputs(slots, g, H, Dh, BS, width * BS,
                                         np.dtype(dtype), seed=seed + i)
                  for i in range(L)]
        q, _, _, bt, sl, _, _, _ = layers[layer]
        stacked = [None if layers[0][i] is None
                   else jnp.stack([lay[i] for lay in layers])
                   for i in (1, 2, 6, 7)]
        return q, stacked, bt, sl, _ragged_positions(slots, g, width, BS,
                                                     seed)

    shape = f"L={L} layer={layer} H={H} Dh={Dh} BS={BS}"
    entries = (("paged_ragged", 1, max_blocks, f"MB={max_blocks}"),
               ("paged_verify", verify_width, max_blocks,
                f"G={verify_width} MB={max_blocks}"),
               ("paged_sparse", 1, sparse_blocks, f"B={sparse_blocks}"))
    for dtype in dtypes:
        tol = 5e-2 if dtype == "bfloat16" else 2e-2
        for seed, (name, g, width, what) in enumerate(entries, 13):
            q, (kp, vp, ks, vs), bt, sl, pos = inputs(dtype, g, width,
                                                      seed)
            kp1, vp1, ks1, vs1 = (None if p is None else p[layer]
                                  for p in (kp, vp, ks, vs))
            if name == "paged_verify":
                got = pa.verify_attend(q, kp, vp, bt, sl, pos, ks, vs,
                                       layer=layer)
                want = verify_ref(q, kp1, vp1, bt, sl, pos, ks1, vs1)
            else:
                got = pa.ragged_attend(q[:, 0], kp, vp, bt, sl, pos[:, 0],
                                       ks, vs, kernel_name=name,
                                       layer=layer)
                want = ragged_ref(q[:, 0], kp1, vp1, bt, sl, pos[:, 0],
                                  ks1, vs1)
            cells.append(_cell(
                f"{name} stacked {dtype} N={slots} {shape} {what}", got,
                want, tol, tol))
    return cells


def validate_paged_gqa_window(*, H=8, Gq=6, Dh=128, BS=16, max_blocks=24,
                              window=128, chunk=40,
                              dtypes=("float32", "bfloat16")):
    """The run kernel with `Gq` query heads to each of `H` KV heads
    (Trinity's 48 on 8), with and without a window, runs cut at 16
    tokens: a chunk of `chunk` tokens deep in one slot's context, a
    decode token in each of two others, padding after. Under a window
    the table columns behind each slot's window read NULL, as a window
    table's do."""
    import numpy as np

    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    ragged_ref = _exact(fa.ragged_gather_reference)
    cells = []
    T, S = chunk + 8, 3
    ctx = max_blocks * BS
    firsts = (ctx - chunk, ctx - 1, BS + 3)
    slot = [0] * chunk + [1, 2] + [-1] * (T - chunk - 2)
    pos = list(range(firsts[0], ctx)) + [firsts[1], firsts[2]] \
        + [0] * (T - chunk - 2)
    slot, pos = jnp.asarray(slot, jnp.int32), jnp.asarray(pos, jnp.int32)
    for dtype in dtypes:
        tol = 5e-2 if dtype == "bfloat16" else 2e-2
        rng = np.random.RandomState(7)
        NB = S * max_blocks + 1
        kp, vp = (jnp.asarray(rng.randn(NB, BS, H, Dh), dtype)
                  for _ in range(2))
        q = jnp.asarray(rng.randn(T, H * Gq, Dh), dtype)
        bt = (1 + np.arange(S * max_blocks, dtype=np.int32)).reshape(
            S, max_blocks)
        for w in (None, window):
            table = bt.copy()
            if w is not None:
                for s_, first in enumerate(firsts):
                    table[s_, :max(first - w + 1, 0) // BS] = 0
            got = pa.ragged_attend(q, kp, vp, jnp.asarray(table), slot,
                                   pos, window=w, max_run=16)
            want = ragged_ref(q, kp, vp, jnp.asarray(bt), slot, pos,
                              window=w)
            want = jnp.where((slot >= 0)[:, None, None], want, 0)
            cells.append(_cell(
                f"paged_ragged gqa {dtype} Hq={H * Gq} H={H} Dh={Dh} "
                f"BS={BS} MB={max_blocks} window={w}", got, want, tol,
                tol))
    return cells


def one_run_three_ways(attend, q, slot, first, dtype, T=128,
                       company=((1, 17), (5, 60))):
    """The outputs of one prompt's rows fed three ways through `attend(q
    [T, HQ, Dh], slot_ids [T], positions [T])`, its keys in the pool
    already: q [N, HQ, Dh] from position `first` of `slot` as ONE run;
    as the power-of-two CHUNKS a scheduler cuts (64, 32, ...), each in
    a step of its own beside the `company`'s decode tokens; and token
    by token (N runs of one: the flat order reversed, so that no two
    tokens join). -> (whole, chunks, singles), each [N, HQ, Dh]
    float32: a row's bits must not depend on its run."""
    import numpy as np

    import jax.numpy as jnp
    q = np.asarray(q, np.float32)
    N = len(q)

    def feed(rows, order, company=()):
        qq = np.full((T,) + q.shape[1:], 0.5, np.float32)
        sl = np.full(T, -1, np.int32)
        ps = np.zeros(T, np.int32)
        for i, (s_, p_) in enumerate(company):
            sl[i], ps[i] = s_, p_
        at = len(company) + np.asarray(order)
        qq[at], sl[at], ps[at] = q[rows], slot, first + np.asarray(rows)
        out = attend(jnp.asarray(qq, dtype), jnp.asarray(sl),
                     jnp.asarray(ps))
        return np.asarray(out.astype(jnp.float32))[at]

    rows = np.arange(N)
    cuts, a = [], 0
    while a < N:                        # the largest power of two left
        cuts.append((a, a + (1 << ((N - a).bit_length() - 1))))
        a = cuts[-1][1]
    chunks = np.concatenate([feed(rows[a:b], np.arange(b - a), company)
                             for a, b in cuts])
    return feed(rows, rows), chunks, feed(rows, rows[::-1])


def validate_paged_planes(*, Dh=128, BS=16, max_run=128, window=4096,
                          cases=((32, 1, None, None), (8, 6, "window", None),
                                 (16, 1, None, 3)),
                          dtypes=("bfloat16", "float32"), singles_tol=0.0):
    """The run kernel a PLANE of heads a product (`pa.plane_heads`: two
    heads of a bf16 pool through the uint32 view, one of a float32 pool
    through the strided read) at the serve cells' shapes, `cases` of
    (KV heads, query heads a KV head, window or None, stacked layers or
    None): Olmo's 32/1 with runs of `max_run` and one past it, Trinity's
    8/6 past its window, GPT's 16/1 on stacked pools with the layer a
    traced scalar; every run length a step holds in each. Then the same
    prompt fed as one run, as power-of-two chunks in company and token
    by token: a row's bits must not depend on its run (`singles_tol`:
    what a rehearsal allows the runs of one token, because XLA:CPU
    rounds a product's sums by its row count; the chip allows nothing)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    ragged_ref = _exact(fa.ragged_gather_reference)
    cells = []

    def layout(w):
        """(first position, tokens) a slot, the deepest past the
        window; the flat slot ids and positions; the tables' width."""
        deep = (w or 0) + 3 * BS + 5
        runs = [(deep, 1), (20, 2), (3, 7), (10, 64),
                (max(deep - max_run - 2, 0), max_run), (5, max_run + 1),
                (0, 1)]
        T = -(-sum(n for _, n in runs) // 8) * 8
        slot = np.full(T, -1, np.int32)
        pos = np.zeros(T, np.int32)
        t = 0
        for s_, (first, n) in enumerate(runs):
            slot[t:t + n], pos[t:t + n] = s_, first + np.arange(n)
            t += n
        return runs, slot, pos, max(f + n for f, n in runs) // BS + 1

    for H, Gq, w, L in cases:
        w = window if w else None
        runs, slot, pos, MB = layout(w)
        T, S = len(slot), len(runs)
        NB = S * MB + 1
        bt = (1 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB)
        table = bt.copy()
        if w is not None:
            for s_, (first, _) in enumerate(runs):
                table[s_, :max(first - w + 1, 0) // BS] = 0
        slot, pos = jnp.asarray(slot), jnp.asarray(pos)
        for dtype in dtypes:
            tol = 5e-2 if dtype == "bfloat16" else 2e-2
            rng = np.random.RandomState(17)
            shape = (NB, BS, H, Dh) if L is None else (L, NB, BS, H, Dh)
            kp, vp = (jnp.asarray(rng.randn(*shape), dtype)
                      for _ in range(2))
            q = jnp.asarray(rng.randn(T, H * Gq, Dh), dtype)
            layer = None if L is None else L - 1
            got = jax.jit(lambda q, kp, vp, li: pa.ragged_attend(
                q, kp, vp, jnp.asarray(table), slot, pos, window=w,
                max_run=max_run, layer=None if L is None else li))(
                    q, kp, vp, jnp.int32(layer or 0))
            # the oracle gathers a token's whole table: 32 tokens a call
            want = jnp.concatenate([
                ragged_ref(q[i:i + 32], kp, vp, jnp.asarray(bt),
                           slot[i:i + 32], pos[i:i + 32], window=w,
                           layer=layer) for i in range(0, T, 32)])
            want = jnp.where((slot >= 0)[:, None, None], want, 0)
            cells.append(_cell(
                f"paged_ragged planes {dtype} Hq={H * Gq} H={H} "
                f"P={pa.plane_heads(H, kp.dtype)} Dh={Dh} BS={BS} "
                f"MB={MB} window={w} max_run={max_run} stacked={L}",
                got, want, tol, tol))
    # one prompt of N tokens deep in slot 0, fed three ways
    H, Gq, N, first = 16, 1, 100, 3 * max_run + 5
    MB = (first + N) // BS + 1
    NB = 8 * MB + 1
    bt = (1 + np.arange(8 * MB, dtype=np.int32)).reshape(8, MB)
    for dtype in dtypes:
        rng = np.random.RandomState(19)
        kp, vp = (jnp.asarray(rng.randn(NB, BS, H, Dh), dtype)
                  for _ in range(2))
        attend = jax.jit(lambda q, sl, ps: pa.ragged_attend(
            q, kp, vp, jnp.asarray(bt), sl, ps, max_run=max_run))
        whole, chunks, singles = one_run_three_ways(
            attend, rng.randn(N, H * Gq, Dh), 0, first, dtype)
        for name, other, allowed in (("chunks", chunks, 0.0),
                                     ("single tokens", singles,
                                      singles_tol)):
            err = float(np.abs(whole - other).max())
            cells.append(Cell(
                f"paged_ragged one run = {name}, bit for bit {dtype} "
                f"H={H} Dh={Dh} BS={BS} N={N}",
                err <= allowed and bool(np.abs(whole).max() > 0.01), err))
    return cells


def validate_paged_block_causal(*, H=4, Gq=8, Dh=128, BS=16, L=4,
                                max_run=128, N=197,
                                dtypes=("bfloat16", "float32"),
                                blocks_tol=0.0):
    """The run kernel under the BLOCK-CAUSAL rule (`causal_block=L`: a
    query attends the keys to the end of its block of L positions) at
    the diffusion cell's heads, 4 KV x 8 query heads a group: a prompt
    of N tokens (not a multiple of L: it ends inside a block, and what
    lies behind it in the pool must stay unseen) as ONE run against the
    XLA oracle under the same rule; then the same prompt in chunks of
    64, a step each, and followed block by block (runs of L, laid in
    reverse so that no two join): a row's bits must not depend on its
    run (`blocks_tol`: what a rehearsal allows the runs of L, because
    XLA:CPU rounds a product's sums by its row count)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    ragged_ref = _exact(fa.ragged_gather_reference)
    T = -(-N // max_run) * max_run
    MB = N // BS + 2
    NB = 2 * MB + 1
    bt = (1 + np.arange(2 * MB, dtype=np.int32)).reshape(2, MB)
    cells = []
    for dtype in dtypes:
        tol = 5e-2 if dtype == "bfloat16" else 2e-2
        rng = np.random.RandomState(23)
        kp, vp = (jnp.asarray(rng.randn(NB, BS, H, Dh), dtype)
                  for _ in range(2))
        q = rng.randn(N, H * Gq, Dh).astype(np.float32)
        attend = jax.jit(lambda q, sl, ps: pa.ragged_attend(
            q, kp, vp, jnp.asarray(bt), sl, ps, max_run=max_run,
            causal_block=L))

        def feed(runs):
            """The prompt's rows `runs` [(first, n)] in one step, in the
            order given."""
            qq = np.full((T, H * Gq, Dh), 0.5, np.float32)
            sl = np.full(T, -1, np.int32)
            ps = np.zeros(T, np.int32)
            at, t = [], 0
            for first, n in runs:
                qq[t:t + n], sl[t:t + n] = q[first:first + n], 1
                ps[t:t + n] = first + np.arange(n)
                at.append((first, t, n))
                t += n
            out = np.asarray(attend(jnp.asarray(qq, dtype), jnp.asarray(sl),
                                    jnp.asarray(ps)).astype(jnp.float32))
            got = np.zeros((N, H * Gq, Dh), np.float32)
            for first, t, n in at:
                got[first:first + n] = out[t:t + n]
            return got, (qq, sl, ps)

        whole, (qq, sl, ps) = feed([(0, N)])
        want = np.concatenate([np.asarray(ragged_ref(
            jnp.asarray(qq[i:i + 32], dtype), kp, vp, jnp.asarray(bt),
            jnp.asarray(sl[i:i + 32]), jnp.asarray(ps[i:i + 32]),
            causal_block=L, max_run=max_run).astype(jnp.float32))
            for i in range(0, T, 32)])[:N]
        # (the oracle's runs are cut 32 tokens a call: a multiple of L,
        # and the last call ends where the prompt does)
        shape = (f"{dtype} Hq={H * Gq} H={H} Dh={Dh} BS={BS} L={L} "
                 f"N={N} max_run={max_run}")
        cells.append(_cell(f"paged_ragged block-causal {shape}", whole,
                           want, tol, tol))
        chunks = np.zeros_like(whole)
        for a in range(0, N, 64):
            chunks[a:a + 64] = feed([(a, min(64, N - a))])[0][a:a + 64]
        blocks = np.zeros_like(whole)
        per = max_run // L                  # blocks a step
        starts = list(range(0, N, L))
        for i in range(0, len(starts), per):
            part = [(b, min(L, N - b)) for b in starts[i:i + per]][::-1]
            got = feed(part)[0]
            for b, n in part:
                blocks[b:b + n] = got[b:b + n]
        for name, other, allowed in (("chunks of 64", chunks, 0.0),
                                     ("block by block", blocks,
                                      blocks_tol)):
            err = float(np.abs(whole - other).max())
            cells.append(Cell(
                f"paged_ragged block-causal one run = {name}, bit for "
                f"bit {shape}",
                err <= allowed and bool(np.abs(whole).max() > 0.01), err))
    return cells


def validate_paged_selection(*, H=4, Gq=8, Dh=128, BS=16, max_run=128,
                             first=2350, N=197, topk=512, rows=16,
                             dtypes=("bfloat16", "float32"),
                             singles_tol=0.0):
    """Attention through a SELECTION at the sparse cell's heads, 4 KV x
    8 query heads a group. (a) The run kernel under `select=` (a packed
    bit mask a row): N rows from position `first` (ten fetched groups
    of 256 keys behind them: both bits of the mask's word planes) as
    ONE run against the XLA oracle under the same selection; the same
    rows in chunks of 64, a step each, and token by token (laid in
    reverse so that no two join): a row's bits must not depend on its
    run; and a selection of EVERYTHING is today's kernel, bit for bit.
    (b) What attends the decode rows (`engine.attend_gathered`): `rows`
    queries at contexts up to the table's width over `topk` gathered
    positions each, against the same oracle."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving.engine import attend_gathered

    ragged_ref = _exact(fa.ragged_gather_reference)
    T = -(-N // max_run) * max_run
    S = first + N
    MB = -(-S // BS) + 1
    NB = rows * MB + 1
    bt = (1 + np.arange(rows * MB, dtype=np.int32)).reshape(rows, MB)
    cells = []
    for dtype in dtypes:
        tol = 5e-2 if dtype == "bfloat16" else 2e-2
        rng = np.random.RandomState(29)
        kp, vp = (jnp.asarray(rng.randn(NB, BS, H, Dh), dtype)
                  for _ in range(2))
        q = rng.randn(N, H * Gq, Dh).astype(np.float32)
        sel = rng.rand(N, MB * BS) < 0.25
        sel[np.arange(N), first + np.arange(N)] = True
        attend = jax.jit(lambda q, sl, ps, m: pa.ragged_attend(
            q, kp, vp, jnp.asarray(bt), sl, ps, max_run=max_run,
            select=m))
        plain = jax.jit(lambda q, sl, ps: pa.ragged_attend(
            q, kp, vp, jnp.asarray(bt), sl, ps, max_run=max_run))

        def feed(runs, every=False):
            """The rows `runs` [(offset, n)] in one step, in the order
            given. -> (their outputs by offset, the step's inputs)."""
            qq = np.full((T, H * Gq, Dh), 0.5, np.float32)
            sl = np.full(T, -1, np.int32)
            ps = np.zeros(T, np.int32)
            mm = np.ones((T, MB * BS), bool)
            at, t = [], 0
            for a, n in runs:
                qq[t:t + n], sl[t:t + n] = q[a:a + n], 1
                ps[t:t + n] = first + a + np.arange(n)
                if not every:
                    mm[t:t + n] = sel[a:a + n]
                at.append((a, t, n))
                t += n
            out = np.asarray(attend(
                jnp.asarray(qq, dtype), jnp.asarray(sl), jnp.asarray(ps),
                jnp.asarray(mm)).astype(jnp.float32))
            got = np.zeros((N, H * Gq, Dh), np.float32)
            for a, t, n in at:
                got[a:a + n] = out[t:t + n]
            return got, (qq, sl, ps, mm)

        whole, (qq, sl, ps, mm) = feed([(0, N)])
        want = np.concatenate([np.asarray(ragged_ref(
            jnp.asarray(qq[i:i + 32], dtype), kp, vp, jnp.asarray(bt),
            jnp.asarray(sl[i:i + 32]), jnp.asarray(ps[i:i + 32]),
            select=jnp.asarray(mm[i:i + 32])).astype(jnp.float32))
            for i in range(0, T, 32)])[:N]
        shape = (f"{dtype} Hq={H * Gq} H={H} Dh={Dh} BS={BS} "
                 f"positions {first}..{S - 1} max_run={max_run}")
        cells.append(_cell(f"paged_ragged under a selection {shape}",
                           whole, want, tol, tol))
        chunks = np.zeros_like(whole)
        for a in range(0, N, 64):
            chunks[a:a + 64] = feed([(a, min(64, N - a))])[0][a:a + 64]
        singles = np.zeros_like(whole)
        for a in range(0, N, max_run):
            part = [(i, 1) for i in range(a, min(a + max_run, N))][::-1]
            got = feed(part)[0]
            for i, _ in part:
                singles[i] = got[i]
        for name, other, allowed in (("chunks of 64", chunks, 0.0),
                                     ("token by token", singles,
                                      singles_tol)):
            err = float(np.abs(whole - other).max())
            cells.append(Cell(
                f"paged_ragged under a selection one run = {name}, bit "
                f"for bit {shape}",
                err <= allowed and bool(np.abs(whole).max() > 0.01), err))
        every, (qq, sl, ps, _) = feed([(0, N)], every=True)
        today = np.asarray(plain(
            jnp.asarray(qq, dtype), jnp.asarray(sl),
            jnp.asarray(ps)).astype(jnp.float32))[:N]
        err = float(np.abs(every - today).max())
        cells.append(Cell(
            f"paged_ragged a selection of everything = no selection, "
            f"bit for bit {shape}", err == 0.0, err))
        # (b) the decode rows: one query a slot over gathered positions
        ctx = rng.randint(topk // 2, MB * BS, size=rows)
        ctx[0], ctx[1] = MB * BS - 1, topk // 2      # longest, under topk
        at = np.full((rows, topk), -1, np.int32)
        mask = np.zeros((rows, MB * BS), bool)
        for r in range(rows):
            n = min(topk, ctx[r] + 1)
            at[r, :n] = np.sort(rng.permutation(ctx[r] + 1)[:n])
            mask[r, at[r, :n]] = True
        qd = jnp.asarray(rng.randn(rows, H * Gq, Dh), dtype)
        got = jax.jit(attend_gathered)(qd, kp, vp, jnp.asarray(bt),
                                       jnp.asarray(at))
        want = ragged_ref(qd, kp, vp, jnp.asarray(bt),
                          jnp.arange(rows, dtype=jnp.int32),
                          jnp.asarray(ctx, jnp.int32),
                          select=jnp.asarray(mask)).astype(jnp.float32)
        cells.append(_cell(
            f"attend_gathered {rows} rows x {topk} positions {dtype} "
            f"Hq={H * Gq} H={H} Dh={Dh} contexts to {MB * BS}", got,
            want, tol, tol))
    return cells


def validate_ragged_expert_matmul(*, sizes=(9, 0, 70, 1), D=256, F=384,
                                  dtypes=("float32", "bfloat16")):
    """The dropless expert layer's ragged grouped matmul, plain and
    SwiGLU, against per-group `x @ w`: groups of uneven sizes (one
    empty, one over a tile) in tile-padded rows."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    cells = []
    E = len(sizes)
    NT = gmm.ragged_num_tiles(sum(sizes) + 60, E)
    bm = gmm.RAGGED_BLOCK_M
    row_start, te, nu = gmm.ragged_layout(jnp.asarray(sizes, jnp.int32),
                                          NT)
    rows = np.concatenate([int(row_start[e]) + np.arange(n)
                           for e, n in enumerate(sizes)])
    group = np.concatenate([np.full(n, e) for e, n in enumerate(sizes)])
    for dtype in dtypes:
        rng = np.random.RandomState(11)
        x = np.zeros((NT * bm, D), np.float32)
        x[rows] = rng.randn(len(rows), D)
        x = jnp.asarray(x, dtype)
        w, w2 = (jnp.asarray(rng.randn(E, D, F) / 16, dtype)
                 for _ in range(2))
        with jax.default_matmul_precision("highest"):
            xf = x[rows].astype(jnp.float32)
            a = jnp.einsum("rd,rdf->rf", xf, w.astype(jnp.float32)[group])
            b = jnp.einsum("rd,rdf->rf", xf, w2.astype(jnp.float32)[group])
        tol = 5e-2 if dtype == "bfloat16" else 2e-2
        cells.append(_cell(
            f"moe_experts ragged {dtype} sizes={list(sizes)} D={D} F={F}",
            gmm.ragged_expert_matmul(x, w, te, nu)[rows], a, tol, tol))
        cells.append(_cell(
            f"moe_experts ragged swiglu {dtype} sizes={list(sizes)} "
            f"D={D} F={F}",
            gmm.ragged_expert_matmul(x, w, te, nu, w2)[rows],
            jax.nn.silu(a) * b, tol, tol))
    return cells


def _delta_case(rng, lens, firsts, T, H, dk, dv, slots, dtype="float32",
                gaps=None, slot_of=None):
    """q, k, v, g, beta, runs, state of one step: run i of `lens[i]`
    tokens from position `firsts[i]` in slot `slot_of[i]` (default i +
    1: slot 0 stays idle), `gaps` padding tokens behind the runs they
    name."""
    import numpy as np

    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import paged_runs
    slot_ids, pos = np.full(T, -1, np.int32), np.zeros(T, np.int32)
    i = 0
    for s, (n, f) in enumerate(zip(lens, firsts)):
        slot_ids[i:i + n] = (s + 1) % slots if slot_of is None \
            else slot_of[s]
        pos[i:i + n] = f + np.arange(n)
        i += n + (gaps or {}).get(s, 0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q, k, v = (jnp.asarray(a, dtype) for a in (
        unit(rng.randn(T, H, dk)) / np.sqrt(dk),
        unit(rng.randn(T, H, dk)), rng.randn(T, H, dv)))
    g = jnp.asarray(-np.exp(rng.uniform(-5, 0, (T, H))), jnp.float32)
    beta = jnp.asarray(2 / (1 + np.exp(-rng.randn(T, H))), jnp.float32)
    state = jnp.asarray(rng.randn(slots, H, dk, dv), jnp.float32)
    return (q, k, v, g, beta,
            paged_runs(jnp.asarray(slot_ids), jnp.asarray(pos), None),
            state)


def validate_gated_delta(*, H=6, dk=96, dv=192, slots=8, chunk=64,
                         lens=(1, 3, 63, 64, 65, 200),
                         dtypes=("float32", "bfloat16"),
                         step=dict(H=30, T=512, slots=32)):
    """The ragged chunked delta-rule kernel (`gated_delta`) against the
    token-by-token recurrence: runs of uneven lengths in one call (a
    decode token, a run one short of a chunk, one of a chunk, one over,
    one of several chunks), a hole of padding tokens, incoming state on
    the runs that do not start at position 0, and a key dim of 96: not
    a multiple of the 128 lanes, padded inside the kernel and not in
    the stored state. Then, at the heads, slots and token budget of
    `step` (the Olmo cell's): the mixes a serving step can hold (one
    token a slot and nothing else; two tokens a slot, the most partial
    chunks there can be; odd lengths, fresh and continued; no run at
    all; a run that ends on the budget's last token), and one prompt
    as one run = in chunks of 64 beside decode runs, bit for bit."""
    import numpy as np

    import jax
    from paddle_tpu.ops.pallas import gated_delta as gd

    ragged = jax.jit(lambda *a: gd.gated_delta_ragged(*a, chunk=chunk))
    scan = _exact(jax.jit(gd.gated_delta_scan))
    rng = np.random.RandomState(5)
    T = -(-(sum(lens) + 12) // 8) * 8
    cells = []
    for dtype in dtypes:
        case = _delta_case(
            rng, lens, [0 if s % 2 == 0 else 5 + s for s in
                        range(len(lens))], T, H, dk, dv, slots, dtype,
            gaps={1: 5})
        want, got = scan(*case), ragged(*case)
        tol = 2e-2 if dtype == "bfloat16" else 2e-4
        shape = f"{dtype} H={H} dk={dk} dv={dv} chunk={chunk} " \
            f"runs={list(lens)}"
        cells.append(_cell(f"gated_delta o {shape}", got[0], want[0],
                           tol, tol))
        cells.append(_cell(f"gated_delta state {shape}", got[1], want[1],
                           2e-4, 2e-4))
    H, T, S = step["H"], step["T"], step["slots"]
    odd = (1, 3, chunk, chunk + 1, 2 * chunk + 1)
    mixes = {
        f"{S} runs of 1": ([1] * S, [7 + s for s in range(S)]),
        f"{S} runs of 2": ([2] * S, [s % 2 * 9 for s in range(S)]),
        f"runs {list(odd)}": (odd, [0, 4, 0, chunk, 0]),
        "no run": ((), ()),
        f"a run to the last of {T} tokens": ((3, T - 3), (11, 0))}
    for name, (ls, firsts) in mixes.items():
        case = _delta_case(rng, ls, firsts, T, H, dk, dv, S)
        want, got = scan(*case), ragged(*case)
        shape = f"H={H} dk={dk} dv={dv} T={T} slots={S}: {name}"
        for what, i in (("o", 0), ("state", 1)):
            cells.append(_cell(f"gated_delta {what} {shape}", got[i],
                               want[i], 2e-4, 2e-4))
    # one prompt of 2 chunks + 1 token in slot 1: as ONE run, and chunk
    # by chunk behind the decode tokens of 13 other slots (a start that
    # is no multiple of 8), the state carried from step to step
    N, d = 2 * chunk + 1, 13
    whole = _delta_case(rng, (N,), (0,), T, H, dk, dv, S)
    o_whole, s_whole = ragged(*whole)
    state, o_parts = whole[6], []
    for at, n in ((0, chunk), (chunk, chunk), (2 * chunk, 1)):
        part = _delta_case(rng, [1] * d + [n], [3] * d + [at], T, H, dk,
                           dv, S, slot_of=list(range(2, d + 2)) + [1])
        fed = [x.at[d:d + n].set(w[at:at + n])
               for x, w in zip(part[:5], whole[:5])]
        o, state = ragged(*fed, part[5], state)
        o_parts.append(o[d:d + n])
    shape = f"H={H} T={T}: {N} tokens as one run = in chunks beside " \
        f"{d} decode runs"
    cells.append(_cell(f"gated_delta o {shape}", np.concatenate(o_parts),
                       o_whole[:N], 0, 0))
    cells.append(_cell(f"gated_delta state {shape}", state[1], s_whole[1],
                       0, 0))
    return cells


def validate_add_ln(*, rows=512, d=256, dtype="bfloat16"):
    """Fused residual-add + LayerNorm against its jnp form: the forward
    pair (normalized, new residual) and the input/scale/shift grads of
    a seeded scalar loss (the custom vjp's backward kernel)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import layer_norm as ln

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(rows, d), jnp.float32).astype(dtype)
    r = jnp.asarray(rng.randn(rows, d), jnp.float32).astype(dtype)
    w = jnp.asarray(1.0 + 0.1 * rng.randn(d), jnp.float32)
    b = jnp.asarray(0.1 * rng.randn(d), jnp.float32)
    g = jnp.asarray(rng.randn(rows, d), jnp.float32)

    def kernel(x, r, w, b):
        return ln._add_ln(x, r, w, b, 1e-5)

    def loss(fn):
        def f(x, r, w, b):
            out, z = fn(x, r, w, b)
            return jnp.sum(out.astype(jnp.float32) * g) \
                + jnp.sum(z.astype(jnp.float32))
        return f

    tol = 5e-2 if dtype == "bfloat16" else 1e-3
    shape = f"{dtype} [{rows}, {d}]"
    fwd = _cell(f"add_ln fwd {shape}", jax.jit(kernel)(x, r, w, b),
                ln.add_ln_reference(x, r, w, b), tol, tol)
    grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2, 3)))
    ref = jax.jit(jax.grad(loss(ln.add_ln_reference),
                           argnums=(0, 1, 2, 3)))
    dx, dr, dw, db = grads(x, r, w, b)
    rx, rr, rw, rb = ref(x, r, w, b)
    bwd = _cell(f"add_ln bwd dx {shape}", (dx, dr), (rx, rr), tol, tol)
    red = _cell(f"add_ln bwd dw/db {shape}", (dw, db), (rw, rb), tol,
                tol)
    return [fwd, bwd, red]


def validate_splash(*, B=1, H=2, S=256, D=64, dtype="bfloat16"):
    """`splash_mha` (jax's library kernel behind the trainer's
    attention, with this repo's block sizes) against XLA attention:
    the causal forward and the q/k/v grads of a seeded scalar loss
    (the fused backward kernel)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.RandomState(13)
    q, k, v, g = (jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
                  .astype(dtype) for _ in range(4))
    scale = 1.0 / np.sqrt(D)

    def reference(q, k, v):
        return jax.nn.dot_product_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), scale=scale,
            is_causal=True).transpose(0, 2, 1, 3)

    def kernel(q, k, v):
        if not fa.splash_supported(S, D):
            raise RuntimeError(f"splash gate refuses S={S} D={D}: the "
                               "cell would compare XLA with XLA")
        return fa.splash_mha(q, k, v, causal=True, scale=scale)

    def grads(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * g.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    tol = 5e-2 if dtype == "bfloat16" else 2e-2
    shape = f"{dtype} [{B}, {H}, {S}, {D}]"
    return [
        _cell(f"splash fwd {shape}", jax.jit(kernel)(q, k, v),
              _exact(jax.jit(reference))(q, k, v), tol, tol),
        _cell(f"splash bwd {shape}", grads(kernel)(q, k, v),
              _exact(grads(reference))(q, k, v), tol, tol),
    ]


def validate_flash(*, cases=((256, 128, "float32"),
                             (512, 128, "bfloat16"))):
    """Hand flash-forward kernel at lane-aligned shapes."""
    import numpy as np

    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.RandomState(11)
    cells = []
    for s, d, dtype in cases:
        q, k, v = (jnp.asarray(rng.randn(3, s, d), jnp.float32)
                   .astype(dtype) for _ in range(3))
        scale = 1.0 / np.sqrt(d)
        cells.append(_cell(
            f"flash_fwd {dtype} S={s} D={d}",
            fa._flash_fwd(q, k, v, scale, True, 128, 128),
            _exact(fa._xla_reference)(q, k, v, scale, True), 3e-2, 3e-2))
    return cells


def validate_grouped_matmul(*, E=4, C=128, D=128, F=256):
    """Grouped-expert matmul: fp32 + int8/int4 weight-only dequant."""
    import numpy as np

    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_matmul as gmm

    oracle = _exact(gmm.grouped_matmul_oracle)
    rng = np.random.RandomState(23)
    x = jnp.asarray(rng.randn(E, C, D), jnp.float32)
    w = jnp.asarray(rng.randn(E, D, F), jnp.float32)
    shape = f"E={E} C={C} D={D} F={F}"
    cells = [_cell(f"grouped_matmul float32 {shape}",
                   gmm.grouped_expert_matmul(x, w), oracle(x, w),
                   2e-2, 2e-2)]
    # int8 weight-only (per-out-channel amax, qmax=127 convention)
    s8 = jnp.maximum(jnp.max(jnp.abs(w), axis=-2), 1e-9)
    q8 = jnp.clip(jnp.round(w / s8[:, None, :] * 127.0), -127,
                  127).astype(jnp.int8)
    cells.append(_cell(f"grouped_matmul int8 {shape}",
                       gmm.grouped_expert_matmul(x, q8, s8),
                       oracle(x, q8, s8), 5e-2, 5e-2))
    # int4 nibble-packed (quantize_int4_experts' layout + fp16 scales)
    q4, s4 = gmm.quantize_int4_experts(w)
    cells.append(_cell(f"grouped_matmul int4 {shape}",
                       gmm.grouped_expert_matmul(x, q4, s4),
                       oracle(x, q4, s4), 5e-2, 5e-2))
    return cells


def run_matrix(rehearse=False):
    """The default matrix: every family at small aligned shapes, the
    stacked-pool entries at the GPT serve cells' (a rehearsal takes
    them small and in two dtypes: the interpreter pays seconds a cell,
    and tier-1 runs it)."""
    small = dict(max_blocks=8, slots=4,
                 dtypes=("bfloat16", "int8")) if rehearse else {}
    return (validate_paged() + validate_paged_stacked(**small)
            + validate_paged_gqa_window()
            + validate_paged_planes(**(dict(
                Dh=16, BS=8, max_run=16, window=40, singles_tol=4e-3,
                cases=((8, 6, "window", None), (4, 1, None, 2)))
                if rehearse else {}))
            + validate_paged_block_causal(**(dict(
                Dh=16, BS=8, max_run=16, N=45, blocks_tol=4e-3,
                dtypes=("bfloat16",)) if rehearse else {}))
            + validate_paged_selection(**(dict(
                Dh=16, BS=8, max_run=16, first=300, N=45, topk=32, rows=4,
                singles_tol=4e-3, dtypes=("bfloat16",))
                if rehearse else {}))
            + validate_ragged_expert_matmul()
            + validate_gated_delta(**(dict(
                H=2, lens=(1, 3, 65), step=dict(H=2, T=264, slots=16))
                if rehearse else {}))
            + validate_add_ln()
            + validate_splash() + validate_flash()
            + validate_grouped_matmul())


def main(argv=None):
    import contextlib

    import jax
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the matrix on the CPU with the kernels in "
                         "interpret mode; validates no device tile")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if args.rehearse:
        from paddle_tpu.ops.pallas import interpret_mode
        mode = interpret_mode()
        print(f"tpu_tile_validate: CPU REHEARSAL on {platform!r} — "
              "kernels in interpret mode, no device tile is validated")
    elif platform != "tpu":
        print(f"tpu_tile_validate: backend is {platform!r}, not tpu — "
              "nothing validated (pass --rehearse for the interpret-"
              "mode rehearsal)", file=sys.stderr)
        return 1
    else:
        mode = contextlib.nullcontext()
    with mode:
        cells = run_matrix(args.rehearse)
    for c in cells:
        print(f"tile {c}")
    bad = [c for c in cells if not c.ok]
    print(f"tpu_tile_validate: {len(cells) - len(bad)}/{len(cells)} "
          f"cells match their XLA oracles"
          + (" (CPU REHEARSAL)" if args.rehearse else
             f" on {jax.devices()[0].device_kind}"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
