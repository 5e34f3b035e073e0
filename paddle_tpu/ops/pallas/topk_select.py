"""Exact top-k SELECTION over rows of float32 scores, as a mask: what
learned sparse attention (`models/keye_vl2.py`, the serving step's
sparse layers) takes its keys by.

`topk_mask(scores, k, cand)` keeps, a row, the `min(k, candidates)`
candidates with the largest scores; equal scores go to the LOWER
column. It never sorts: a row's k-th largest score is found by
bisection on the order-preserving integer image of its float32 scores
(32 counting passes, one bit a pass), and the ties at that score are
cut at a column found the same way (a counting pass a bit of the
column index). The result is the set a
stable sort by descending score would give, for any k and any scores
(-0.0 and +0.0 are one score, as they compare).

Two forms of it, the same set bit for bit, chosen by backend
(`topk_select_enabled`):

- `topk_mask_jnp`: plain `jax.numpy` over the whole width of every
  row, the CPU path and the oracle;
- the Mosaic kernel `topk_select`: a tile of `ROWS` rows a grid step.
  A tile's REACH is its rows' largest candidate column + 1; it copies
  its scores and candidates below the reach, rounded up to a strip of
  columns (`strip_of`), into VMEM once, makes the order keys there, and
  every counting pass walks those strips only (`cols_walked` is the
  rule, for the engine's counters): a row's columns past its position are never
  read, a tile with no candidate reads nothing, a tile whose rows hold
  at most k candidates each keeps them without a pass, and the ties'
  cut runs only in a tile where a row has more keys equal to its k-th
  score than it needs.

`index_scores(qI, w, kI)` is the indexer's score itself, the one place
its formula is written; `mask_positions(keep, k)` turns a mask into the
ascending list of the kept columns, for the rows that attend a GATHERED
selection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False

#: rows a grid step of the kernel: the sublane tile of its int8 candidates
#: and mask
ROWS = 32
_LANES = 128
_MIN = -2 ** 31


def topk_select_enabled() -> bool:
    """The Mosaic kernel on a TPU backend (or interpreted, in tests);
    the `jax.numpy` form elsewhere."""
    if _INTERPRET:
        return True
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


def strip_of(width):
    """Columns a copy and a step of a counting pass over a table `width`
    wide: the largest of 1024, 512, 256, 128 that divides the width
    rounded up to whole lane tiles (a longer strip costs fewer loop
    steps a pass; the table is padded to the lane tile only)."""
    lanes = -(-int(width) // _LANES) * _LANES
    return next(s for s in (1024, 512, 256, _LANES) if lanes % s == 0)


def cols_walked(reach, width):
    """Columns the kernel walks for rows whose reaches (largest candidate
    column + 1, 0 for a row with none) are `reach`, in row order, on a
    table `width` wide (host arithmetic, for the engine's counters): a
    tile of `ROWS` rows, the last one padded, walks its largest reach
    rounded up to `strip_of(width)`, a counting pass over every row of
    it."""
    reach, strip = [int(r) for r in reach], strip_of(width)
    return sum(ROWS * -(-max(reach[i:i + ROWS]) // strip) * strip
               for i in range(0, len(reach), ROWS))


def index_scores(qI, w, kI):
    """I(t, s) = sum_j w_t[j] relu(qI_t[j] . kI_s), [T, S] float32, of
    queries (qI [T, J, Di], w [T, J] float32, the scale folded in) over
    keys kI [S, Di]: operands in their own dtype, float32 sums."""
    s = jnp.einsum("tjd,sd->tjs", qI, kI,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)


def signed_key(x):
    """float32 -> int32, order preserving as SIGNED ints (the two zeros
    are made one first): `order_key` less its shift onto the unsigned
    order, the form the kernel compares."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    # a negative float's magnitude bits run the wrong way: flip them
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def order_key(x):
    """float32 -> uint32, order preserving: `a < b` as floats iff
    `order_key(a) < order_key(b)` as unsigned ints, and `a == b` iff the
    keys are equal (the two zeros are made one first)."""
    b = signed_key(x.astype(jnp.float32))
    # the signed order shifted onto the unsigned one
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def kth_largest(keys, k):
    """keys [R, C] uint32, C >= k >= 1 -> [R] the k-th largest of each
    row: the largest v with `count(keys >= v) >= k`, built from the top
    bit down."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        n = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, t)
    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros((keys.shape[0],), jnp.uint32))


def nth_column(flags, n):
    """flags [R, C] bool, n [R] int32 -> [R] the column of each row's
    n-th set flag (n >= 1; 0 where n < 1): the largest t with
    `count(flags[:t]) < n`, built from the top bit down. (A running
    count along the row would do; XLA expands one into windowed
    reductions that cost several passes and carry no scope.)"""
    C = flags.shape[1]
    col = jnp.arange(C, dtype=jnp.int32)[None, :]
    bits = max(C - 1, 1).bit_length()

    def bit(i, t):
        cand = t | (jnp.int32(1) << (bits - 1 - i))
        below = jnp.sum(flags & (col < cand[:, None]), axis=1,
                        dtype=jnp.int32)
        return jnp.where(below < n, cand, t)
    return jax.lax.fori_loop(
        0, bits, bit, jnp.zeros((flags.shape[0],), jnp.int32))


def topk_mask(scores, k, cand):
    """scores [R', C'] float32, cand [R, C] bool (the columns a row may
    take; R' >= R, C' >= C: scores' first R rows and C columns are read,
    so a caller hands over a padded buffer as it stands, not a slice
    that would be copied) -> keep [R, C] bool: the `min(k, cand.sum())`
    candidates of each row with the largest scores, equal scores to the
    lower column. Exact."""
    if topk_select_enabled() and int(k) < cand.shape[1]:
        return _topk_select(scores, int(k), cand)
    return topk_mask_jnp(scores, k, cand)


def topk_mask_jnp(scores, k, cand):
    """`topk_mask` in `jax.numpy`, every pass over the whole width."""
    k = int(k)
    R, C = cand.shape
    if k >= C:
        return cand
    keys = jnp.where(cand, order_key(scores[:R, :C]), jnp.uint32(0))
    # a candidate's key is never 0 unless its score is -nan: a column
    # that is no candidate sorts below every one that is
    kth = kth_largest(keys, k)[:, None]
    above = keys > kth
    equal = keys == kth
    need = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    # the ties: the `need` lowest columns among the equal scores
    col = jnp.arange(C, dtype=jnp.int32)[None, :]
    last = nth_column(equal, need)[:, None]
    ties = equal & (col <= last) & (need > 0)[:, None]
    return (above | ties) & cand


def _select_kernel(strips_ref, full_ref, s_hbm, c_hbm, o_ref, s_v, c_v,
                   key_v, sem, *, k, SW, bits):
    """One tile of rows: `strips_ref[i]` strips of columns hold every
    candidate of its rows (0: none), `full_ref[i]` says whether a row
    holds more than k of them. The order key in its signed form
    (`signed_key`: Mosaic compares int32): unsigned 0, a column that is no
    candidate, is INT_MIN. A tile's strips land in one of two buffers,
    copied while the tile before it counts."""
    i = pl.program_id(0)
    n = strips_ref[i]
    TR = o_ref.shape[0]
    i32 = jnp.int32
    buf = i % 2
    shape = (TR, _LANES)
    zeros = jnp.zeros(shape, i32)
    lane = jax.lax.broadcasted_iota(i32, shape, 1)

    def at(j, u):
        return pl.ds(pl.multiple_of(j * SW + u * _LANES, _LANES), _LANES)

    def copies(tile, b, j):
        rows = pl.ds(pl.multiple_of(tile * TR, TR), TR)
        cols = pl.ds(pl.multiple_of(j * SW, SW), SW)
        return (pltpu.make_async_copy(s_hbm.at[rows, cols],
                                      s_v.at[b, :, cols], sem.at[b, 0]),
                pltpu.make_async_copy(c_hbm.at[rows, cols],
                                      c_v.at[b, :, cols], sem.at[b, 1]))

    def fetch(tile, b):
        def start(j, c):
            for cp in copies(tile, b, j):
                cp.start()
            return c
        jax.lax.fori_loop(0, strips_ref[tile], start, 0)

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        fetch(i + 1, 1 - buf)

    def is_cand(j, u):
        return c_v[buf, :, at(j, u)].astype(i32) != 0

    def walk(body, init):
        """body(j, u, carry) over the 128-lane pieces of the walked
        strips."""
        def strip(j, carry):
            for u in range(SW // _LANES):
                carry = body(j, u, carry)
            return carry
        return jax.lax.fori_loop(0, n, strip, init)

    def per_row(acc):
        return jnp.broadcast_to(jnp.sum(acc, axis=1, keepdims=True), shape)

    def count(pred):
        """[TR, 128]: a row's count of `pred(key, column)`."""
        def body(j, u, acc):
            col = lane + (j * SW + u * _LANES)
            return acc + pred(key_v[:, at(j, u)], col).astype(i32)
        return per_row(walk(body, zeros))

    def build(j, u, held0):
        if u == 0:
            for cp in copies(i, buf, j):
                cp.wait()
        b = signed_key(s_v[buf, :, at(j, u)])
        c = is_cand(j, u)
        key_v[:, at(j, u)] = jnp.where(c, b, i32(_MIN))
        # a candidate whose key is INT_MIN (one NaN pattern) ties with
        # the columns that are none
        return held0 + (c & (b == _MIN)).astype(i32)

    held0 = per_row(walk(build, zeros))

    def kth_bit(b, t):
        # unsigned bisection, as `kth_largest`, compared in signed form
        cu = t | jax.lax.shift_left(i32(1), 31 - b)
        n_ge = count(lambda key, col: key >= (cu ^ _MIN))
        return jnp.where(n_ge >= k, cu, t)

    # a tile whose rows hold at most k candidates each keeps them all:
    # a k-th key of unsigned 0 gives that
    kth = jax.lax.fori_loop(0, full_ref[i] * 32, kth_bit, zeros) ^ i32(_MIN)

    def above_equal(j, u, acc):
        key = key_v[:, at(j, u)]
        return (acc[0] + (key > kth).astype(i32),
                acc[1] + (key == kth).astype(i32))

    above, equal = map(per_row, walk(above_equal, (zeros, zeros)))
    need = k - above
    # the ties need a cut where a row has more equal keys than it needs
    # and one of them is a candidate
    cut = (need > 0) & (equal > need) & ((kth != _MIN) | (held0 > 0))

    def col_bit(b, t):
        cc = t | jax.lax.shift_left(i32(1), bits - 1 - b)
        below = count(lambda key, col: (key == kth) & (col < cc))
        return jnp.where(below < need, cc, t)

    last = jax.lax.fori_loop(0, jnp.max(cut.astype(i32)) * bits, col_bit,
                             zeros)
    last = jnp.where(cut, last, i32(2 ** 31 - 1))
    tie = need > 0

    def out(j, u, c):
        key = key_v[:, at(j, u)]
        col = lane + (j * SW + u * _LANES)
        keep = (key > kth) | ((key == kth) & tie & (col <= last)
                              & is_cand(j, u))
        o_ref[:, at(j, u)] = keep.astype(jnp.int8)
        return c

    walk(out, 0)

    def clear(j, c):
        o_ref[:, pl.ds(pl.multiple_of(j * SW, SW), SW)] = jnp.zeros(
            (TR, SW), jnp.int8)
        return c

    jax.lax.fori_loop(n, o_ref.shape[1] // SW, clear, 0)


def _topk_select(scores, k, cand):
    """`topk_mask` through the kernel (k < C)."""
    R, C = cand.shape
    TR, SW = ROWS, strip_of(C)
    Rp, Cp = -(-R // TR) * TR, -(-C // _LANES) * _LANES
    nt = Rp // TR
    # a row's reach and its count of candidates, a tile's largest
    reach = jnp.max(jnp.where(cand, jnp.arange(1, C + 1, dtype=jnp.int32),
                              0), axis=1)
    held = jnp.sum(cand, axis=1, dtype=jnp.int32)
    tiles = lambda x: jnp.max(                              # noqa: E731
        jnp.pad(x, (0, Rp - R)).reshape(nt, TR), axis=1)
    strips = (tiles(reach) + SW - 1) // SW
    full = (tiles(held) > k).astype(jnp.int32)
    s, c = scores.astype(jnp.float32), cand.astype(jnp.int8)
    if s.shape[0] < Rp or s.shape[1] < Cp:
        s = jnp.pad(s[:R, :C], ((0, Rp - R), (0, Cp - C)))
    if (Rp, Cp) != (R, C):
        c = jnp.pad(c, ((0, Rp - R), (0, Cp - C)))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # two buffers of scores and of candidates, the keys, the mask's two
    vmem = TR * Cp * (2 * 4 + 2 * 1 + 4 + 2 * 1)
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k, SW=SW,
                          bits=max(Cp - 1, 1).bit_length()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nt,), in_specs=[hbm, hbm],
            out_specs=pl.BlockSpec((TR, Cp), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, TR, Cp), jnp.float32),
                            pltpu.VMEM((2, TR, Cp), jnp.int8),
                            pltpu.VMEM((TR, Cp), jnp.int32),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(100 * 2 ** 20,
                                     max(32 * 2 ** 20, vmem + 2 ** 22)))),
        interpret=_INTERPRET, name="topk_select",
    )(strips, full, s, c)
    return out[:R, :C] != 0


def mask_positions(keep, k):
    """keep [R, C] bool with at most k set a row -> positions [R, k]
    int32 ascending, -1 past the row's count."""
    C = keep.shape[1]
    col = jnp.arange(C, dtype=jnp.int32)
    # distinct integer keys: no tie for top_k to break
    val, _ = jax.lax.top_k(jnp.where(keep, C - col[None, :], 0),
                           min(int(k), C))
    pos = jnp.where(val > 0, C - val, -1)
    if pos.shape[1] < k:
        pos = jnp.pad(pos, ((0, 0), (0, k - pos.shape[1])),
                      constant_values=-1)
    return pos
