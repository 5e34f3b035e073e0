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

`index_scores(qI, w, kI)` is the indexer's score itself, the one place
its formula is written; `mask_positions(keep, k)` turns a mask into the
ascending list of the kept columns, for the rows that attend a GATHERED
selection. Plain
`jax.numpy`, fixed shapes: a kernel that does the same in one pass over
a row held in VMEM would take this file's place (ROADMAP queue 2 A).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def index_scores(qI, w, kI):
    """I(t, s) = sum_j w_t[j] relu(qI_t[j] . kI_s), [T, S] float32, of
    queries (qI [T, J, Di], w [T, J] float32, the scale folded in) over
    keys kI [S, Di]: operands in their own dtype, float32 sums."""
    s = jnp.einsum("tjd,sd->tjs", qI, kI,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)


def order_key(x):
    """float32 -> uint32, order preserving: `a < b` as floats iff
    `order_key(a) < order_key(b)` as unsigned ints, and `a == b` iff the
    keys are equal (the two zeros are made one first)."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x).astype(jnp.float32)
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    # a negative float's magnitude bits run the wrong way: flip them;
    # then shift the signed order onto the unsigned one
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
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
    """scores [R, C] float32, cand [R, C] bool (the columns a row may
    take) -> keep [R, C] bool: the `min(k, cand.sum())` candidates of
    each row with the largest scores, equal scores to the lower
    column. Exact."""
    k = int(k)
    if k >= scores.shape[1]:
        return cand
    keys = jnp.where(cand, order_key(scores), jnp.uint32(0))
    # a candidate's key is never 0 unless its score is -nan: a column
    # that is no candidate sorts below every one that is
    kth = kth_largest(keys, k)[:, None]
    above = keys > kth
    equal = keys == kth
    need = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    # the ties: the `need` lowest columns among the equal scores
    col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    last = nth_column(equal, need)[:, None]
    ties = equal & (col <= last) & (need > 0)[:, None]
    return (above | ties) & cand


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
