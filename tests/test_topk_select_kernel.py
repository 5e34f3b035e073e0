"""The exact top-k kernel `topk_select` (interpreted, on the CPU) against
the `jax.numpy` form it stands in for, bit for bit: ties at the k-th
score, the two zeros, -inf and a NaN whose order key is the one a
column that is no candidate takes; rows with fewer candidates than k,
with k and with none; candidates that are no prefix; reaches that end
inside a strip; row counts that end inside a tile; tiles that hold the
rows of two runs; and the rule by which the engine counts the columns
the kernel walks."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import interpret_mode
from paddle_tpu.ops.pallas import topk_select as ts

# -0.0 is one score with +0.0; this NaN's order key is 0, the key of a
# column that is no candidate
NAN_AT_ZERO = np.uint32(0xFFFFFFFF).view(np.float32)


@functools.lru_cache(maxsize=None)
def jitted(fn, k):
    return jax.jit(lambda s, c: fn(s, k, c))


def both(score, cand, k):
    s, c = jnp.asarray(score, jnp.float32), jnp.asarray(cand, bool)
    want = np.asarray(jitted(ts.topk_mask_jnp, k)(s, c))
    with interpret_mode():
        assert ts.topk_select_enabled()
        got = np.asarray(jitted(ts.topk_mask, k)(s, c))
    return got, want


def assert_same(score, cand, k):
    got, want = both(score, cand, k)
    bad = np.argwhere(got != want)
    assert not len(bad), (k, bad[:8])
    return got


def prefix(rng, R, C, lo=-1):
    return np.arange(C)[None, :] <= rng.integers(lo, C, size=(R, 1))


def tied(rng, R, C):
    """Scores from five values (many exact ties at any k-th score), a
    row of one score, zeros of both signs, -inf, the NaN of key 0."""
    score = rng.integers(-2, 3, size=(R, C)).astype(np.float32) * 0.25
    score[0] = 1.0
    score[1, ::2] = -0.0
    score[2, 3:300] = -np.inf
    score[3, 7::5] = NAN_AT_ZERO
    score[4, :] = -np.inf
    return score


@pytest.mark.parametrize("k", (1, 5, 64, 300, 700))
def test_ties_zeros_infinities_and_a_nan(k):
    rng = np.random.default_rng(k)
    R, C = 45, 1300
    cand = prefix(rng, R, C)
    cand[5] = rng.random(C) < 0.3                 # no prefix
    cand[6] = False                               # none
    cand[7] = np.arange(C) < k                    # exactly k
    cand[8] = np.arange(C) < k - 1                # fewer than k
    cand[9] = False
    cand[9, [3, 60, 900]] = True                  # a few, far apart
    score = tied(rng, R, C)
    score[9, 60] = NAN_AT_ZERO
    assert_same(score, cand, k)


@pytest.mark.parametrize("seed", range(3))
def test_random_scores_and_candidates_that_are_no_prefix(seed):
    rng = np.random.default_rng(100 + seed)
    R, C = 40, 1100
    score = rng.normal(size=(R, C)).astype(np.float32)
    cand = rng.random((R, C)) < rng.random((R, 1))
    got = assert_same(score, cand, 48)
    assert (got.sum(1) == np.minimum(48, cand.sum(1))).all()
    assert not (got & ~cand).any()


@pytest.mark.parametrize("R,C", ((1, 130), (31, 512), (33, 513), (70, 2000)),
                         ids=("one_row", "a_tile_less_one_of_a_strip",
                              "a_row_and_a_column_over", "several_tiles"))
def test_shapes_that_end_inside_a_tile_or_a_strip(R, C):
    rng = np.random.default_rng(R * C)
    score = tied(rng, max(R, 5), C)[:R]
    # reaches anywhere, most no multiple of the strip
    cand = prefix(rng, R, C)
    assert_same(score, cand, 17)


def test_tiles_that_hold_the_rows_of_two_runs():
    """The flat rows as a step lays them: runs of consecutive positions
    one after the other, a tile holding the end of one and the start of
    the next, a stretch of rows of no run; a short run's rows walk as
    far as the long run's that shares their tile."""
    rng = np.random.default_rng(5)
    C = 2500
    runs = [(0, 20), (2300, 30), (40, 1), (900, 25)]
    pos = np.concatenate([p + np.arange(n) for p, n in runs])
    R = len(pos) + 20                              # rows of no run
    cand = np.zeros((R, C), bool)
    cand[:len(pos)] = np.arange(C)[None, :] <= pos[:, None]
    score = tied(rng, R, C)
    for k in (8, 64):
        assert_same(score, cand, k)


def test_a_padded_score_buffer_is_read_in_place():
    """Scores handed over as a buffer with more rows and columns than
    the candidates (the engine's chunk scores): only its first R rows
    and C columns count, whatever the padding holds."""
    rng = np.random.default_rng(12)
    R, C = 50, 1100
    score = tied(rng, R + 40, C + 300)
    score[R:] = np.inf
    score[:, C:] = np.inf
    cand = prefix(rng, R, C)
    got, want = both(score, cand, 40)
    assert (got == want).all()
    _, sliced = both(score[:R, :C], cand, 40)
    assert (want == sliced).all()


def test_k_of_the_whole_width_keeps_every_candidate():
    rng = np.random.default_rng(9)
    score = rng.normal(size=(10, 200)).astype(np.float32)
    cand = prefix(rng, 10, 200)
    for k in (200, 500):
        got, want = both(score, cand, k)
        assert (got == cand).all() and (want == cand).all()


def test_the_kernel_is_in_the_program_only_where_enabled():
    s = jax.ShapeDtypeStruct((40, 700), jnp.float32)
    c = jax.ShapeDtypeStruct((40, 700), jnp.bool_)
    select = functools.partial(ts.topk_mask, k=16)
    assert not ts.topk_select_enabled()            # the CPU backend
    assert "topk_select" not in str(jax.make_jaxpr(
        lambda s, c: select(s, cand=c))(s, c))
    with interpret_mode():
        assert "topk_select" in str(jax.make_jaxpr(
            lambda s, c: select(s, cand=c))(s, c))


def brute_walk(cand, rows, strip):
    """Columns walked, from the candidates themselves: a tile of `rows`
    rows walks its largest candidate column + 1, in whole strips."""
    R, C = cand.shape
    total = 0
    for t in range(0, R, rows):
        cols = np.flatnonzero(cand[t:t + rows].any(0))
        reach = cols[-1] + 1 if len(cols) else 0
        total += rows * -(-reach // strip) * strip
    return total


@pytest.mark.parametrize("seed", range(3))
def test_cols_walked_is_the_count_over_the_candidates(seed):
    rng = np.random.default_rng(seed)
    R, C = 75, 3000
    cand = prefix(rng, R, C)
    cand[rng.random(R) < 0.3] = False
    cand[3] = rng.random(C) < 0.01
    reach = [np.flatnonzero(r)[-1] + 1 if r.any() else 0 for r in cand]
    strip = ts.strip_of(C)
    assert strip == 1024                           # 3000 -> 3072 = 3 x 1024
    assert ts.cols_walked(reach, C) == brute_walk(cand, ts.ROWS, strip)
    assert ts.cols_walked([0] * R, C) == 0
    assert ts.cols_walked([1], C) == ts.ROWS * strip
    assert ts.cols_walked([strip, strip + 1], C) == ts.ROWS * 2 * strip
    # the longest strip that divides the width in whole lane tiles
    assert [ts.strip_of(w) for w in (33792, 2048, 1536, 256, 160, 1300)] \
        == [1024, 1024, 512, 256, 256, 128]


def test_the_walk_of_a_made_up_plan_and_its_reader():
    """Two decode tokens (positions 9, 700) and a chunk of 40 from 1000,
    on a table of 4096 columns, 64 flat rows and 4 slots: the chunk rows
    2..41 reach 1001..1040 (two tiles of 32 rows, 2048 columns each),
    the decode rows 10 and 701 (one tile, 1024 columns)."""
    import os
    import sys
    import types

    from paddle_tpu.serving import engine as engine_mod
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    from harness.files import load_module
    assert (ts.ROWS, ts.strip_of(4096)) == (32, 1024)
    plan = engine_mod.Plan(decode=[(0, [1], 9), (1, [1], 700)],
                           prefills=[(2, [1] * 40, 1000, False)], expired=[])
    work, _ = engine_mod._sparse_work(plan, 16, 128, 64, 4, 4096)
    walked = 2 * 32 * 2048 + 32 * 1024
    assert work["idx_cols_walked"] == walked
    assert work["idx_cols_width"] == (64 + 4) * 4096

    reader = load_module("layer_metrics", "sparse_attn.select_walk_pct")
    logged = []
    ctx = lambda flight: types.SimpleNamespace(            # noqa: E731
        flight=flight, log=logged.append)
    flight = [dict(work), dict(idx_cols_walked=0, idx_cols_width=278528),
              dict(sparse_rows_decode=0)]                  # no such field
    assert reader.read(ctx(flight)) == pytest.approx(
        100.0 * walked / (2 * 278528))
    assert "2 steps" in logged[-1]
    # a program that does not record the fields (the parent): nothing
    assert reader.read(ctx([dict(sparse_rows_decode=3)])) is None
    assert reader.read(ctx([])) is None
    import json
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "sparse_attn.select_walk_pct")
    assert entry == dict(
        name="sparse_attn.select_walk_pct", unit="%", better="lower",
        source="program_counter", layer="sparse_attn",
        moves="serve_tokens_per_s",
        workloads=["serve_keye_vl2_30b_a3b_longctx"])
