"""Attention through a learned selection, below the serving engine
(`models/keye_vl2.py`, `ops/pallas/topk_select.py`, the run kernel's
`select=`, the cache's indexer-key pool), at a small size on the CPU:
the eager model against the plain reference
(`benchmarks/configs/keye_vl2_30b_a3b_pp8_serve_reference.py`), the
exact selection against a stable sort on constructed ties, the kernel
(interpreted) under a selection against the gather reference."""
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.files import load_module  # noqa: E402

from paddle_tpu.models import keye_vl2  # noqa: E402
from paddle_tpu.models.serving_block import LearnedSelection  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas import interpret_mode  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402
from paddle_tpu.ops.pallas import topk_select as ts  # noqa: E402
from paddle_tpu.serving.kv_cache import (INDEXER_LANES,  # noqa: E402
                                         PagedKVCache)

REF = load_module("configs", "keye_vl2_30b_a3b_pp8_serve_reference")
VOCAB, TOPK = 97, 16


def small(topk=TOPK, **kw):
    return keye_vl2.KeyeArch(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        num_layers=2, num_experts=8, top_k=2, expert_width=32,
        vocab_rows=VOCAB, rope_theta=1e4, max_positions=256,
        compute_dtype="float32", selection=LearnedSelection(
            num_heads=4, head_dim=16, topk=topk, rope_dims=8,
            scale=4 ** -0.5 * 16 ** -0.5), **kw)


@functools.lru_cache(maxsize=None)
def model(topk=TOPK):
    return keye_vl2.KeyeModel(small(topk), seed=3)


def reference_cfg(arch):
    sel = arch.selection
    return dict(num_heads=arch.num_heads, num_kv_heads=arch.num_kv_heads,
                head_dim=arch.head_dim, eps=arch.eps,
                rope_theta=arch.rope_theta, top_k=arch.top_k,
                norm_topk=arch.norm_topk, idx_heads=sel.num_heads,
                idx_dim=sel.head_dim, idx_rope_dims=sel.rope_dims,
                idx_scale=sel.scale, topk=sel.topk)


def ids_of(n, seed=1):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


# ------------------------------------------- the model and the reference


def test_arch_from_the_source_s_keys():
    cfg = dict(hidden_size=2048, num_attention_heads=32,
               num_key_value_heads=4, head_dim=128, num_hidden_layers=48,
               num_experts=128, num_experts_per_tok=8,
               moe_intermediate_size=768, vocab_size=151936,
               norm_topk_prob=True, rope_theta=1e7, rms_norm_eps=1e-6,
               max_position_embeddings=262144,
               sa_config=dict(indexer_num_heads=16, indexer_head_dim=64,
                              indexer_num_kv_heads=1, topk=2048,
                              q_chunk_size=512, kv_chunk_size=512))
    arch = keye_vl2.arch_from_config(cfg, max_positions=33792)
    sel = arch.selection
    assert arch.layer_kinds == ("sparse",) * 48
    assert (sel.num_heads, sel.head_dim, sel.topk, sel.rope_dims) == (
        16, 64, 2048, 32)
    assert sel.scale == pytest.approx(1 / 32)
    assert arch.block_decoding is None and arch.window is None
    assert arch.max_positions == 33792
    shapes = keye_vl2.weight_shapes(arch)["layer"]
    per_layer = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert abs(per_layer - 625.4e6) < 0.2e6      # the issue's count
    with pytest.raises(ValueError, match="several key heads"):
        keye_vl2.arch_from_config(dict(cfg, sa_config=dict(
            cfg["sa_config"], indexer_num_kv_heads=2)))


@pytest.mark.parametrize("length", (3 * TOPK + 5, 6 * TOPK))
def test_eager_model_is_the_plain_reference(length):
    m = model()
    ids = jnp.asarray(ids_of(length), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(m.forward)(ids))
    cfg = reference_cfg(m.arch)
    want, _, _, keep = jax.jit(lambda w, i: REF.forward(w, i, cfg))(
        m.weights, ids)
    want = np.asarray(want)
    assert np.abs(got - want).max() < 1e-4 * want.std()
    # every row past the first topk selects exactly topk keys
    n = np.asarray(keep).sum(-1)
    assert (n == np.minimum(np.arange(length) + 1, TOPK)[None]).all()


def test_rows_over_a_prefix_are_the_whole_forward():
    m = model()
    cfg = reference_cfg(m.arch)
    ids = jnp.asarray(ids_of(70, seed=2), jnp.int32)
    N, S = 9, 70
    n = S - N
    z, edge, sc, keep = jax.jit(
        lambda w, i: REF.forward(w, i, cfg, last=N))(m.weights, ids)
    cache = jax.jit(lambda w, i: REF.prefix(w, i, cfg, S))(
        m.weights, ids[:n])
    z2, edge2, sc2, keep2 = jax.jit(
        lambda w, c, i: REF.rows(w, c, n, i, cfg))(
        m.weights, cache, ids[n:])
    assert np.abs(np.asarray(z2 - z)).max() < 1e-5
    assert np.abs(np.asarray(edge2 - edge)).max() < 1e-5
    # the rows' keys lie as [the cache's S rows, the N rows]
    own = np.concatenate([np.asarray(keep2)[..., :n],
                          np.asarray(keep2)[..., S:]], -1)
    assert (own == np.asarray(keep)).all()
    # fed its own selection back, the reference gives the same rows
    z3 = jax.jit(lambda w, c, i, k: REF.rows(
        w, c, n, i, cfg, select=k))(m.weights, cache, ids[n:], keep2)[0]
    assert np.abs(np.asarray(z3 - z2)).max() < 1e-6


def test_a_context_under_topk_gives_the_dense_model_s_logits():
    ids = ids_of(TOPK - 2, seed=4)
    sparse, dense = model(), model(topk=10 ** 6)
    assert (np.asarray(sparse.forward(ids))
            == np.asarray(dense.forward(ids))).all()
    longer = ids_of(3 * TOPK, seed=4)
    a, b = (np.asarray(m.forward(longer)) for m in (sparse, dense))
    assert (a[:TOPK] == b[:TOPK]).all()
    assert np.abs(a[TOPK:] - b[TOPK:]).max() > 1e-3    # it is live


def test_generate_is_greedy_over_forward():
    m = model()
    prompt = ids_of(20, seed=5)
    out = m.generate(prompt, 3)
    seq = list(prompt)
    for t in out:
        assert t == int(np.asarray(m.forward(seq)[-1]).argmax())
        seq.append(t)
    assert m.generate(prompt, 3, eos_token_id=out[1]) == out[:2]


# ----------------------------------------------------------- the selection


def stable_topk(score, cand, k):
    """Members by a stable sort of the candidates, descending."""
    keep = np.zeros_like(cand)
    for r in range(score.shape[0]):
        cols = np.flatnonzero(cand[r])
        best = cols[np.argsort(-score[r, cols], kind="stable")][:k]
        keep[r, best] = True
    return keep


def test_order_key_keeps_the_order_of_floats():
    x = np.array([-np.inf, -3e38, -2.5, -1e-30, -0.0, 0.0, 1e-30, 1.0,
                  3e38, np.inf], np.float32)
    keys = np.asarray(ts.order_key(jnp.asarray(x)))
    assert keys[4] == keys[5]                     # the two zeros are one
    order = np.delete(keys, 4)
    assert (np.diff(order.astype(np.int64)) > 0).all()


@pytest.mark.parametrize("context", (TOPK - 1, TOPK, TOPK + 1, 5 * TOPK))
def test_selection_on_constructed_ties(context):
    """Scores from a handful of values, so that the k-th largest score
    is shared by several keys on both sides of the boundary, zeros of
    both signs among them: the members are the stable sort's, equal
    scores to the lower position."""
    rng = np.random.default_rng(context)
    C = 6 * TOPK
    score = rng.integers(-2, 3, size=(7, C)).astype(np.float32) * 0.25
    score[0] = 1.0                                 # one score for all
    score[1, ::2] = -0.0
    cand = np.arange(C)[None, :] < context
    cand = np.broadcast_to(cand, score.shape).copy()
    cand[6, :] = np.arange(C) < 3                  # fewer than topk
    got = np.asarray(ts.topk_mask(jnp.asarray(score), TOPK,
                                  jnp.asarray(cand)))
    want = stable_topk(score, cand, TOPK)
    assert (got == want).all()
    assert (got[0, :min(context, TOPK)]).all()     # the lowest positions
    ref = np.asarray(REF.select(jnp.asarray(score), jnp.asarray(cand),
                                TOPK))
    assert (ref == want).all()
    at = ts.mask_positions(jnp.asarray(got), TOPK)
    for r in range(score.shape[0]):
        members = np.flatnonzero(got[r])
        assert len(members) == min(TOPK, cand[r].sum())
        assert list(np.asarray(at[r])[:len(members)]) == list(members)
        assert (np.asarray(at[r])[len(members):] == -1).all()


@pytest.mark.parametrize("C", (1, 2, 37, 64, 300))
def test_nth_column_is_the_running_count_s(C):
    rng = np.random.default_rng(C)
    flags = rng.random((6, C)) < 0.4
    flags[0] = True
    flags[1] = False
    n = rng.integers(-1, C + 2, size=6).astype(np.int32)
    got = np.asarray(ts.nth_column(jnp.asarray(flags), jnp.asarray(n)))
    for r in range(6):
        cols = np.flatnonzero(flags[r])
        if 1 <= n[r] <= len(cols):
            assert got[r] == cols[n[r] - 1], (r, n[r])
        elif n[r] < 1:
            assert got[r] == 0


def test_selection_of_random_scores_and_of_everything():
    rng = np.random.default_rng(0)
    score = rng.normal(size=(5, 200)).astype(np.float32)
    cand = np.arange(200)[None, :] <= rng.integers(0, 200, size=(5, 1))
    for k in (1, 7, 64, 200, 1000):
        got = np.asarray(ts.topk_mask(jnp.asarray(score), k,
                                      jnp.asarray(cand)))
        assert (got == stable_topk(score, cand, k)).all(), k


# ------------------------------------------- the run kernel under a selection


def paged_inputs(T, Hq, Hkv, Dh, BS, MB, S, runs, dtype, seed=0):
    rng = np.random.default_rng(seed)
    NB = S * MB + 1
    q = jnp.asarray(rng.normal(size=(T, Hq, Dh)), dtype)
    kp = jnp.asarray(rng.normal(size=(NB, BS, Hkv, Dh)), dtype)
    vp = jnp.asarray(rng.normal(size=(NB, BS, Hkv, Dh)), dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, NB)).reshape(S, MB),
                     jnp.int32)
    slot = np.full(T, -1, np.int32)
    pos = np.zeros(T, np.int32)
    at = 0
    for s, first, n in runs:
        slot[at:at + n], pos[at:at + n] = s, first + np.arange(n)
        at += n
    return q, kp, vp, bt, jnp.asarray(slot), jnp.asarray(pos)


@pytest.mark.parametrize("dtype,MB,runs", (
    (jnp.float32, 8, ((0, 50, 10), (1, 90, 9), (2, 5, 3))),
    (jnp.bfloat16, 8, ((0, 50, 10), (1, 90, 9), (2, 5, 3))),
    # 150 blocks of 16 in groups of 16: ten groups, bits 0 and 1 of
    # the word planes
    (jnp.bfloat16, 150, ((0, 2300, 12), (1, 700, 8)))),
    ids=("float32", "bfloat16", "bfloat16-long"))
def test_run_kernel_under_a_selection(dtype, MB, runs):
    T, Hq, Hkv, Dh, BS, S = 24, 8, 2, 32, 16, 3
    q, kp, vp, bt, slot, pos = paged_inputs(T, Hq, Hkv, Dh, BS, MB, S,
                                            runs, dtype)
    rng = np.random.default_rng(1)
    sel = rng.random((T, MB * BS)) < 0.4
    sel[np.arange(T), np.asarray(pos)] = True      # a row sees itself
    live = np.asarray(slot) >= 0
    with interpret_mode():
        kw = dict(max_run=8)
        got = pa.ragged_attend(q, kp, vp, bt, slot, pos,
                               select=jnp.asarray(sel), **kw)
        plain = pa.ragged_attend(q, kp, vp, bt, slot, pos, **kw)
        every = pa.ragged_attend(q, kp, vp, bt, slot, pos,
                                 select=jnp.ones_like(sel), **kw)
    want = fa.ragged_gather_reference(q, kp, vp, bt, slot, pos,
                                      select=jnp.asarray(sel))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    err = np.abs(np.asarray(got, np.float32)
                 - np.asarray(want, np.float32))[live]
    assert err.max() < tol
    # a selection of everything is today's kernel, bit for bit
    assert (np.asarray(every) == np.asarray(plain)).all()
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(plain, np.float32))[live].max() > 1e-2


def test_no_selection_traces_today_s_kernel():
    """`select=None` adds nothing to the traced call: the jaxpr of the
    kernel path is the one without the argument (as `causal_block=None`
    is held)."""
    q, kp, vp, bt, slot, pos = paged_inputs(
        16, 4, 2, 32, 16, 4, 2, ((0, 20, 9), (1, 3, 1)), jnp.float32)
    with interpret_mode():
        a = jax.make_jaxpr(lambda *x: pa.ragged_attend(*x, max_run=8))(
            q, kp, vp, bt, slot, pos)
        b = jax.make_jaxpr(lambda *x: pa.ragged_attend(
            *x, max_run=8, select=None))(q, kp, vp, bt, slot, pos)
        c = jax.make_jaxpr(lambda *x: fa.ragged_paged_attention(
            *x, max_run=8, select=None))(q, kp, vp, bt, slot, pos)
    assert str(a) == str(b) == str(c)


def test_runs_left_out_of_the_kernel_leave_as_zeros():
    """A caller that attends its one-token runs elsewhere hands the
    kernel the other runs: the rows of no run are zeros."""
    q, kp, vp, bt, slot, pos = paged_inputs(
        16, 4, 2, 32, 16, 4, 2, ((0, 20, 9), (1, 3, 1)), jnp.float32)
    runs = pa.paged_runs(slot, pos, 8)
    n, start, length, rslot, first = runs
    only = (jnp.asarray([2], jnp.int32), start,
            jnp.where(jnp.arange(16) < 2, length, 0), rslot, first)
    with interpret_mode():
        full = pa.ragged_attend(q, kp, vp, bt, slot, pos, max_run=8,
                                runs=runs)
        part = pa.ragged_attend(q, kp, vp, bt, slot, pos, max_run=8,
                                runs=only)
    assert (np.asarray(part)[:9] == np.asarray(full)[:9]).all()
    assert (np.asarray(part)[9:] == 0).all()
    assert np.abs(np.asarray(full)[9]).max() > 0


def test_select_bits_layout():
    """Group g of G blocks is bit g // NW of plane g % NW; a lane a key
    of the group."""
    T, MB, BS, G, longest = 3, 150, 16, 16, 8
    sel = np.zeros((T, MB * BS), bool)
    sel[1, 9 * G * BS + 5] = True          # group 9: plane 1, bit 1
    sel[2, 3 * G * BS + 255] = True        # group 3: plane 3, bit 0
    words, NW = pa.select_bits(jnp.asarray(sel), T, MB, BS, G, longest)
    assert NW == 8 and words.shape == ((T + longest) * NW * 2, 128)
    w = np.asarray(words).reshape(T + longest, NW, G * BS)
    assert w[1, 1, 5] == 2 and w[2, 3, 255] == 1
    assert np.count_nonzero(w) == 2


# ------------------------------------------------- the indexer-key pool


def cache(**kw):
    return PagedKVCache(
        2, 4, 16, num_blocks=12, block_size=8, max_slots=3,
        max_blocks_per_slot=4, dtype="float32", num_kv_heads=2,
        layer_kinds=("sparse", "sparse"), indexer_dim=16, **kw)


def test_indexer_pool_rides_the_k_v_s_block_table():
    kv = cache()
    assert kv.sparse_layers == [0, 1] == kv.attention_layers
    assert [tuple(p.shape) for p in kv.idx_pools] \
        == [(12, 8, INDEXER_LANES)] * 2
    pools = kv._pools()
    assert len(pools) == 6 and pools[4] is kv.idx_pools[0]
    kv._set_pools([p + 1 for p in pools])
    assert float(kv.idx_pools[1][0, 0, 0]) == 1.0
    assert float(kv.k_pools[1][0, 0, 0, 0]) == 1.0
    # K + V and a padded indexer row, two layers, float32
    assert kv.kv_bytes_per_token == 2 * (2 * 2 * 16 * 4
                                         + INDEXER_LANES * 4)
    assert len(kv.tables()) == 1           # no table of its own
    with pytest.raises(ValueError, match="indexer_dim"):
        PagedKVCache(1, 4, 16, num_blocks=4, block_size=8, max_slots=1,
                     max_blocks_per_slot=2, layer_kinds=("sparse",))


def test_truncate_carries_the_pool_and_sharing_is_refused():
    kv = cache()
    assert kv.ensure_capacity(0, 30)
    before = list(kv.block_tables[0])
    assert kv.truncate_slot(0, 9) == 2     # host ints: nothing to write
    assert list(kv.block_tables[0][:2]) == before[:2]
    assert list(kv.block_tables[0][2:]) == [0, 0]
    with pytest.raises(ValueError, match="cow_block.*sparse layer"):
        kv.cow_block(0, 0)
    with pytest.raises(ValueError, match="export_blocks.*sparse layer"):
        kv.export_blocks([1])
    kv.release_slot(0)
    assert kv.blocks_in_use == 0


def test_arch_is_frozen_and_says_what_its_layers_are():
    arch = small()
    with pytest.raises(dataclasses.FrozenInstanceError):
        arch.num_layers = 3
    assert set(arch.layer_kinds) == {"sparse"}
    assert arch.layers == arch.layer_kinds
