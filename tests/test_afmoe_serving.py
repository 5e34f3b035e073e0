"""AFMoE (Trinity) through the normal serving path, at a small size on
the CPU: the model's eager forward and the engine's mixed step (paged
cache with window and full tables, grouped-query paged kernel, dropless
share-aware experts) against the plain reference of
`benchmarks/configs/trinity_large_ep8_serve_reference.py`."""
import contextlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.files import load_module  # noqa: E402

from paddle_tpu.models import afmoe  # noqa: E402
from paddle_tpu.ops.pallas import interpret_mode  # noqa: E402

REF = load_module("configs", "trinity_large_ep8_serve_reference")
VOCAB = 96


def small(dtype="float32", held=4, rank=0, window=16):
    """5 layers of the three kinds: dense/sliding, 3 x moe/sliding,
    moe/full; 4 query heads on 2 KV heads of 16; 16 experts top-2."""
    return afmoe.make_arch(
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        num_dense_layers=1, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, window=window, dense_width=128, vocab_rows=VOCAB,
        max_positions=256, compute_dtype=dtype,
        moe=dict(num_experts=16, top_k=2, expert_width=32,
                 experts_held=held, expert_rank=rank, route_scale=2.448))


def ref_cfg(arch, **over):
    cfg = dict(num_heads=arch.num_heads, num_kv_heads=arch.num_kv_heads,
               head_dim=arch.head_dim, window=arch.window, eps=arch.eps,
               rope_theta=arch.rope_theta, layer_kinds=arch.layer_kinds,
               top_k=arch.moe.top_k, route_scale=arch.moe.route_scale,
               expert_rank=arch.moe.expert_rank)
    return dict(cfg, **over)


def reference(model, seq, last=None, ref=REF, **over):
    import jax.numpy as jnp
    return np.asarray(ref.logits(
        model.weights, jnp.asarray(seq, jnp.int32),
        ref_cfg(model.arch, **over), last=last)[0])


def margins(model, prompt, answer, **over):
    """The engine's greedy tokens against the reference, teacher-forced:
    how far under the reference's largest logit each lies, in standard
    deviations of its position's logits."""
    z = reference(model, list(prompt) + list(answer[:-1]),
                  last=len(answer), **over)
    got = z[np.arange(len(answer)), np.asarray(answer)]
    return (z.max(-1) - got) / z.std(-1)


def row_errors(rows, z):
    """The driver's statistic: rms of (row - reference row) in standard
    deviations of the reference row, a position."""
    return np.sqrt(((rows - z) ** 2).mean(-1)) / z.std(-1)


def serve(model, prompts, new_tokens, *, dtype="float32", interpret=False,
          budget=16, watch=None):
    """-> (engine, each request's tokens, each request's rows of logits
    as `engine.sample_logits` held them when a token was taken)."""
    from paddle_tpu.serving.engine import ServingEngine
    with (interpret_mode() if interpret else contextlib.nullcontext()):
        eng = ServingEngine(model, max_slots=3, block_size=4,
                            num_blocks=80, max_seq_len=128,
                            token_budget=budget, cache_dtype=dtype)
        reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        rows, slots = [[] for _ in reqs], [-1] * len(reqs)
        while eng.scheduler.has_work:
            had = [len(r.output) for r in reqs]
            eng.step()
            for i, r in enumerate(reqs):
                slots[i] = r.slot if r.slot >= 0 else slots[i]
                if len(r.output) > had[i]:
                    rows[i].append(np.asarray(
                        eng.sample_logits[slots[i]]))
            if watch is not None:
                watch(eng)
    return eng, [list(r.output) for r in reqs], \
        [np.stack(r) for r in rows]


PROMPTS = [np.random.default_rng(1).integers(0, VOCAB, n).tolist()
           for n in (40, 7, 23)]


# ------------------------------------------------- model and reference


@pytest.mark.parametrize("rank", (0, 3))
def test_eager_forward_is_the_reference(rank):
    m = afmoe.AfmoeForGeneration(small(rank=rank), seed=3)
    ids = np.random.default_rng(0).integers(0, VOCAB, 50)
    want = reference(m, ids)
    got = np.asarray(m.forward(ids))
    assert np.abs(got - want).max() < 2e-6
    assert want.std() > 0.05


def test_bf16_forward_stays_near_the_reference():
    m = afmoe.AfmoeForGeneration(small("bfloat16"), seed=3)
    ids = np.random.default_rng(0).integers(0, VOCAB, 50)
    want = reference(m, ids)
    got = np.asarray(m.forward(ids), np.float32)
    # a routing near-tie may flip a position; most lie within rounding
    err = np.abs(got - want).max(-1) / want.std()
    assert np.median(err) < 0.15


@pytest.mark.parametrize("interpret", (False, True),
                         ids=("gather", "kernels"))
def test_engine_past_the_window_is_the_reference(interpret):
    """Prefill in chunks of 16, then decode, contexts up to 64 against
    a window of 16: through the paged cache (window blocks released),
    the run kernel and the ragged expert matmuls (interpreted), every
    greedy token is the float32 reference's largest logit, and the rows
    of logits the engine keeps are the reference's."""
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    eng, outs, rows = serve(m, PROMPTS, 24, interpret=interpret)
    assert eng.step_compile_count() == 1
    assert eng.kv.blocks_in_use == 0
    assert eng.kv.blocks_released_behind_window > 0
    for p, out, r in zip(PROMPTS, outs, rows):
        assert len(out) == 24 and r.shape == (24, VOCAB)
        assert margins(m, p, out).max() == 0.0
        assert r.argmax(-1).tolist() == out
        z = reference(m, p + out[:-1], last=24)
        assert row_errors(r, z).max() < 2e-5


def test_engine_bf16_by_margin():
    m = afmoe.AfmoeForGeneration(small("bfloat16"), seed=3)
    _, outs, _ = serve(m, PROMPTS, 24, dtype="bfloat16", interpret=True)
    worst = max(margins(m, p, o).max() for p, o in zip(PROMPTS, outs))
    # hidden 64 in bf16 is coarse (logit sigma 0.16): the chip cell's
    # tolerance is set at its own widths; here, no wrong computation
    assert worst < 0.6


def test_one_chunk_and_many_chunks_agree():
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    _, a, _ = serve(m, PROMPTS[:1], 6, budget=16)
    _, b, _ = serve(m, PROMPTS[:1], 6, budget=64)
    assert a == b


# ------------ what the comparison must catch, and what it must let pass


CONTROLS = load_module("configs", "trinity_large_ep8_serve_controls")
DRIVERS = load_module("drivers", "serve_frontend_afmoe")
FAULT_ERR = 0.01       # sigma (rms of a row); the float32 engine: 2e-5


@pytest.mark.parametrize("kind", ("bf16_operands", "fp8_operands",
                                  "bf16_accumulate"))
def test_logits_catch_a_lower_precision(kind):
    """The controls' lower-precision copies of the reference (the chip
    cell runs the same ones): at hidden 64 even bfloat16 operands are
    far from float32; each coarser than the one before."""
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    seq = PROMPTS[0] + PROMPTS[2]
    z = reference(m, seq, last=24)
    low = reference(m, seq, last=24,
                    ref=CONTROLS.low_precision_reference(kind))
    err = np.median(row_errors(low, z))
    print(kind, err)
    lo, hi = {"bf16_operands": (0.003, 0.02), "fp8_operands": (0.05, 1),
              "bf16_accumulate": (0.01, 0.2)}[kind]
    assert lo < err < hi


def test_logits_catch_a_window_one_block_short():
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    short = afmoe.AfmoeForGeneration(small(window=12), weights=m.weights)
    _, (out,), (rows,) = serve(short, PROMPTS[:1], 24)
    # against the reference at the window as published, along the
    # faulty engine's own tokens
    err = row_errors(rows, reference(m, PROMPTS[0] + out[:-1], last=24))
    assert err.min() > FAULT_ERR
    z = reference(short, PROMPTS[0] + out[:-1], last=24)
    assert row_errors(rows, z).max() < 2e-5


def test_logits_catch_a_dropped_pair():
    """One (token, expert) pair a step dropped in the last expert
    layer, the first held choice of the step's first token: alone, a
    request's decoded position IS that token."""
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    undo = CONTROLS.drop_a_pair(afmoe, 4, m.arch.moe.experts_held,
                                m.arch.moe.expert_rank)
    try:
        _, (out,), (rows,) = serve(m, PROMPTS[:1], 24)
    finally:
        undo()
    err = row_errors(rows, reference(m, PROMPTS[0] + out[:-1], last=24))
    # a position is hit where its token chose a held expert in the last
    # layer (4 of 16 held, top-2): some are, and those by far
    assert (err > FAULT_ERR).sum() >= 3
    assert (err < 2e-5).sum() >= 3 or err.min() > FAULT_ERR


def _driver_for(model, **limits):
    import types
    env = types.SimpleNamespace(
        config_name="trinity_large_ep8_serve", log=lambda m: None,
        config={"reference": dict(
            {"logit_err_sigmas": 1e-4, "tie_gap": 0.05, "max_passes": 8},
            **limits)})
    d = DRIVERS.Driver(env)
    d.loop.close()
    d.model = model
    return d


def _swap(L, S, *at):
    """(out, into) int32 [L, S] with the (layer, position, out, into)s
    of `at` set."""
    import jax.numpy as jnp
    out = np.full((L, S), -1, np.int32)
    into = out.copy()
    for layer, pos, o, i in at:
        out[layer, pos], into[layer, pos] = o, i
    return jnp.asarray(out), jnp.asarray(into)


def _closest(scores, held, layers):
    """The (gap, layer, position, out, into) of the closest chosen /
    unchosen pair with one of them held here, over `layers`; top-2, so
    the window is ranks 0..3 and the first two are chosen."""
    best = None
    for l in layers:
        for p in range(scores.shape[1]):
            for o in (0, 1):
                for i in (2, 3):
                    if held[l, p, o] or held[l, p, i]:
                        g = float(scores[l, p, o] - scores[l, p, i])
                        if best is None or g < best[0]:
                            best = (g, l, p, o, i)
    return best


def test_swap_takes_another_expert_at_one_position():
    import jax.numpy as jnp
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    seq = jnp.asarray(PROMPTS[0], jnp.int32)
    cfg = ref_cfg(m.arch)
    z0, scores, held = (np.asarray(a) for a in
                        REF.logits(m.weights, seq, cfg))
    assert scores.shape == held.shape == (4, 40, 4)
    assert (np.diff(scores, axis=-1) <= 0).all()    # ranked
    assert 0.1 < held.mean() < 0.45                 # 4 of 16 held
    _, layer, p, o, i = _closest(scores, held, range(4))
    z1 = np.asarray(REF.logits(m.weights, seq, cfg,
                               swap=_swap(4, 40, (layer, p, o, i)))[0])
    assert np.array_equal(z0[:p], z1[:p])          # causal
    assert np.abs(z0[p] - z1[p]).max() > 1e-4      # another answer
    none = REF.logits(m.weights, seq, cfg, swap=_swap(4, 40))
    assert np.array_equal(np.asarray(none[0]), z0)
    # the first ranked out, the fourth in (held here, so that it
    # shows): not only the last chosen and the first not
    layer, p = np.argwhere(held[:, :, 3])[-1]
    z2 = np.asarray(REF.logits(m.weights, seq, cfg,
                               swap=_swap(4, 40, (layer, p, 0, 3)))[0])
    assert np.array_equal(z0[:p], z2[:p])
    assert np.abs(z0[p] - z2[p]).max() > 1e-4


def test_compare_holds_a_near_tie_against_the_other_answers():
    """Rows computed with another expert at one near-tie (and, below
    it, at a second one that only the swapped pass shows) are correct
    when the gap is under `tie_gap`, not correct when it is not; rows
    that are off elsewhere stay off."""
    import jax.numpy as jnp
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    prompt, answer = PROMPTS[0][:30], PROMPTS[0][30:]
    cfg = ref_cfg(m.arch)
    seq = jnp.asarray(prompt + answer[:-1], jnp.int32)
    _, scores, held = (np.asarray(a) for a in REF.logits(
        m.weights, seq, cfg, last=len(answer)))
    gap, layer, p, o, i = _closest(scores, held, range(3))
    # that position the last one compared: with 40 keys (4096 in the
    # cell) a swapped position moves the ones after it too
    answer = answer[:p + 1]
    seq, N = seq[:len(prompt) + p], p + 1
    S = len(prompt) + p
    first = (int(layer), o, i)
    # ... and the last layer's closest pair too, in the world after it
    _, s1, h1 = (np.asarray(a) for a in REF.logits(
        m.weights, seq, cfg, last=N,
        swap=_swap(4, S, (layer, S - 1, o, i))))
    last = _closest(s1[:, -1:], h1[:, -1:], [3])
    if last is None:
        pytest.skip("the last layer's edge experts are not held here")
    gap3, _, _, o3, i3 = last
    rows = np.array(REF.logits(
        m.weights, seq, cfg, last=N,
        swap=_swap(4, S, (layer, S - 1, o, i), (3, S - 1, o3, i3)))[0])
    wide = max(gap, gap3) * 1.01
    got = _driver_for(m, tie_gap=wide, max_passes=40).compare(
        prompt, answer, rows)
    assert got["err"].max() < 1e-5 and got["passes"] >= 3
    assert got["swaps"][p] == (first, (3, o3, i3))
    assert all(not c for n, c in enumerate(got["swaps"]) if n != p)
    tight = _driver_for(m, tie_gap=gap * 0.5).compare(
        prompt, answer, rows)
    assert tight["err"][p] > 1e-3 and tight["passes"] == 1
    assert np.delete(tight["err"], p).max(initial=0) < 1e-5
    rows[0] += 0.01 * np.random.default_rng(0).standard_normal(VOCAB)
    off = _driver_for(m, tie_gap=wide).compare(prompt, answer, rows)
    assert off["err"][0] > 1e-3 and off["passes"] <= 8


# ------------------------------------------------------------- routing


def test_selection_bias_selects_and_does_not_weigh():
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe_utils import route_sigmoid_topk
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((32, 24)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((24, 8)) / 5, jnp.float32)
    zero = jnp.zeros((8,), jnp.float32)
    bias = zero.at[5].set(10.0)               # expert 5 always chosen
    idx0, w0 = route_sigmoid_topk(x, wr, zero, 2, route_scale=2.448)
    idx1, w1 = route_sigmoid_topk(x, wr, bias, 2, route_scale=2.448)
    assert (np.asarray(idx1) == 5).any(-1).all()
    assert not (np.asarray(idx0) == 5).any(-1).all()
    # weights are the sigmoid scores of the chosen, normalised, scaled:
    # the bias is not in them
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(wr)))
    got = np.take_along_axis(s, np.asarray(idx1), 1)
    want = got / got.sum(-1, keepdims=True) * 2.448
    np.testing.assert_allclose(np.asarray(w1), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 2.448, rtol=1e-5)


def test_shares_add_up_to_the_uncut_layer():
    """The eight ranks' expert parts, the shared expert counted once,
    are the uncut reference's MoE layer."""
    import jax.numpy as jnp
    full = afmoe.AfmoeForGeneration(small(held=16), seed=4)
    lw = full.weights["layers"][2]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 64)),
                    jnp.float32)
    valid = jnp.ones((40,), bool)
    shared = afmoe._swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"])
    total = shared
    local_pairs = 0
    for rank in range(8):
        arch = small(held=2, rank=rank)
        part = dict(lw, **{n: lw[n][2 * rank:2 * rank + 2]
                           for n in ("e_gate", "e_up", "e_down")})
        m, st = afmoe.moe_ffn(arch, part, x, valid)
        total = total + (m - shared)
        local_pairs += int(st["pairs_local"])
        assert int(st["pairs_total"]) == 80
    assert local_pairs == 80                   # every pair, once
    uncut, _ = afmoe.moe_ffn(full.arch, lw, x, valid)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-6)
    # and the plain reference's layer, through a one-layer model
    one = afmoe.make_arch(
        layer_types=["full_attention"], num_dense_layers=0,
        **{k: v for k, v in full.arch.__dict__.items()
           if k not in ("layers", "moe")},
        moe=dict(full.arch.moe.__dict__))
    assert one.layers[0].ffn == afmoe.MOE


@pytest.mark.parametrize("interpret", (False, True),
                         ids=("ragged_dot", "kernel"))
def test_dropless_all_tokens_on_one_expert(interpret):
    """512 tokens whose every choice is one held expert: nothing is
    dropped, whatever a capacity factor would have allowed."""
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe_utils import dropless_expert_ffn
    rng = np.random.default_rng(0)
    T, D, F, Eh = 512, 32, 48, 4
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((Eh, D, F)) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((Eh, F, D)) / 6, jnp.float32)
    idx = jnp.full((T, 1), 2 + Eh, jnp.int32)          # rank 1's expert 2
    wts = jnp.ones((T, 1), jnp.float32)
    with (interpret_mode() if interpret else contextlib.nullcontext()):
        out, st = dropless_expert_ffn(x, idx, wts, jnp.ones((T,), bool),
                                      wg, wu, wd, expert_rank=1)
    h = np.asarray(x) @ np.asarray(wg[2])
    want = (h / (1 + np.exp(-h)) * (np.asarray(x) @ np.asarray(wu[2]))) \
        @ np.asarray(wd[2])
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-4)
    assert int(st["pairs_local"]) == T == int(st["max_expert_pairs"])
    assert int(st["experts_hit"]) == 1
    # the same tokens routed to another rank's expert cost and add nothing
    out, st = dropless_expert_ffn(x, idx, wts, jnp.ones((T,), bool),
                                  wg, wu, wd, expert_rank=0)
    assert not np.asarray(out).any() and int(st["pairs_local"]) == 0


@pytest.mark.parametrize("swiglu", (False, True))
@pytest.mark.parametrize("sizes", ([3, 0, 9, 1], [0, 0, 0, 0],
                                   [40, 0, 0, 2]))
def test_ragged_expert_matmul_kernel(sizes, swiglu):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as gm
    rng = np.random.default_rng(0)
    E, D, F, bm = len(sizes), 64, 48, 8
    NT = gm.ragged_num_tiles(sum(sizes) + 5, E, bm)
    row_start, te, nu = gm.ragged_layout(jnp.asarray(sizes, jnp.int32),
                                         NT, bm)
    x = np.zeros((NT * bm, D), np.float32)
    w = rng.standard_normal((E, D, F)).astype(np.float32) / 8
    w2 = rng.standard_normal((E, D, F)).astype(np.float32) / 8
    want, rows = np.zeros((NT * bm, F), np.float32), []
    for e, n in enumerate(sizes):
        r0 = int(row_start[e])
        assert r0 % bm == 0
        x[r0:r0 + n] = rng.standard_normal((n, D))
        y = x[r0:r0 + n] @ w[e]
        if swiglu:
            y = y / (1 + np.exp(-y)) * (x[r0:r0 + n] @ w2[e])
        want[r0:r0 + n] = y
        rows += list(range(r0, r0 + n))
    assert int(nu[0]) == sum(-(-n // bm) for n in sizes)
    for ctx in (interpret_mode(), contextlib.nullcontext()):
        with ctx:
            got = np.asarray(gm.ragged_expert_matmul(
                jnp.asarray(x), jnp.asarray(w), te, nu,
                jnp.asarray(w2) if swiglu else None, block_m=bm,
                block_d=32))
        assert np.abs(got[rows] - want[rows]).max(initial=0) < 1e-4


# ---------------------------------------------------------- the kernel


@pytest.mark.parametrize("Hkv,Gq,Dh,BS,window,max_run", [
    (2, 1, 16, 8, None, None),      # as before: heads equal, no window
    (2, 3, 16, 8, None, None),      # 3 query heads a KV head
    (2, 3, 16, 8, 16, None),        # ... and a window
    (2, 3, 16, 8, 20, 8),           # runs cut at 8 tokens
    (8, 6, 128, 16, 32, 16),        # Trinity's heads
    (2, 1, 16, 8, 5, 4),            # a window under a block
])
def test_run_kernel_grouped_queries_and_window(Hkv, Gq, Dh, BS, window,
                                               max_run):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas.flash_attention import \
        ragged_gather_reference
    rng = np.random.default_rng(0)
    T, S, MB, NB = 40, 4, 12, 60
    kp, vp = (jnp.asarray(rng.standard_normal((NB, BS, Hkv, Dh)),
                          jnp.float32) for _ in range(2))
    bt = rng.permutation(np.arange(1, NB))[:S * MB].reshape(
        S, MB).astype(np.int32)
    # slot 0: a chunk of 21 from 50; slot 1 decodes at 77; slot 2 a
    # chunk of 9 from 0; slot 3 decodes at 3; the rest is padding
    slot = [0] * 21 + [1] + [2] * 9 + [3]
    pos = list(range(50, 71)) + [77] + list(range(9)) + [3]
    slot += [-1] * (T - len(slot))
    pos += [0] * (T - len(pos))
    slot, pos = jnp.asarray(slot, jnp.int32), jnp.asarray(pos, jnp.int32)
    q = jnp.asarray(rng.standard_normal((T, Hkv * Gq, Dh)), jnp.float32)
    want = ragged_gather_reference(q, kp, vp, jnp.asarray(bt), slot, pos,
                                   window=window)
    want = np.where(np.asarray(slot >= 0)[:, None, None], want, 0)
    # a window table has let go of what lies behind each slot's window
    released = bt.copy()
    if window is not None:
        for s_, first in ((0, 50), (1, 77), (2, 0), (3, 3)):
            released[s_, :max(first - window + 1, 0) // BS] = 0
    with interpret_mode():
        got = pa.ragged_attend(q, kp, vp, jnp.asarray(released), slot, pos,
                               window=window, max_run=max_run)
    assert np.abs(np.asarray(got) - want).max() < 2e-5


def test_runs_are_cut_at_max_run():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import paged_runs
    slot = jnp.asarray([0] * 10 + [1] + [-1] * 5, jnp.int32)
    pos = jnp.asarray(list(range(20, 30)) + [7] + [0] * 5, jnp.int32)
    n, start, length, rslot, rpos = (np.asarray(a) for a in paged_runs(
        slot, pos, max_run=4))
    assert n[0] == 4
    assert list(start[:4]) == [0, 4, 8, 10]
    assert list(length[:4]) == [4, 4, 2, 1] and not length[4:].any()
    assert list(rslot[:4]) == [0, 0, 0, 1]
    assert list(rpos[:4]) == [20, 24, 28, 7]
    whole = paged_runs(slot, pos)
    assert np.asarray(whole[0])[0] == 2


# ----------------------------------------------------------- the cache


def test_window_table_releases_and_counts_both_kinds():
    from paddle_tpu.serving.kv_cache import PagedKVCache
    kv = PagedKVCache(3, 4, 8, num_kv_heads=2, num_blocks=20, block_size=4,
                      max_slots=2, max_blocks_per_slot=16, dtype="float32",
                      layer_kinds=("sliding", "sliding", "full"), window=8,
                      num_window_blocks=6)
    assert [p.shape for p in kv._pools()] == \
        [(6, 4, 2, 8)] * 4 + [(20, 4, 2, 8)] * 2
    assert len(kv.tables()) == 2
    held = []
    for n in range(1, 41):
        assert kv.ensure_capacity(0, n)
        kv.slot_lens[0] = n
        kv.release_behind_window(0)
        held.append(kv.window_allocator.num_used)
        # the columns the next query can reach are held, the rest NULL
        first = max(n - 8 + 1, 0) // 4
        assert (kv.window_tables[0, :first] == 0).all()
        assert (kv.window_tables[0, first:-(-n // 4)] > 0).all()
    assert max(held) <= 8 // 4 + 1 and kv.allocator.num_used == 10
    assert kv.blocks_released_behind_window == 10 - held[-1]
    assert kv.window_held_tokens() == (40 - 4 * (10 - held[-1]), 40)
    # the second slot is held to what BOTH pools can still give
    assert kv.fit_tokens(1) == min(9 * 4, (5 - held[-1]) * 4)
    assert not kv.ensure_capacity(1, kv.fit_tokens(1) + 1)
    assert kv.ensure_capacity(1, kv.fit_tokens(1))
    kv.release_slot(0)
    kv.release_slot(1)
    assert kv.blocks_in_use == 0 and kv.window_allocator.invariant_ok
    assert kv.blocks_total == 26


def test_window_blocks_stay_bounded_while_a_context_grows():
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    peaks = []
    eng, _, _ = serve(m, PROMPTS, 24, watch=lambda e: peaks.append(
        (e.kv.window_allocator.num_used, e.kv.allocator.num_used)))
    # 3 slots x (window 16 + a step's 16 tokens) / block 4, + 1 each
    assert max(w for w, _ in peaks) <= 3 * (32 // 4 + 1)
    assert max(f for _, f in peaks) > max(w for w, _ in peaks)
    assert eng.kv.blocks_in_use == 0


def test_preemption_frees_both_kinds_and_resumes():
    from paddle_tpu.serving.engine import ServingEngine
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    _, want, _ = serve(m, PROMPTS, 12)
    # a full-layer pool too small for the three contexts together
    eng = ServingEngine(m, max_slots=3, block_size=4, num_blocks=22,
                        max_seq_len=128, token_budget=16,
                        cache_dtype="float32")
    reqs = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    eng.run()
    assert eng.scheduler.preemption_count > 0
    assert [list(r.output) for r in reqs] == want
    assert eng.kv.blocks_in_use == 0


@pytest.mark.parametrize("option", (
    dict(prefix_caching=True), dict(draft_k=2), dict(kv_dtype="int8"),
    dict(sparse_blocks=4), dict(ticks_per_dispatch=4),
    dict(max_adapters=2)))
def test_what_the_block_path_does_not_build_is_refused(option):
    from paddle_tpu.serving.engine import ServingEngine
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    with pytest.raises(ValueError, match="window layers|GPT step"):
        ServingEngine(m, max_slots=2, block_size=4, num_blocks=20,
                      max_seq_len=64, token_budget=16, **option)


# -------------------------------------------------- spans and counters


def test_flight_record_counts_pairs_tokens_and_blocks():
    from paddle_tpu.serving import tracing
    from paddle_tpu.serving.engine import ServingEngine
    m = afmoe.AfmoeForGeneration(small(), seed=3)
    eng = ServingEngine(m, max_slots=3, block_size=4, num_blocks=80,
                        max_seq_len=128, token_budget=16,
                        cache_dtype="float32")
    tracing.enable()
    try:
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=8)
        eng.run()
    finally:
        tracing.disable()
    recs = list(eng.flight.records)
    fields = ("moe_pairs_total", "moe_pairs_local", "moe_experts_hit",
              "moe_max_expert_pairs", "kv_tokens_read_window",
              "kv_tokens_read_full", "attn_pairs_window",
              "attn_pairs_full", "kv_blocks_in_use_window",
              "kv_blocks_in_use_full",
              "kv_blocks_released_behind_window", "kv_tokens_held_window",
              "kv_tokens_context")
    for r in recs:
        assert set(fields) <= set(r)
        tokens = r["prefill_tokens"] + r["decode_tokens"]
        # 4 expert layers x top-2
        assert r["moe_pairs_total"] == tokens * 2 * 4
        assert 0 <= r["moe_pairs_local"] <= r["moe_pairs_total"]
        assert r["moe_experts_hit"] <= 4 * 4
        assert r["kv_tokens_read_window"] <= r["kv_tokens_read_full"]
        assert r["attn_pairs_window"] <= r["attn_pairs_full"]
        assert r["kv_tokens_held_window"] <= r["kv_tokens_context"]
        assert r["kv_blocks_in_use"] == r["kv_blocks_in_use_window"] \
            + r["kv_blocks_in_use_full"]
        # what the paged kernel computes over the layers of both kinds
        kinds = eng._block.arch.layer_kinds
        assert r["attn_logits_useful"] == eng._block.arch.num_heads * (
            kinds.count("full") * r["attn_pairs_full"]
            + kinds.count("sliding") * r["attn_pairs_window"])
        assert r["attn_logits_issued"] >= r["attn_logits_useful"] > 0
    assert sum(r["kv_blocks_released_behind_window"] for r in recs) == \
        eng.kv.blocks_released_behind_window > 0
    local = sum(r["moe_pairs_local"] for r in recs)
    total = sum(r["moe_pairs_total"] for r in recs)
    assert 0.1 < local / total < 0.45          # 4 of 16 experts held
    # the first step is one chunk of 16 from position 0: 16 keys read,
    # 16 * 17 / 2 pairs, of either kind (the window is 16)
    assert recs[0]["kv_tokens_read_window"] == 16
    assert recs[0]["attn_pairs_window"] == recs[0]["attn_pairs_full"] == 136
    txt = eng._step_fn._jitted.trace(
        *eng.example_step_args()).lower().as_text(debug_info=True)
    for scope in ("moe_router", "moe_experts", "moe_shared", "attn_window",
                  "attn_full"):
        assert scope in txt, scope
