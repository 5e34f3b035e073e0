"""SDAR-MoE at a small size on the CPU: the model's eager forward and
`generate()` (block diffusion in plain loops) against the plain
reference of `benchmarks/configs/sdar_30b_a3b_pp8_serve_reference.py`,
the softmax router, the unmask rules, and the block-causal rule of the
paged kernel (interpreted) and of its fallback against a dense mask."""
import functools
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.files import load_module  # noqa: E402

from paddle_tpu.models import sdar_moe  # noqa: E402
from paddle_tpu.models.serving_block import BlockDecoding  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas import interpret_mode  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402

REF = load_module("configs", "sdar_30b_a3b_pp8_serve_reference")
DRIVERS = load_module("drivers", "serve_frontend_sdar")
VOCAB, MASK = 97, 96


def small(rule="low_confidence_static", threshold=0.9, steps=4, L=4,
          dtype="float32"):
    return sdar_moe.SdarMoeArch(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        num_layers=2, num_experts=8, top_k=2, expert_width=32,
        vocab_rows=VOCAB, rope_theta=1e4, max_positions=256,
        compute_dtype=dtype, block_decoding=BlockDecoding(
            block_length=L, mask_token_id=MASK, denoising_steps=steps,
            rule=rule, threshold=threshold))


@functools.lru_cache(maxsize=None)
def model(**kw):
    return sdar_moe.SdarMoeForGeneration(small(**kw), seed=3)


def row_errors(rows, z):
    return np.sqrt(((rows - z) ** 2).mean(-1)) / z.std(-1)


def test_eager_forward_is_the_reference():
    import jax.numpy as jnp
    m = model()
    ids = np.random.default_rng(0).integers(0, VOCAB, 50)
    want = np.asarray(REF.logits(m.weights, jnp.asarray(ids, jnp.int32),
                                 DRIVERS.reference_cfg(m.arch)))
    got = np.asarray(m.forward(ids))
    assert row_errors(got, want).max() < 2e-4
    assert want.std() > 0.05


def test_the_mask_is_block_causal_and_not_causal():
    """Row i depends on the later rows of its own block and on nothing
    past the block's end."""
    m = model()
    ids = np.random.default_rng(1).integers(0, VOCAB, 12)
    z = np.asarray(m.forward(ids))
    later = ids.copy()
    later[9] = (later[9] + 1) % VOCAB           # inside the last block
    z2 = np.asarray(m.forward(later))
    assert np.array_equal(z[:8], z2[:8])
    assert not np.allclose(z[8], z2[8])         # row 8 sees position 9


def test_arch_from_the_source_s_keys():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar_30b_a3b_pp8_serve.json")) as f:
        cfg = json.load(f)
    arch = sdar_moe.arch_from_config(cfg["source_config"],
                                     generation=cfg["generation"])
    assert (arch.num_layers, arch.hidden_size, arch.num_heads,
            arch.num_kv_heads, arch.head_dim) == (48, 2048, 32, 4, 128)
    assert (arch.num_experts, arch.top_k, arch.expert_width,
            arch.vocab_rows) == (128, 8, 768, 151936)
    assert arch.layer_kinds == ("full",) * 48 and arch.window is None
    bd = arch.block_decoding
    assert (bd.block_length, bd.mask_token_id, bd.rule, bd.threshold,
            bd.denoising_steps) == (4, 151669, "low_confidence_dynamic",
                                    0.9, 4)
    cut = sdar_moe.arch_from_config(cfg, generation=cfg["generation"])
    assert cut.num_layers == 6 and cut.max_positions == 16896
    # every width of the cut is the source's
    for key, value in cfg["source_config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    with pytest.raises(ValueError, match="dense layers"):
        sdar_moe.arch_from_config(
            dict(cfg, mlp_only_layers=[0]), generation=cfg["generation"])


@pytest.mark.parametrize("norm", (True, False))
def test_route_softmax_topk(norm):
    import jax.numpy as jnp
    from paddle_tpu.parallel.moe_utils import route_softmax_topk
    rng = np.random.default_rng(2)
    x = rng.normal(size=(9, 16)).astype(np.float32)
    w = rng.normal(size=(16, 12)).astype(np.float32)
    idx, wts = route_softmax_topk(jnp.asarray(x), jnp.asarray(w), 3,
                                  norm_topk=norm)
    z = x @ w
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, -1)[:, :3]
    assert np.array_equal(np.asarray(idx), want) and idx.dtype == jnp.int32
    pw = np.take_along_axis(p, want, 1)
    if norm:
        pw = pw / pw.sum(-1, keepdims=True)
        assert np.allclose(np.asarray(wts).sum(-1), 1.0, atol=1e-6)
    assert np.allclose(np.asarray(wts), pw, atol=1e-6)


# ------------------------------------------------------ the unmask rules


def test_static_rule_takes_the_most_confident():
    bd = BlockDecoding(4, MASK, denoising_steps=4)
    conf = [0.2, 0.9, 0.9, 0.1]
    assert bd.decide([0, 1, 2, 3], conf, 0) == [1]      # a tie: the earlier
    assert bd.decide([0, 2, 3], conf, 1) == [2]
    assert bd.decide([3], conf, 3) == [3]
    two = BlockDecoding(4, MASK, denoising_steps=2)
    assert two.decide([0, 1, 2, 3], conf, 0) == [1, 2]
    assert [BlockDecoding(8, MASK, denoising_steps=3).transfers(i)
            for i in range(5)] == [3, 3, 2, 1, 1]
    assert [BlockDecoding(4, MASK).transfers(i) for i in range(4)] == [1] * 4


def test_dynamic_rule_takes_all_above_the_threshold_and_at_least_n():
    bd = BlockDecoding(4, MASK, denoising_steps=4,
                       rule="low_confidence_dynamic", threshold=0.5)
    assert bd.decide([0, 1, 2, 3], [0.6, 0.1, 0.7, 0.55], 0) == [0, 2, 3]
    assert bd.decide([0, 1, 2, 3], [0.4, 0.1, 0.3, 0.2], 0) == [0]
    assert bd.decide([1, 3], [0.99, 0.1, 0.99, 0.2], 2) == [3]
    with pytest.raises(ValueError, match="unmask rule"):
        BlockDecoding(4, MASK, rule="random")
    with pytest.raises(ValueError, match="power of two"):
        BlockDecoding(6, MASK)


# --------------------------------------- the reference's incremental pass


def reference_generate(m, prompt, new_tokens):
    """Block diffusion by the REFERENCE alone: `prefix`, `denoise_pass`,
    `append`, and the rule re-stated in plain numpy. -> (tokens, the
    rows [L, V] and ids fed of every denoise pass)."""
    import jax.numpy as jnp
    cfg = DRIVERS.reference_cfg(m.arch)
    bd = m.arch.block_decoding
    L = bd.block_length
    n0 = len(prompt) // L * L
    length = (len(prompt) + new_tokens + L) // L * L
    cache = REF.prefix(m.weights, jnp.asarray(prompt[:n0], jnp.int32), cfg,
                       length)
    block = list(prompt[n0:]) + [bd.mask_token_id] * (L - len(prompt) + n0)
    decided = [i < len(prompt) - n0 for i in range(L)]
    out, start, log = [], n0, []
    while len(out) < new_tokens:
        n_pass = 0
        while not all(decided):
            z = np.asarray(REF.denoise_pass(
                m.weights, cache, start, jnp.asarray(block, jnp.int32),
                cfg)[0])
            log.append((start, list(block), z))
            p = np.exp(z - z.max(-1, keepdims=True))
            conf = (p / p.sum(-1, keepdims=True)).max(-1)
            masked = [i for i in range(L) if not decided[i]]
            n = min(bd.transfers(n_pass), len(masked))
            take = sorted(masked, key=lambda i: (-conf[i], i))[:n]
            if bd.rule == "low_confidence_dynamic":
                high = [i for i in masked if conf[i] > bd.threshold]
                take = high if len(high) >= n else take
            for i in take:
                block[i], decided[i] = int(z[i].argmax()), True
            n_pass += 1
        kvs = REF.denoise_pass(m.weights, cache, start,
                               jnp.asarray(block, jnp.int32), cfg)[2]
        cache = REF.append(cache, start, kvs)
        out += block[len(prompt) + len(out) - start:]
        start += L
        block, decided = [bd.mask_token_id] * L, [False] * L
    return out[:new_tokens], log


@pytest.mark.parametrize("plen", (8, 9, 10, 11))
def test_a_pass_over_the_cache_is_the_whole_forward(plen):
    """At every pass, the reference's rows of the block over a cache of
    the blocks before equal its whole forward's last rows (under the
    mask the earlier rows cannot depend on the block), and the model's
    eager `generate()` takes the same tokens."""
    import jax.numpy as jnp
    m = model()
    prompt = np.random.default_rng(plen).integers(0, VOCAB, plen).tolist()
    tokens, log = reference_generate(m, prompt, 6)
    assert len(log) >= 6
    cfg = DRIVERS.reference_cfg(m.arch)
    for start, block, z in log:
        whole = np.asarray(REF.logits(
            m.weights, jnp.asarray(prompt[:start] + tokens[
                :max(start - len(prompt), 0)] + block, jnp.int32), cfg,
            last=4))
        assert row_errors(z, whole).max() < 1e-5
    assert m.generate(prompt, 6) == tokens


def test_generate_stops_at_eos_and_at_the_horizon():
    m = model()
    prompt = np.random.default_rng(5).integers(0, VOCAB, 7).tolist()
    out = m.generate(prompt, 9)
    assert len(out) == 9 and m.generate(prompt, 3) == out[:3]
    assert m.generate(prompt, 9, eos_token_id=out[4]) == \
        out[:out.index(out[4]) + 1]


# ------------------------------------------------- the kernel's mask rule


def dense_block_causal(q, k, v, L):
    S, Hq, Dh = q.shape
    Hkv = k.shape[1]
    pos = np.arange(S)
    keep = (pos[None, :] // L) <= (pos[:, None] // L)
    qg = q.reshape(S, Hkv, Hq // Hkv, Dh).astype(np.float64)
    s = np.einsum("qhgd,khd->hgqk", qg, k.astype(np.float64)) / np.sqrt(Dh)
    s = np.where(keep[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hgqk,khd->qhgd", p,
                     v.astype(np.float64)).reshape(S, Hq, Dh)


def paged_case(S, Hkv, Gq, Dh, BS, dtype, seed=0):
    """One slot's sequence of S tokens laid into scattered blocks of a
    pool, what lies past its end filled with large values."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    MB = -(-S // BS) + 1
    NB = 2 * MB + 3
    q = rng.normal(size=(S, Hkv * Gq, Dh)).astype(np.float32)
    k = rng.normal(size=(S, Hkv, Dh)).astype(np.float32)
    v = rng.normal(size=(S, Hkv, Dh)).astype(np.float32)
    table = np.zeros((2, MB), np.int32)
    table[1, :MB - 1] = rng.permutation(np.arange(1, NB))[:MB - 1]
    kp = np.full((NB, BS, Hkv, Dh), 50.0, np.float32)
    vp = np.full((NB, BS, Hkv, Dh), 50.0, np.float32)
    for p in range(S):
        kp[table[1, p // BS], p % BS] = k[p]
        vp[table[1, p // BS], p % BS] = v[p]
    cast = lambda a: jnp.asarray(a).astype(dtype)   # noqa: E731
    return q, k, v, cast(kp), cast(vp), jnp.asarray(table)


def attend_in_runs(case, chunks, L, T, dtype, kernel, max_run=16):
    import jax.numpy as jnp
    q, _, _, kp, vp, table = case
    out = np.zeros(q.shape, np.float32)
    for a, b in chunks:
        n = b - a
        qq = np.zeros((T,) + q.shape[1:], np.float32)
        qq[:n] = q[a:b]
        slot = np.full(T, -1, np.int32)
        slot[:n] = 1
        pos = np.zeros(T, np.int32)
        pos[:n] = np.arange(a, b)
        args = (jnp.asarray(qq).astype(dtype), kp, vp, table,
                jnp.asarray(slot), jnp.asarray(pos))
        if kernel:
            with interpret_mode():
                o = fa.ragged_paged_attention(*args, max_run=max_run,
                                              causal_block=L)
        else:
            o = fa.ragged_paged_attention(*args, max_run=max_run,
                                          causal_block=L)
        out[a:b] = np.asarray(o.astype(jnp.float32))[:n]
    return out


@pytest.mark.parametrize("kernel", (False, True), ids=("gather", "pallas"))
@pytest.mark.parametrize("Hkv,Gq,dtype,L", (
    (2, 4, "float32", 4), (4, 8, "bfloat16", 4), (2, 1, "float32", 8)))
def test_block_causal_rule_against_a_dense_mask(kernel, Hkv, Gq, dtype, L):
    """A prompt as one run = in chunks = followed block by block, and
    all of them the dense block-causal mask's; a sequence that ends
    inside a block sees nothing of what lies behind it. Bit for bit
    where the CPU's products have the same shapes (the interpreted
    kernel takes a run of 4 in a shorter tile, and the CPU's dot then
    sums in another order: `tools/tpu_tile_validate.py` holds the three
    to the same bits on the chip)."""
    S, BS, T = 37, 8, 48
    case = paged_case(S, Hkv, Gq, 32, BS, dtype)
    q, k, v = case[:3]
    if dtype == "bfloat16":
        import jax.numpy as jnp
        rnd = lambda a: np.asarray(jnp.asarray(a).astype(  # noqa: E731
            jnp.bfloat16).astype(jnp.float32))
        q, k, v = rnd(q), rnd(k), rnd(v)
    want = dense_block_causal(q, k, v, L)
    one = attend_in_runs(case, [(0, S)], L, T, dtype, kernel)
    tol = 2e-5 if dtype == "float32" else 3e-2
    assert np.abs(one - want).max() < tol
    chunks = attend_in_runs(case, [(0, 16), (16, 32), (32, S)], L, T,
                            dtype, kernel)
    blocks = attend_in_runs(
        case, [(b, min(b + L, S)) for b in range(0, S, L)], L, T, dtype,
        kernel)
    assert np.array_equal(one, chunks)
    if kernel:
        assert np.abs(one - blocks).max() < tol / 10
    else:
        assert np.array_equal(one, blocks)


def test_without_a_block_the_kernel_is_today_s():
    """`causal_block=None` (and 1, the same mask) traces the very
    program it traced before the argument was there."""
    import jax
    case = paged_case(20, 2, 2, 32, 8, "float32")
    import jax.numpy as jnp
    q = jnp.zeros((16, 4, 32), jnp.float32)
    slot = jnp.asarray([1] * 5 + [-1] * 11, jnp.int32)
    pos = jnp.asarray(list(range(7, 12)) + [0] * 11, jnp.int32)

    def text(**kw):
        with interpret_mode():
            return str(jax.make_jaxpr(lambda *a: pa.ragged_attend(
                *a, max_run=8, **kw))(q, case[3], case[4], case[5], slot,
                                      pos))

    assert text() == text(causal_block=None) == text(causal_block=1)
    assert text() != text(causal_block=4)
    with pytest.raises(ValueError, match="power of two"):
        text(causal_block=6)
    assert pa.block_end(13, 4) == 15 and pa.block_end(16, 8) == 23


def test_counts_follow_a_query_to_its_block_s_end():
    """`_attention_work_by_kind` and `logits_issued` under the
    block-causal rule, against a count key by key."""
    from paddle_tpu.serving.engine import _attention_work_by_kind
    from paddle_tpu.serving.scheduler import Plan
    L = 4
    plan = Plan([(0, [1, 2, 3, 4], 8), (3, [5, 6, 7, 8], 40)],
                [(1, np.arange(18), 12, False), (2, np.arange(7), 0, True)],
                ())
    work = _attention_work_by_kind(plan, None, L)
    pairs = read = 0
    for start, n in ((8, 4), (40, 4), (12, 18), (0, 7)):
        read += start + n
        for p in range(start, start + n):
            pairs += min(p // L * L + L - 1, start + n - 1) + 1
    assert work["attn_pairs_full"] == pairs
    assert work["kv_tokens_read_full"] == read
    assert work["attn_pairs_window"] == 0
    causal = _attention_work_by_kind(plan, None, None)
    assert causal["attn_pairs_full"] < pairs
    # the kernel's own count: never less with the block rule, and a run
    # of one block inside one fetched group issues the same either way
    tiles = pa.kernel_tiles(64, 2, 2, 32, 8, 12, np.float32, max_run=16)
    runs = [(8, 4), (40, 4), (12, 18), (0, 7)]
    kw = dict(tiles=tiles, H=2, Gq=2, block_size=8, max_run=16)
    assert pa.logits_issued(runs, causal_block=L, **kw) >= \
        pa.logits_issued(runs, **kw) > 0
    assert pa.logits_issued([(8, 4)], causal_block=L, **kw) == \
        pa.logits_issued([(8, 4)], **kw)
