"""splash_mha / flash attention dispatch tests.

On the CPU test mesh the splash Pallas kernel is gated off and the XLA
fallback runs — these tests pin the fallback's numerics and the
dispatch conditions. On a real TPU the same parity asserts run against
the actual kernel (tolerances hold for both)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.flash_attention import (splash_mha,
                                                   splash_supported)


def _naive(q, k, v, causal, scale):
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        S, T = logits.shape[-2:]
        logits = jnp.where(jnp.tril(jnp.ones((S, T), bool)), logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [True, False])
def test_splash_mha_matches_naive(causal):
    B, H, S, D = 2, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    out = splash_mha(q, k, v, causal=causal)
    ref = _naive(q, k, v, causal, 1.0 / math.sqrt(D))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_splash_mha_grads_flow():
    B, H, S, D = 1, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)

    def loss(q, k, v):
        return splash_mha(q, k, v, causal=True).astype(jnp.float32).sum()

    def loss_ref(q, k, v):
        return _naive(q, k, v, True, 1.0 / math.sqrt(D)).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=5e-2, atol=5e-2)


def test_splash_gate():
    # the kernel only claims lane-aligned seq and a head_dim the
    # INSTALLED kernel tiles; everything else must take the XLA path
    # (and still be correct)
    assert not splash_supported(100, 64)   # S % 128 != 0
    assert not splash_supported(256, 80)   # D % 64 != 0
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 100, 32))
    out = splash_mha(q, q, q, causal=True)
    assert out.shape == (1, 2, 100, 32)


def test_splash_gate_takes_head_dim_64(_interpret_splash):
    """head_dim 64 (GPT-350M, BERT) is inside the gate and runs the
    kernel; a refusal from the kernel would raise, not fall back."""
    assert splash_supported(256, 64)
    assert splash_supported(256, 128)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 128, 64))
    out = splash_mha(q, q, q, causal=True)
    assert out.shape == (1, 2, 128, 64)


def test_functional_flash_attention_uses_dispatch():
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    B, S, H, D = 2, 128, 2, 64
    x = paddle.randn([B, S, H, D])
    out, _ = F.flash_attention(x, x, x, causal=True)
    assert list(out.shape) == [B, S, H, D]
    ref = _naive(jnp.swapaxes(x._data, 1, 2), jnp.swapaxes(x._data, 1, 2),
                 jnp.swapaxes(x._data, 1, 2), True, 1.0 / math.sqrt(D))
    np.testing.assert_allclose(
        np.asarray(out.numpy(), np.float32),
        np.asarray(jnp.swapaxes(ref, 1, 2)), rtol=2e-2, atol=2e-2)


def _naive_masked(q, k, v, keep, causal, scale):
    """Oracle: key-padding mask as additive bias (segment-id semantics
    on the real rows; padded query rows differ by contract)."""
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    bias = jnp.where(keep[:, None, None, :] > 0, 0.0, -1e30)
    logits = logits + bias
    if causal:
        S, T = logits.shape[-2:]
        logits = jnp.where(jnp.tril(jnp.ones((S, T), bool)), logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32))


@pytest.fixture
def _interpret_splash():
    """Run the real splash Pallas kernel in interpret mode on the CPU
    mesh, so the segment-id plumbing (not just the XLA fallback) is
    exercised in CI."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


@pytest.mark.parametrize("causal", [False, True])
def test_splash_mha_key_padding_matches_oracle(_interpret_splash, causal):
    # head_dim 128: a shape the INSTALLED kernel accepts, so the real
    # segment-id plumbing (not the XLA fallback) runs in interpret mode
    B, H, S, D = 2, 2, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    lens = np.array([96, 128])
    keep = jnp.asarray(np.arange(S)[None, :] < lens[:, None], jnp.int32)
    out = splash_mha(q, k, v, causal=causal, kv_keep=keep)
    ref = _naive_masked(q, k, v, keep, causal, 1.0 / math.sqrt(D))
    # compare only real (unpadded) query rows: padded rows are garbage
    # by contract (reference varlen flash never reads them back)
    real = np.asarray(keep, bool)
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[real.nonzero()[0][:, None],
                                    :, real.nonzero()[1][:, None]],
        np.asarray(ref)[real.nonzero()[0][:, None], :,
                        real.nonzero()[1][:, None]],
        rtol=2e-2, atol=2e-2)


def test_splash_mha_key_padding_grads(_interpret_splash):
    B, H, S, D = 1, 2, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    keep = jnp.asarray(np.arange(S)[None, :] < 80, jnp.int32)
    w = jnp.where(keep[:, None, :, None] > 0, 1.0, 0.0)  # mask pad rows

    def loss(q, k, v):
        return (splash_mha(q, k, v, causal=False, kv_keep=keep) * w).sum()

    def loss_ref(q, k, v):
        return (_naive_masked(q, k, v, keep, False,
                              1.0 / math.sqrt(D)) * w).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=5e-2, atol=5e-2)


def test_sdpa_routes_key_padding_mask_to_splash(_interpret_splash,
                                                monkeypatch):
    """scaled_dot_product_attention with a [B,1,1,S] bool mask must take
    the splash segment-id path on TPU, not the additive-bias fallback."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops.pallas import flash_attention as fa

    calls = {}
    orig = fa.splash_mha

    def spy(*a, **kw):
        calls["kv_keep"] = kw.get("kv_keep")
        return orig(*a, **kw)
    monkeypatch.setattr(fa, "splash_mha", spy)

    B, S, H, D = 2, 128, 2, 128
    x = paddle.randn([B, S, H, D])
    keep = np.arange(S)[None, :] < np.array([100, 128])[:, None]
    mask = paddle.to_tensor(keep[:, None, None, :])  # [B,1,1,S] bool
    out = F.scaled_dot_product_attention(x, x, x, attn_mask=mask)
    assert calls.get("kv_keep") is not None, \
        "key-padding mask did not reach the splash kernel"
    ref = _naive_masked(
        jnp.swapaxes(x._data, 1, 2), jnp.swapaxes(x._data, 1, 2),
        jnp.swapaxes(x._data, 1, 2), jnp.asarray(keep, jnp.int32),
        False, 1.0 / math.sqrt(D))
    got = jnp.swapaxes(out._data.astype(jnp.float32), 1, 2)
    real = keep
    np.testing.assert_allclose(
        np.asarray(got)[real.nonzero()[0][:, None], :,
                        real.nonzero()[1][:, None]],
        np.asarray(ref)[real.nonzero()[0][:, None], :,
                        real.nonzero()[1][:, None]],
        rtol=2e-2, atol=2e-2)


def test_sdpa_float_key_padding_mask_equivalent():
    """Float 0/-1e9 [B,1,1,S] masks (paddle convention) give the same
    result as bool masks — on the XLA fallback path here (CPU gate)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    B, S, H, D = 2, 64, 2, 32
    x = paddle.randn([B, S, H, D])
    keep = np.arange(S)[None, :] < np.array([40, 64])[:, None]
    mb = paddle.to_tensor(keep[:, None, None, :])
    mf = paddle.to_tensor(((keep.astype(np.float32) - 1.0)
                           * 1e9)[:, None, None, :])
    ob = F.scaled_dot_product_attention(x, x, x, attn_mask=mb).numpy()
    of = F.scaled_dot_product_attention(x, x, x, attn_mask=mf).numpy()
    real = keep
    np.testing.assert_allclose(ob[real.nonzero()[0], real.nonzero()[1]],
                               of[real.nonzero()[0], real.nonzero()[1]],
                               rtol=1e-5, atol=1e-5)


def test_sdpa_broadcast_batch_mask_splash(_interpret_splash):
    """A [1,1,1,S] mask must broadcast over a B>1 batch on the splash
    path (regression: vmap size mismatch on the segment ids)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    B, S, H, D = 2, 128, 2, 64
    x = paddle.randn([B, S, H, D])
    keep = (np.arange(S) < 96)[None, None, None, :]
    out = F.scaled_dot_product_attention(
        x, x, x, attn_mask=paddle.to_tensor(keep))
    assert list(out.shape) == [B, S, H, D]
    assert np.isfinite(np.asarray(out.numpy(), np.float32)[:, :96]).all()


def test_sdpa_float_bias_not_binarized(_interpret_splash):
    """[B,1,1,S] float biases with moderate values must take the exact
    additive path even on TPU (no silent keep/drop binarization)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    B, S, H, D = 2, 128, 2, 64
    x = paddle.randn([B, S, H, D])
    rng = np.random.RandomState(0)
    bias = rng.randn(B, 1, 1, S).astype(np.float32)
    out = F.scaled_dot_product_attention(
        x, x, x, attn_mask=paddle.to_tensor(bias)).numpy()
    q = jnp.swapaxes(x._data, 1, 2).astype(jnp.float32)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, q) / math.sqrt(D) \
        + bias[:, :, 0][:, :, None, :]
    ref = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(logits, -1), q)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               rtol=2e-2, atol=2e-2)
