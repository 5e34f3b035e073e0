"""Pallas fused add+LayerNorm kernel tests: jnp fallback AND the real
kernels via pallas interpret mode (CPU-executable), incl. the
hand-written custom_vjp backward."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_fused_add_ln_matches_reference():
    """Pallas fused residual-add+LN (jnp fallback on CPU): forward and
    grads must match the unfused math."""
    from paddle_tpu.ops.pallas.layer_norm import add_ln

    rng = np.random.RandomState(0)
    B, S, d = 2, 64, 256
    x = jnp.asarray(rng.randn(B, S, d), jnp.float32)
    r = jnp.asarray(rng.randn(B, S, d), jnp.float32)
    w = jnp.asarray(rng.rand(d), jnp.float32)
    b = jnp.asarray(rng.randn(d), jnp.float32)

    def ref(x, r, w, b):
        z = x + r
        mu = z.mean(-1, keepdims=True)
        var = z.var(-1, keepdims=True)
        return (z - mu) / jnp.sqrt(var + 1e-5) * w + b, z

    out, z = add_ln(x, r, w, b)
    ro, rz = ref(x, r, w, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(z), np.asarray(rz), rtol=1e-5)

    def loss(f):
        def inner(x, r, w, b):
            o, z = f(x, r, w, b)
            return (o * 1.3).sum() + (z * 0.7).sum()
        return inner

    g = jax.grad(loss(add_ln), argnums=(0, 1, 2, 3))(x, r, w, b)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2, 3))(x, r, w, b)
    for a, bb in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-3, atol=2e-3)


def test_fused_add_ln_pallas_kernels_interpret_mode(monkeypatch):
    """Run the ACTUAL Pallas fwd+bwd kernels (interpret mode) and check
    against the unfused math — covers _fwd_kernel/_bwd_kernel and the
    custom vjp (incl. the residual cotangent pass-through) on CPU."""
    import paddle_tpu.ops.pallas.layer_norm as lnmod
    monkeypatch.setattr(lnmod, "_INTERPRET", True)

    rng = np.random.RandomState(1)
    B, S, d = 2, 256, 128       # rows = 512 (tiles), d % 128 == 0
    x = jnp.asarray(rng.randn(B, S, d), jnp.float32)
    r = jnp.asarray(rng.randn(B, S, d), jnp.float32)
    w = jnp.asarray(rng.rand(d), jnp.float32)
    b = jnp.asarray(rng.randn(d), jnp.float32)

    def ref(x, r, w, b):
        z = x + r
        mu = z.mean(-1, keepdims=True)
        var = z.var(-1, keepdims=True)
        return (z - mu) / jnp.sqrt(var + 1e-5) * w + b, z

    out, z = lnmod.add_ln(x, r, w, b)
    ro, rz = ref(x, r, w, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(z), np.asarray(rz), rtol=1e-6)

    def loss(f):
        def inner(x, r, w, b):
            o, z = f(x, r, w, b)
            return (o * 1.3).sum() + (z * 0.7).sum()
        return inner

    g = jax.grad(loss(lnmod.add_ln), argnums=(0, 1, 2, 3))(x, r, w, b)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2, 3))(x, r, w, b)
    for a, bb in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-4)


def test_add_ln_non_tileable_falls_back():
    import paddle_tpu.ops.pallas.layer_norm as lnmod
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(3, 5, 100), jnp.float32)  # nothing tiles
    r = jnp.asarray(rng.randn(3, 5, 100), jnp.float32)
    w = jnp.ones((100,), jnp.float32)
    b = jnp.zeros((100,), jnp.float32)
    out, z = lnmod.add_ln(x, r, w, b)
    zf = np.asarray(x + r)
    mu = zf.mean(-1, keepdims=True)
    var = zf.var(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out),
                               (zf - mu) / np.sqrt(var + 1e-5),
                               rtol=2e-4, atol=2e-4)
