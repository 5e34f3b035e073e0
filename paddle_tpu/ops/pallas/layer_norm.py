"""Fused (residual-add +) LayerNorm as Pallas TPU kernels, fwd + bwd.

Motivation (docs/gpt_perf_analysis.md): in the GPT train step the
residual adds + LN fusions run 5-15x above their bandwidth roofline —
XLA materialises layout conversions between the scan carry's S-minor
layout and the matmuls' d-minor layout around every add/LN. A Pallas
kernel pins one layout and does the add + normalise in a single
read/write pass; the custom vjp's backward kernel computes the heavy
[N, d] dz in one pass, with the small dgamma/dbeta reductions left to
XLA (they fuse into a single f32[d] pass).

API (used by parallel/hybrid_gpt.py when enabled):
    add_ln(x, r, w, b, eps)     -> (normalized, z=x+r)   (z is the new
                                   residual stream)
Plain jnp math off-TPU and for non-tileable shapes (on a TPU the
latter warns).
`_INTERPRET` runs the kernels in pallas interpret mode (CPU tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _on_tpu():
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


# --------------------------------------------------------------- kernels

def _fwd_kernel(x_ref, r_ref, w_ref, b_ref, o_ref, z_ref, mu_ref,
                rs_ref, *, eps):
    x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    z_ref[...] = x.astype(z_ref.dtype)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps)
    out = xc * rs * w_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)
    mu_ref[...] = mu
    rs_ref[...] = rs


def _bwd_kernel(z_ref, w_ref, mu_ref, rs_ref, g_ref, dz_ref, *, eps):
    z = z_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    rs = rs_ref[...]
    zhat = (z - mu) * rs
    dzh = g * w
    m1 = jnp.mean(dzh, axis=-1, keepdims=True)
    m2 = jnp.mean(dzh * zhat, axis=-1, keepdims=True)
    dz = rs * (dzh - m1 - zhat * m2)
    dz_ref[...] = dz.astype(dz_ref.dtype)


_BLOCK_ROWS = 256
_INTERPRET = False  # pallas interpret mode (CPU tests)


def _run_fwd(x2, r2, w, b, eps):
    n, d = x2.shape
    br = _BLOCK_ROWS
    grid = (n // br,)
    kernel = functools.partial(_fwd_kernel, eps=eps)
    out, z, mu, rs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_INTERPRET, name="add_ln_fwd",
    )(x2, r2, w.reshape(1, d), b.reshape(1, d))
    return out, z, mu, rs


def _run_bwd_dz(z2, w, mu, rs, g2, eps):
    n, d = z2.shape
    br = _BLOCK_ROWS
    grid = (n // br,)
    kernel = functools.partial(_bwd_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), g2.dtype),
        interpret=_INTERPRET, name="add_ln_bwd",
    )(z2, w.reshape(1, d), mu, rs, g2)


# ------------------------------------------------------------ custom vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _add_ln(x, r, w, b, eps):
    out, z, _, _ = _core_fwd(x, r, w, b, eps)
    return out, z


def _core_fwd(x, r, w, b, eps):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    r2 = r.reshape(-1, d)
    out, z, mu, rs = _run_fwd(x2, r2, w, b, eps)
    return out.reshape(shape), z.reshape(shape), mu, rs


def _add_ln_fwd(x, r, w, b, eps):
    out, z, mu, rs = _core_fwd(x, r, w, b, eps)
    return (out, z), (z, w, mu, rs)


def _add_ln_bwd(eps, res, cts):
    g_out, g_z = cts
    z, w, mu, rs = res
    shape = z.shape
    d = shape[-1]
    z2 = z.reshape(-1, d)
    g2 = g_out.reshape(-1, d)
    dz = _run_bwd_dz(z2, w, mu, rs, g2, eps).reshape(shape)
    dz = dz + g_z  # the residual-stream cotangent flows straight through
    # small per-feature reductions: one fused f32[d] XLA pass
    zf = z2.astype(jnp.float32)
    zhat = (zf - mu) * rs
    gf = g2.astype(jnp.float32)
    dw = jnp.sum(gf * zhat, axis=0).astype(w.dtype)
    db = jnp.sum(gf, axis=0).astype(w.dtype)
    return dz, dz, dw, db


_add_ln.defvjp(_add_ln_fwd, _add_ln_bwd)


def add_ln_reference(x, r, w, b, eps=1e-5):
    """The plain jnp form of `add_ln`: the off-TPU path and the oracle
    the kernel is checked against."""
    z = x + r
    zf = z.astype(jnp.float32)
    mu = jnp.mean(zf, axis=-1, keepdims=True)
    var = jnp.var(zf, axis=-1, keepdims=True)
    out = ((zf - mu) / jnp.sqrt(var + eps) * w + b).astype(x.dtype)
    return out, z


def add_ln(x, r, w, b, eps=1e-5):
    """(LN(x + r) * w + b, x + r) — fused on TPU; `add_ln_reference`
    off-TPU or when rows/features don't tile (rows % 256, d % 128)."""
    import math as _math
    n_rows = _math.prod(x.shape[:-1])
    if x.shape[-1] % 128 == 0 and n_rows % _BLOCK_ROWS == 0:
        if _on_tpu() or _INTERPRET:
            return _add_ln(x, r, w.astype(jnp.float32),
                           b.astype(jnp.float32), eps)
    else:
        from . import xla_fallback
        xla_fallback("add_ln", f"the gate refuses {n_rows} rows x "
                     f"{x.shape[-1]} features (needs rows % "
                     f"{_BLOCK_ROWS} == 0 and features % 128 == 0)")
    return add_ln_reference(x, r, w, b, eps)
