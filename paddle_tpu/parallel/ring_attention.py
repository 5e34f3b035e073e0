"""Ring attention — context parallelism over the sequence axis.

The reference snapshot has NO sequence/context parallelism (SURVEY §5.7);
this is a first-class TPU-native extension: K/V blocks rotate around the
"cp" mesh axis via `lax.ppermute` (ICI neighbor hops) while each device
holds one query block, accumulating online-softmax partials — attention
memory O(S/cp) per device, compute fully overlapped around the ring
(Liu et al., Ring Attention; the blockwise core matches our pallas flash
kernel's math).

Layout: q/k/v [B, S, H, D] logically; sharded over cp on S. Causal is
handled by masking each (q_block, k_block) pair by their ring offset.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import shard_map as _shard_map


def _block_attn(q, k, v, scale, mask):
    """Online-softmax partials for one (q_block, k_block) pair.
    q [B,Sq,H,D], k/v [B,Sk,H,D]; mask [Sq,Sk] bool or None.
    Returns (acc [B,Sq,H,D] fp32, m [B,H,Sq], l [B,H,Sq])."""
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _ring_attention_local(q, k, v, *, axis_name, cp, causal, scale):
    """Per-device body (inside shard_map). q/k/v local [B, S/cp, H, D]."""
    B, Sl, H, D = q.shape
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    neg_inf = jnp.full((B, H, Sl), -jnp.inf, jnp.float32)
    zero_l = jnp.zeros((B, H, Sl), jnp.float32)
    zero_acc = jnp.zeros((B, Sl, H, D), jnp.float32)

    def step(carry, i):
        k_cur, v_cur, m_prev, l_prev, acc_prev = carry
        # k_cur originated on rank (rank - i) mod cp
        src = (rank - i) % cp
        if causal:
            q_pos = rank * Sl + jnp.arange(Sl)
            k_pos = src * Sl + jnp.arange(Sl)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        acc_i, m_i, l_i = _block_attn(q, k_cur, v_cur, scale, mask)
        m_new = jnp.maximum(m_prev, m_i)
        a1 = jnp.exp(m_prev - m_new)
        a2 = jnp.exp(m_i - m_new)
        l_new = l_prev * a1 + l_i * a2
        acc_new = (acc_prev * jnp.transpose(a1, (0, 2, 1))[..., None]
                   + acc_i * jnp.transpose(a2, (0, 2, 1))[..., None])
        # rotate k/v to the next rank
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    (k_f, v_f, m, l, acc), _ = jax.lax.scan(
        step, (k, v, neg_inf, zero_l, zero_acc), jnp.arange(cp))
    l_t = jnp.transpose(jnp.maximum(l, 1e-30), (0, 2, 1))[..., None]
    return (acc / l_t).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name="cp", causal=True,
                   scale=None):
    """q/k/v: [B, S, H, D] logical arrays (or sharded); returns same.

    When `mesh` is None builds a 1-D ring over all devices, in the
    slice's physical ring order. S must divide by the cp size.
    """
    if mesh is None:
        from .env import device_grid
        mesh = Mesh(device_grid(jax.devices(), (jax.device_count(),)),
                    (axis_name,))
    cp = mesh.shape[axis_name]
    B, S, H, D = q.shape
    assert S % cp == 0, f"seq {S} must divide cp {cp}"
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    body = functools.partial(_ring_attention_local, axis_name=axis_name,
                             cp=cp, causal=causal, scale=scale)
    spec = P(None, axis_name, None, None)
    fn = _shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
