"""Plain reference of `gpt2_medium_train`: the GPT-2 forward pass, its
mean next-token cross-entropy, and training steps on it (autodiff,
global-norm clipping, AdamW) in straightforward `jax.numpy` and
float32 — no kernel, no fused loss, no code of the program under
test. Learned positions, pre-LayerNorm blocks (eps 1e-5), QKV
projection laid out [3, heads, head_dim] on its output axis, causal
softmax attention, tanh GELU (`gelu_new`, as the source's config
names), final LayerNorm, untied linear head.

`p` is the trainer's parameter tree as float32 arrays:
    tok_emb [V, d], pos_emb [S, d], ln_f_w ln_f_b [d], head [d, V],
    blocks: ln1_w ln1_b ln2_w ln2_b b_o b_fc2 [L, d], w_qkv [L, d, 3d],
            b_qkv [L, 3d], w_o [L, d, d], w_fc1 [L, d, 4d], b_fc1 [L, 4d],
            w_fc2 [L, 4d, d]
"""
import math

import jax
import jax.numpy as jnp

EPS = 1e-5


def layer_norm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * w + b


def loss(p, tokens, labels, num_heads):
    """tokens, labels [B, S] -> mean cross-entropy in nats."""
    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        x = p["tok_emb"][tokens] + p["pos_emb"][:S]
        d = x.shape[-1]
        dh = d // num_heads
        causal = jnp.tril(jnp.ones((S, S), bool))

        def block(x, lw):
            h = layer_norm(x, lw["ln1_w"], lw["ln1_b"])
            qkv = (h @ lw["w_qkv"] + lw["b_qkv"]).reshape(
                B, S, 3, num_heads, dh)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
            a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            a = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, d)
            x = x + a @ lw["w_o"] + lw["b_o"]
            h = layer_norm(x, lw["ln2_w"], lw["ln2_b"])
            f = jax.nn.gelu(h @ lw["w_fc1"] + lw["b_fc1"],
                            approximate=True)
            return x + f @ lw["w_fc2"] + lw["b_fc2"], None

        # checkpointed only so that the backward of `train_losses`
        # fits beside the trainer's state; it changes no value
        x, _ = jax.lax.scan(jax.checkpoint(block), x, p["blocks"])
        z = layer_norm(x, p["ln_f_w"], p["ln_f_b"]) @ p["head"]
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -picked.mean()


def train_step(state, t, tokens, labels, num_heads, hp):
    """One training step, number `t` from 1, on (parameters, first
    moments, second moments): gradients of `loss` clipped to the global
    norm `hp["grad_clip"]`, then AdamW (bias-corrected moments; the
    decay on every array of the tree with more than one axis, as the
    trainer applies it). Returns the new state and the loss before the
    step."""
    p, m, v = state
    b1, b2 = hp["beta1"], hp["beta2"]
    value, g = jax.value_and_grad(loss)(p, tokens, labels, num_heads)
    norm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / (norm + 1e-6))
    g = jax.tree.map(lambda a: a * scale, g)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

    def update(w, a, b):
        wd = hp["weight_decay"] if w.ndim > 1 else 0.0
        return w - hp["learning_rate"] * (
            a / (1 - b1 ** t) / (jnp.sqrt(b / (1 - b2 ** t)) + hp["eps"])
            + wd * w)
    return (jax.tree.map(update, p, m, v), m, v), value


def train_losses(p, tokens, labels, num_heads, steps, hp):
    """The loss before each of `steps` training steps on one batch,
    from the parameters `p`. The state is a copy, updated in place
    step by step, so that it fits beside the trainer's own."""
    step = jax.jit(train_step, static_argnums=4, donate_argnums=0)
    zeros = lambda q: jax.tree.map(jnp.zeros_like, q)    # noqa: E731
    state = jax.jit(lambda q: (jax.tree.map(jnp.copy, q), zeros(q),
                               zeros(q)))(p)
    losses = []
    for t in range(1, steps + 1):
        state, value = step(state, float(t), tokens, labels, num_heads,
                            hp)
        losses.append(value)
    return losses
