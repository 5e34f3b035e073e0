"""Tuned block-sparse decode budgets (ISSUE 17 satellite).

`ServingEngine(sparse_blocks=B)` trades decode-attention reads for a
fixed per-step block budget; docs/SERVING.md hand-picks B=8 for the
smoke geometry. `tune_sparse_budget` replaces the hand-pick with a
measured sweep on the retrieval ("needle") workload — the adversarial
case for block scoring, where dropping one matching block visibly
corrupts greedy outputs (tools/longctx_smoke.py's contract 2):

* build a dense reference engine and the tuned candidates over the
  SAME long-prompt batch;
* walk `candidates` ascending and keep the SMALLEST budget whose
  greedy token agreement with the dense engine meets
  `agreement_target` (default the 0.99 smoke floor);
* record the winner in the kernel-autotune cache under kernel
  ``sparse_budget``, keyed by `shape_bucket(hidden, head_dim)` — the
  key `ServingEngine(sparse_blocks="auto")` resolves at construction,
  so every later engine of that geometry boots with the tuned budget
  for free (same discipline as the ISSUE 11 `block_size="auto"`).

The sweep runs offline (bench lane / ops runbook), never on a serving
path: one dense + len(candidates) engines, one mixed-step compile
each.
"""
from __future__ import annotations

import numpy as np

__all__ = ["needle_model", "needle_prompts", "tune_sparse_budget"]


def needle_model(num_layers=2, vocab=64, hidden=32, maxpos=256,
                 qk_gain=3.0, pe_scale=0.02):
    """Tiny GPT conditioned into a retrieval transformer: channel-
    sparse embeddings + identity q/k with gain, so attention
    concentrates on same-token ("needle") positions while values /
    projections / lm head keep their random init. The workload
    tools/longctx_smoke.py validates the sparse contract on."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..models.gpt import GPTForGeneration

    paddle.seed(0)
    model = GPTForGeneration(vocab_size=vocab, hidden_size=hidden,
                             num_layers=num_layers,
                             num_attention_heads=1,
                             max_position_embeddings=maxpos,
                             compute_dtype="float32")
    we = np.zeros((vocab, hidden), np.float32)
    we[np.arange(vocab), np.arange(vocab) % hidden] = 1.0
    model.word_embeddings.weight._data = jnp.asarray(we)
    model.position_embeddings.weight._data = (
        jnp.asarray(model.position_embeddings.weight._data) * pe_scale)
    names, dec = model.decoder._param_tensors()
    eye = jnp.eye(hidden, dtype=jnp.float32)
    for n, t in zip(names, dec):
        if n == "qkv_w":
            w = jnp.asarray(t._data)
            L = w.shape[0]
            w = w.at[:, :, :hidden].set(qk_gain * eye[None].repeat(L, 0))
            w = w.at[:, :, hidden:2 * hidden].set(
                qk_gain * eye[None].repeat(L, 0))
            t._data = w
    model.eval()
    return model


def needle_prompts(n=16, lo=90, hi=200, vocab=64, seed=7):
    """Long random prompts (tens of candidate blocks per slot by the
    end of decode) — the regime where a too-small budget must drop
    scored blocks and lose needles."""
    rng = np.random.RandomState(seed)
    return [rng.randint(2, vocab, int(k)).tolist()
            for k in rng.randint(lo, hi, n)]


def tune_sparse_budget(model=None, *, candidates=(4, 6, 8, 12, 16),
                       sparse_recent=2, agreement_target=0.99,
                       prompts=None, max_new_tokens=12,
                       max_seq_len=224, block_size=4, max_slots=4,
                       persist=True, verbose=False):
    """Sweep `candidates` (ascending block budgets B) on the needle
    workload; record the smallest B meeting `agreement_target` in the
    autotune cache and return

        {"best": {"sparse_blocks": B, "sparse_recent": r} | None,
         "agreement": float, "skip_ratio": float, "bucket": (...),
         "sweep": [{"sparse_blocks", "agreement", "skip_ratio"}, ...]}

    `best` is None (and nothing is recorded) when no candidate meets
    the floor — `sparse_blocks="auto"` then keeps its conservative
    default."""
    from ..ops.pallas import autotune as _kt
    from .engine import ServingEngine

    if model is None:
        model = needle_model()
    if prompts is None:
        prompts = needle_prompts(vocab=int(model.vocab_size))

    def engine(**kw):
        return ServingEngine(model, max_slots=max_slots,
                             block_size=block_size,
                             max_seq_len=max_seq_len,
                             cache_dtype="float32", seed=0, **kw)

    dense = engine()
    ref = dense.generate_batch([list(p) for p in prompts],
                               max_new_tokens=max_new_tokens)
    total = sum(len(o) for o in ref)
    # the key `ServingEngine(sparse_blocks="auto")` looks up: head
    # count and head width
    dec = model.decoder
    bucket = _kt.shape_bucket(dec.num_heads, dec.head_dim)
    sweep, best = [], None
    for B in sorted(int(b) for b in candidates):
        eng = engine(sparse_blocks=B, sparse_recent=int(sparse_recent))
        out = eng.generate_batch([list(p) for p in prompts],
                                 max_new_tokens=max_new_tokens)
        agree = sum(a == b for x, y in zip(ref, out)
                    for a, b in zip(x, y)) / max(1, total)
        row = {"sparse_blocks": B, "agreement": agree,
               "skip_ratio": eng.sparse_skip_ratio()}
        sweep.append(row)
        if verbose:
            print(f"  B={B:3d} agreement={agree:.4f} "
                  f"skip={row['skip_ratio']:.3f}")
        if best is None and agree >= agreement_target:
            best = row
            # candidates are ascending, so the first hit IS the
            # smallest budget; keep sweeping only for the report
    result = {"best": None, "agreement": 0.0, "skip_ratio": 0.0,
              "bucket": bucket, "sweep": sweep}
    if best is not None:
        cfg = {"sparse_blocks": best["sparse_blocks"],
               "sparse_recent": int(sparse_recent)}
        _kt.record("sparse_budget", bucket, np.dtype(np.float32), cfg,
                   meta={"agreement": best["agreement"],
                         "skip_ratio": best["skip_ratio"],
                         "target": float(agreement_target)},
                   persist=persist)
        result.update(best=cfg, agreement=best["agreement"],
                      skip_ratio=best["skip_ratio"])
    return result
