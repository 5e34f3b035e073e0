"""The seam `ServingEngine` steps a model that brings its own block
through (`models/afmoe.py`, `models/olmo_hybrid.py`,
`models/sdar_moe.py`): the architecture, the weight tree (an argument
of the jitted step) and the functions of `(arch, weights, ...)` that
the model's eager forward calls too; `BlockDecoding`, what an
architecture says of itself when it generates by diffusion over blocks
instead of a token at a time; and `LearnedSelection`, what it says of
the indexer of its "sparse" layers (attention through a selection)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ServingBlock:
    arch: object            # frozen description: `layers`, `layer_kinds`,
    #                          `num_heads`, `num_kv_heads`, `head_dim`, ...
    #                          and `block_decoding` (a `BlockDecoding`)
    #                          where the model decodes by blocks,
    #                          `selection` (a `LearnedSelection`) where
    #                          a layer's kind is "sparse", `latent` (a
    #                          `LatentAttention`) where it is "latent"
    weights: dict
    embed: object           # (arch, weights, token_ids) -> h [T, D]
    layer: object           # (arch, li, layer weights, h, positions,
    #                          valid, attend[, recur]) -> (h, stats or
    #                          None); `recur` only where the architecture
    #                          has "linear" layers; a "sparse" layer
    #                          calls `attend(q, k, v, li, (qI, kI, w))`
    head: object            # (arch, weights, h rows) -> logits
    stat_names: tuple = ()  # flight-record names of the step's counters
    fold_stats: object = None   # (int32[len(stat_names)], a layer's
    #                              stats) -> int32[len(stat_names)]


@dataclasses.dataclass(frozen=True)
class BlockDecoding:
    """How a model that generates by DIFFUSION OVER BLOCKS decodes; an
    architecture that carries one (`arch.block_decoding`) is served a
    block a slot instead of a token a slot.

    Positions are cut into blocks of `block_length` L at multiples of L
    from position 0, and attention is block-causal (a query sees the
    keys up to the end of its own block). A block is generated by
    DENOISE passes: its L positions are fed, decided ones as their
    token, the others as `mask_token_id`; the row at position i
    predicts token i (no shift); each masked row gives a candidate and
    its confidence, the softmax probability of that candidate; `decide`
    picks the masked positions that take their candidate, for good.
    When none is masked, one more pass (the COMMIT) writes the block's
    final K/V, and the next block starts."""
    block_length: int
    mask_token_id: int
    denoising_steps: int = None         # default: one position a pass
    rule: str = "low_confidence_static"
    threshold: float = 0.9              # of `low_confidence_dynamic`

    RULES = ("low_confidence_static", "low_confidence_dynamic")

    def __post_init__(self):
        if self.rule not in self.RULES:
            raise ValueError(f"unmask rule {self.rule!r}: one of "
                             f"{self.RULES}")
        L = self.block_length
        if L < 2 or L & (L - 1):
            raise ValueError(f"block_length={L}: a power of two, 2 or "
                             "more (the kernel's block-causal mask)")

    def transfers(self, n_pass):
        """Positions denoise pass `n_pass` (from 0) of a block decides
        at the least: L spread over `denoising_steps`, the remainder on
        the first passes; one where the schedule has run out (a block
        that began with fewer masked positions ends sooner)."""
        steps = self.denoising_steps or self.block_length
        base, rem = divmod(self.block_length, steps)
        return max(1, base + (n_pass < rem)) if n_pass < steps else 1

    def decide(self, masked, confidence, n_pass):
        """The positions (ascending) that denoise pass `n_pass` decides:
        `masked` the block's masked positions, `confidence[i]` position
        i's. Static: the `transfers(n_pass)` most confident (a tie goes
        to the earlier position). Dynamic: every one above `threshold`,
        and at least that many."""
        n = min(self.transfers(n_pass), len(masked))
        if self.rule == "low_confidence_dynamic":
            high = [i for i in masked
                    if float(confidence[i]) > self.threshold]
            if len(high) >= n:
                return high
        return sorted(sorted(
            masked, key=lambda i: (-float(confidence[i]), i))[:n])

    def decide_rows(self, masked, confidence, n_pass):
        """`decide` for many blocks at once, in `jax.numpy` (the step
        decides on the device): `masked` bool [S, L], `confidence`
        float32 [S, L], `n_pass` int32 [S] -> bool [S, L], the
        positions each block's pass decides. The same choice as
        `decide` on the same float32 values: a tie goes to the earlier
        position, and the dynamic rule compares against the largest
        float32 at or below `threshold`, which a float32 exceeds exactly
        when it exceeds the threshold in double precision."""
        import jax.numpy as jnp
        import numpy as np
        L = self.block_length
        steps = self.denoising_steps or L
        base, rem = divmod(L, steps)
        want = jnp.where(n_pass < steps, jnp.maximum(
            1, base + (n_pass < rem).astype(jnp.int32)), 1)
        n = jnp.minimum(want, masked.sum(-1))
        c, i = confidence, jnp.arange(L)
        # ahead[s, a, b]: masked position b ranks before position a
        ahead = masked[:, None, :] & (
            (c[:, None, :] > c[:, :, None])
            | ((c[:, None, :] == c[:, :, None]) & (i < i[:, None])))
        take = masked & (ahead.sum(-1) < n[:, None])
        if self.rule == "low_confidence_dynamic":
            t = np.float32(self.threshold)
            if float(t) > self.threshold:
                t = np.nextafter(t, np.float32(-np.inf))
            high = masked & (c > t)
            take = jnp.where((high.sum(-1) >= n)[:, None], high, take)
        return take


@dataclasses.dataclass(frozen=True)
class LearnedSelection:
    """What the "sparse" layers of an architecture say of their INDEXER
    (DeepSeek Sparse Attention): beside q, k, v a layer makes, a token,
    `num_heads` indexer queries qI [num_heads, head_dim], ONE indexer
    key kI [head_dim] (the only part that is cached) and `num_heads`
    float32 weights w; a query at t scores every key s <= t

        I(t, s) = sum_j  w_t[j] * scale * relu(qI_t[j] . kI_s)

    in float32, and attends the `min(topk, t + 1)` keys with the largest
    scores, equal scores going to the LOWER position; every attention
    head shares the selection. `rope_dims` leading dims of qI and kI
    carry the rotary embedding, `eps` is the key norm's."""
    num_heads: int
    head_dim: int
    topk: int
    rope_dims: int
    scale: float
    eps: float = 1e-6


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """What the "latent" layers of an architecture say of their cache
    (multi-head latent attention, DeepSeek-V2): a token caches ONE row
    shared by every query head, its latent `c` (`value_dim` numbers,
    which is also what the values are made of) and its rotary key
    (`rope_dim`), in `lanes` lanes (whole lane tiles, zeros past the
    two). A latent layer calls `attend(qa, row, None, li, (q_nope,
    q_pe, kv_b))`: `qa [T, H, lanes]` its absorbed query rows, `row [T,
    lanes]` its tokens' latent rows, and for the EXPANDED form the query
    heads `[T, H, nope_dim]`, `[T, H, rope_dim]` and the up-projection
    `kv_b [value_dim, H (nope_dim + v_dim)]` (a head's W_UK columns,
    then its W_UV). The engine appends the rows and returns `(lat, o,
    rows)`: for the rows it attended absorbed, `lat [T, H, value_dim]`
    = `softmax(scale * qa . rows) . rows[:, :value_dim]` over the
    slot's keys up to the row's position, which the layer up-projects
    itself; for the rows of `rows [T]` bool, `o [T, H, v_dim]` the
    head outputs of the expanded form (`o` and `rows` None where no row
    took it)."""
    value_dim: int
    rope_dim: int
    scale: float
    nope_dim: int
    v_dim: int

    @property
    def lanes(self):
        return -(-(self.value_dim + self.rope_dim) // 128) * 128
