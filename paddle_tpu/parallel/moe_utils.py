"""MoE routing + dispatch utilities.

Two layers live here:

1. **The fixed-shape top-k capacity router** (ISSUE 10): softmax gate,
   per-expert capacity slots, overflow dropped (the caller's residual
   path covers dropped tokens), GShard-style load-balance loss and
   router z-loss. Dispatch and combine are expressed as one-hot
   einsums over `[T, k, C]` / `[T, k, E]` masks, so the whole MoE
   block is static-shape and XLA fuses it — the TPU replacement for
   the reference's `number_count`/`assign_pos`/
   `prune_gate_by_capacity` CUDA op chain. Every MoE consumer shares
   this one core: `parallel.hybrid_gpt._moe_ffn` (training),
   `incubate.nn.fused_transformer._ffn_moe` (fused stack + eager),
   `incubate.distributed.models.moe.MoELayer`, and the serving mixed
   step (`serving.engine`).

2. **Expert-parallel exchange.** `all_to_all_dispatch` /
   `all_to_all_combine` move the `[E, C, d]` dispatch tensors over an
   expert-parallel mesh axis inside a compiled step (the
   `global_scatter/global_gather` capability riding `lax.all_to_all`
   on ICI); the eager `global_scatter/global_gather` wrappers keep
   parity with `python/paddle/distributed/utils/moe_utils.py:21,144`
   for the reference's dygraph API surface.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from ..core.tensor import Tensor
from . import env as dist_env


# ---------------------------------------------------------------------
# fixed-shape top-k capacity routing (pure jax; shapes never depend on
# routing decisions, so the consumers stay one-compile)
# ---------------------------------------------------------------------


def expert_capacity(num_tokens, num_experts, top_k, capacity_factor):
    """Per-expert capacity slots C = ceil(factor * T * k / E), floored
    at 1. At `capacity_factor >= E / top_k` (e.g. >= top_k when
    E == top_k**2) C reaches T, so no token can overflow — the
    zero-drop regime the smoke contracts pin."""
    c = capacity_factor * float(num_tokens) * float(top_k) \
        / float(num_experts)
    return max(1, int(math.ceil(c)))


@dataclasses.dataclass
class DispatchPlan:
    """Fixed-shape masks for one routed token set.

    disp  [T, k, C]  0/1 dispatch mask (capacity slot per choice);
                     None when built with `build_masks=False` (the
                     index-based grouped-matmul path never reads it)
    comb  [T, k, C]  gate-weighted combine mask (disp * gate value);
                     None like `disp` under `build_masks=False`
    e_oh  [T, k, E]  expert one-hot per choice (invalid/padded rows 0)
    counts  [E] f32  tokens each expert actually received (post-drop)
    dropped    f32   (token, choice) pairs lost to capacity overflow
    gate_idx [T, k]  chosen expert per (token, choice)
    slot  [T, k]     capacity slot within the chosen expert
    in_cap [T, k]    bool: the choice landed inside capacity
    gates [T, k]     renormalized gate values (the combine weights)
    """
    disp: object
    comb: object
    e_oh: object
    counts: object
    dropped: object
    gate_idx: object = None
    slot: object = None
    in_cap: object = None
    gates: object = None


def capacity_dispatch(gate_val, gate_idx, num_experts, capacity,
                      valid=None, dtype=None, build_masks=True):
    """Build the dispatch/combine masks for already-chosen experts.

    gate_val/gate_idx [T, k]; `valid` [T] bool masks padding tokens
    (they claim no capacity and never reach an expert — the serving
    engine's empty slots). Slot assignment is a cumulative count in
    token-major, choice-minor order, so earlier tokens win capacity
    (GShard's position-in-expert semantics); an overflowing choice is
    dropped: its disp/comb rows are zero and the caller's residual
    connection carries the token through unchanged.

    `build_masks=False` skips materializing the [T, k, C] one-hot
    disp/comb masks — the index-based dispatch/combine below only
    needs the (gate_idx, slot, in_cap, gates) integer plan, and for
    serving-scale C the masks are the dominant memory term."""
    import jax
    import jax.numpy as jnp

    T, k = gate_val.shape
    E, C = int(num_experts), int(capacity)
    dtype = dtype or gate_val.dtype
    oh = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)          # [T,k,E]
    if valid is not None:
        oh = oh * valid.astype(jnp.int32)[:, None, None]
    flat_oh = oh.reshape(T * k, E)
    # position of each (token, choice) within its expert's arrival order
    pos = jnp.cumsum(flat_oh, axis=0) * flat_oh - 1            # [T*k,E]
    slot = jnp.sum(pos * flat_oh, axis=-1).reshape(T, k)       # [T,k]
    routed = jnp.sum(oh, axis=-1) > 0                          # [T,k]
    in_cap = routed & (slot < C)
    disp = comb = None
    if build_masks:
        disp = (jax.nn.one_hot(slot, C, dtype=dtype)
                * in_cap[..., None].astype(dtype))             # [T,k,C]
        comb = disp * gate_val.astype(dtype)[..., None]
    e_oh = oh.astype(dtype)
    # counts summed in f32 from the int masks: a bf16 compute dtype
    # would round the running sum past ~256 tokens per expert and
    # break the exact-count contracts (sum == T*k) the smokes pin
    kept = jnp.sum(oh.astype(jnp.float32)
                   * in_cap[..., None].astype(jnp.float32),
                   axis=(0, 1))                                # [E]
    dropped = (jnp.sum(routed.astype(jnp.float32))
               - jnp.sum(in_cap.astype(jnp.float32)))
    return DispatchPlan(disp=disp, comb=comb, e_oh=e_oh, counts=kept,
                        dropped=dropped, gate_idx=gate_idx, slot=slot,
                        in_cap=in_cap, gates=gate_val)


def _masked_axis_sums(vals, valid, axes):
    """Sum `vals` ([T, ...]) over tokens (masked by `valid`) and over
    the given mesh axes; returns (sums, n_tokens) — the ingredients of
    an EP/DP-invariant mean."""
    import jax
    import jax.numpy as jnp

    if valid is not None:
        v = valid.astype(vals.dtype)
        vals = vals * v.reshape((-1,) + (1,) * (vals.ndim - 1))
        n = jnp.sum(v.astype(jnp.float32))
    else:
        n = jnp.asarray(float(vals.shape[0]), jnp.float32)
    s = jnp.sum(vals, axis=0)
    if axes:
        s = jax.lax.psum(s, axes)
        n = jax.lax.psum(n, axes)
    return s, n


def router_balance_loss(probs, e_oh, valid=None, axes=None):
    """GShard/Switch load-balance loss, top-k generalized:

        aux = E * sum_e  mean_t(probs[t, e]) * f_e
        f_e = (1 / (T * k)) * sum_{t,j} 1[choice (t, j) routed to e]

    Uniform routing gives aux == 1 (the minimum for a fixed me). When
    `axes` names mesh axes (("dp", "ep") in the hybrid step), the two
    means are computed over the GLOBAL token set via psums, so the
    loss — and its gradient — is invariant to how tokens are sharded
    (the EP=2 vs EP=1 parity contract)."""
    import jax.numpy as jnp

    E = probs.shape[-1]
    k = e_oh.shape[1]
    me_s, n = _masked_axis_sums(probs.astype(jnp.float32), valid, axes)
    ce_s, _ = _masked_axis_sums(
        jnp.sum(e_oh.astype(jnp.float32), axis=1), valid, axes)
    n = jnp.maximum(n, 1.0)
    me = me_s / n
    ce = ce_s / (n * float(k))
    return float(E) * jnp.sum(me * ce)


def router_z_loss(logits, valid=None, axes=None):
    """Router z-loss (ST-MoE): mean_t logsumexp(logits[t])^2 — keeps
    the gate logits small so the softmax stays in its stable range."""
    import jax
    import jax.numpy as jnp

    z = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1) ** 2
    s, n = _masked_axis_sums(z, valid, axes)
    return s / jnp.maximum(n, 1.0)


@dataclasses.dataclass
class RouterOutput:
    plan: DispatchPlan
    gates: object        # [T, k] renormalized top-k gate values
    balance_loss: object  # scalar f32
    z_loss: object        # scalar f32


def top_k_routing(logits, top_k, capacity, valid=None, axes=None,
                  dtype=None, build_masks=True):
    """Softmax gate -> top-k -> renormalize -> capacity dispatch.

    logits [T, E] f32-castable; returns a `RouterOutput` whose plan
    carries the fixed-shape dispatch/combine masks plus the aux
    losses. `axes` (mesh axis names) makes the aux statistics global —
    pass the data-sharding axes when tracing inside shard_map.
    `build_masks=False` keeps the plan index-only (the grouped-matmul
    dispatch path — see `capacity_dispatch`)."""
    import jax
    import jax.numpy as jnp

    lf = logits.astype(jnp.float32)
    probs = jax.nn.softmax(lf, axis=-1)
    topv, topi = jax.lax.top_k(probs, int(top_k))
    gates = topv / jnp.maximum(
        jnp.sum(topv, axis=-1, keepdims=True), 1e-12)
    plan = capacity_dispatch(gates, topi, logits.shape[-1], capacity,
                             valid=valid, dtype=dtype or logits.dtype,
                             build_masks=build_masks)
    aux = router_balance_loss(probs, plan.e_oh, valid=valid, axes=axes)
    z = router_z_loss(lf, valid=valid, axes=axes)
    return RouterOutput(plan=plan, gates=gates, balance_loss=aux,
                        z_loss=z)


def dispatch_tokens(x, plan, e_oh=None):
    """x [T, d] -> dispatched [E, C, d] (each expert's capacity
    buffer, zero-padded on unclaimed slots). Pass a sliced `e_oh`
    ([T, k, E_loc]) to build only one shard's resident-expert buffers
    — the serving EP path, where computing all E and slicing after
    would waste (ep-1)/ep of the dispatch einsum."""
    import jax.numpy as jnp
    e_oh = plan.e_oh if e_oh is None else e_oh
    return jnp.einsum("tkc,tke,td->ecd", plan.disp, e_oh,
                      x.astype(plan.disp.dtype))


def combine_tokens(eout, plan):
    """eout [E, C, d] expert outputs -> [T, d] gate-weighted mixture;
    dropped (token, choice) pairs contribute 0."""
    import jax.numpy as jnp
    return jnp.einsum("tkc,tke,ecd->td", plan.comb, plan.e_oh,
                      eout.astype(plan.comb.dtype))


# ---------------------------------------------------------------------
# index-based dispatch/combine (ISSUE 11): the grouped-expert-matmul
# companions. Instead of contracting [T, k, C] x [T, k, E] one-hot
# masks, the capacity assignment becomes ONE [E, C] token-index table
# (a scatter) and dispatch/combine become gathers — no mask tensor is
# ever materialized, and the expert FFN runs on the dense [E, C, d]
# buffers via `ops.pallas.grouped_matmul.grouped_expert_matmul`.
# The einsum pair above stays the parity oracle and the fallback.
# ---------------------------------------------------------------------


def dispatch_indices(plan, num_experts, capacity):
    """[E, C] int32 token index per capacity slot (-1 = unclaimed).

    Each in-capacity (token, choice) owns a unique (expert, slot) by
    construction (`slot` is the arrival position within the expert),
    so the scatter has no collisions; dropped/padded choices are
    routed out of bounds and dropped by the scatter mode."""
    import jax.numpy as jnp
    T, k = plan.slot.shape
    E, C = int(num_experts), int(capacity)
    ok = plan.in_cap.reshape(-1)
    e = jnp.where(ok, plan.gate_idx.reshape(-1), E)
    c = jnp.where(ok, plan.slot.reshape(-1), 0)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    tos = jnp.full((E, C), -1, jnp.int32)
    return tos.at[e, c].set(tok, mode="drop")


def dispatch_tokens_indexed(x, plan, num_experts, capacity,
                            indices=None):
    """x [T, d] -> [E, C, d] capacity buffers via gather (unclaimed
    slots zero) — semantically identical to `dispatch_tokens`."""
    import jax.numpy as jnp
    tos = dispatch_indices(plan, num_experts, capacity) \
        if indices is None else indices
    g = x[jnp.maximum(tos, 0)]                       # [E, C, d]
    return g * (tos >= 0).astype(x.dtype)[..., None]


def combine_tokens_indexed(eout, plan, e_offset=0, num_local=None):
    """eout [E_loc, C, d] -> [T, d] gate-weighted mixture via gather —
    semantically identical to `combine_tokens`. `e_offset`/`num_local`
    select a resident expert range (the serving EP path: each shard
    combines only its local experts' outputs and psums the partial
    mixtures over the ep axis)."""
    import jax.numpy as jnp
    E_loc, C = eout.shape[0], eout.shape[1]
    if num_local is None:
        num_local = E_loc
    e = plan.gate_idx
    local = plan.in_cap & (e >= e_offset) & (e < e_offset + num_local)
    el = jnp.clip(e - e_offset, 0, E_loc - 1)
    cl = jnp.clip(plan.slot, 0, C - 1)
    vals = eout[el, cl]                              # [T, k, d]
    w = plan.gates.astype(eout.dtype) * local.astype(eout.dtype)
    return jnp.sum(vals * w[..., None], axis=1)


# ---------------------------------------------------------------------
# expert-parallel exchange over a mesh axis (inside shard_map)
# ---------------------------------------------------------------------


def all_to_all_dispatch(dispatched, axis, ep):
    """[E, C, d] per-rank dispatch buffers -> [E_loc, ep * C, d] per-
    expert inputs on the expert's owner rank. The compiled
    `global_scatter`: each rank keeps the buckets of its resident
    experts from every source rank (the received leading dim indexes
    the source, concatenated into the capacity axis)."""
    import jax
    import jax.numpy as jnp
    E, C, d = dispatched.shape
    E_loc = E // int(ep)
    t = dispatched.reshape(int(ep), E_loc, C, d)
    t = jax.lax.all_to_all(t, axis, split_axis=0, concat_axis=0,
                           tiled=False)
    return jnp.swapaxes(t, 0, 1).reshape(E_loc, int(ep) * C, d)


def all_to_all_combine(eout, axis, ep):
    """Inverse of `all_to_all_dispatch` (the compiled `global_gather`):
    [E_loc, ep * C, d] expert outputs -> [E, C, d] back on the token
    owners."""
    import jax
    import jax.numpy as jnp
    E_loc, epC, d = eout.shape
    C = epC // int(ep)
    t = jnp.swapaxes(eout.reshape(E_loc, int(ep), C, d), 0, 1)
    t = jax.lax.all_to_all(t, axis, split_axis=0, concat_axis=0,
                           tiled=False)
    return t.reshape(E_loc * int(ep), C, d)


def _counts(t):
    return np.asarray(t._data if isinstance(t, Tensor) else t,
                      np.int64).reshape(-1)


def _world():
    # eager per-"card" exchange: a card is a PROCESS under the
    # single-controller SPMD model (the 8 local devices of one process
    # are driven by one copy of this python code)
    import jax
    return jax.process_count()


def global_scatter(x, local_count, global_count, group=None,
                   use_calc_stream=True):
    """x [B, d]; local_count/global_count [n_expert * world_size].
    Returns the rows this card's experts receive (expert-major)."""
    world = _world()
    lc, gc = _counts(local_count), _counts(global_count)
    arr = np.asarray(x._data if isinstance(x, Tensor) else x)
    if world == 1:
        # single card: receiving (card0, expert e) == sending bucket e;
        # x is already bucket-ordered by local_count
        if not np.array_equal(lc, gc):
            raise ValueError(
                "global_scatter single-card: local_count != global_count")
        return Tensor(arr[:int(lc.sum())])
    # multi-card eager: exchange the per-bucket segments over the object
    # collective (CPU path; compiled MoE uses all_to_all on-device)
    from .comm_extras import all_gather_object
    n_e = lc.size // world
    offs = np.concatenate([[0], np.cumsum(lc)])
    segs = [arr[offs[i]:offs[i + 1]] for i in range(lc.size)]
    everyone = []
    all_gather_object(everyone, segs, group=group)
    rank = dist_env.get_rank()
    out = []
    for src in range(world):               # global_count layout
        for e in range(n_e):
            out.append(everyone[src][rank * n_e + e])
    got = np.concatenate([s for s in out if len(s)]) if any(
        len(s) for s in out) else arr[:0]
    if got.shape[0] != int(gc.sum()):
        raise ValueError("global_scatter: global_count mismatch")
    return Tensor(got)


def global_gather(x, local_count, global_count, group=None,
                  use_calc_stream=True):
    """Inverse of global_scatter: return expert outputs to the cards
    that sent the tokens."""
    world = _world()
    lc, gc = _counts(local_count), _counts(global_count)
    arr = np.asarray(x._data if isinstance(x, Tensor) else x)
    if world == 1:
        if not np.array_equal(lc, gc):
            raise ValueError(
                "global_gather single-card: local_count != global_count")
        return Tensor(arr[:int(gc.sum())])
    from .comm_extras import all_gather_object
    n_e = lc.size // world
    rank = dist_env.get_rank()
    offs = np.concatenate([[0], np.cumsum(gc)])
    # my received buckets, keyed by (src card, expert)
    segs = [arr[offs[i]:offs[i + 1]] for i in range(gc.size)]
    everyone = []
    all_gather_object(everyone, segs, group=group)
    out = []
    for dst in range(world):               # local_count layout
        for e in range(n_e):
            # the rows I sent to (dst, e) came back in dst's bucket
            # indexed by my rank
            out.append(everyone[dst][rank * n_e + e])
    got = np.concatenate([s for s in out if len(s)]) if any(
        len(s) for s in out) else arr[:0]
    if got.shape[0] != int(lc.sum()):
        raise ValueError("global_gather: local_count mismatch")
    return Tensor(got)


# ---------------------------------------------------------------------
# sigmoid and softmax top-k routing and the share-aware DROPLESS expert
# layer (models/afmoe.py, models/sdar_moe.py: a chip of an expert-parallel deployment holds
# `experts_held` of the routed experts, routes over all of them, and
# computes its own experts' part; no capacity slot, no dropped pair)
# ---------------------------------------------------------------------


def route_sigmoid_topk(x, w_router, select_bias, top_k, *,
                       route_norm=True, route_scale=1.0):
    """Sigmoid router with a selection-only bias.

    x [T, D]; w_router [D, E]; select_bias [E]. Scores
    `s = sigmoid(x W)` in float32; the experts are CHOSEN by
    `top_k(s + select_bias)` and WEIGHTED by `s` alone, normalised over
    the chosen (`route_norm`) and scaled. Returns (idx [T, k] int32,
    weights [T, k] float32)."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + select_bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * route_scale


def route_softmax_topk(x, w_router, top_k, *, norm_topk=True):
    """Softmax router: `p = softmax(x W)` over ALL the experts in
    float32, the `top_k` largest chosen and weighted by `p`,
    renormalised over the chosen to sum 1 with `norm_topk`. x [T, D];
    w_router [D, E]. Returns (idx [T, k] int32, weights [T, k]
    float32), as `route_sigmoid_topk` does."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def dropless_expert_ffn(x, idx, weights, valid, w_gate, w_up, w_down,
                        expert_rank=0):
    """The held experts' part of a routed SwiGLU layer, nothing dropped.

    x [T, D] tokens; idx, weights [T, k] from the router over ALL the
    experts; valid [T] bool (padding tokens route nowhere); w_gate,
    w_up [Eh, D, F] and w_down [Eh, F, D] are the `Eh` experts held
    here, global experts `[Eh * expert_rank, Eh * (expert_rank + 1))`.
    Every (token, choice) pair that lands on a held expert is computed:
    the pairs are sorted by expert into tile-padded ragged groups
    (`grouped_matmul.ragged_layout`; static shapes from T and k alone)
    and the three matmuls run as ragged grouped matmuls. Pairs routed
    to experts held elsewhere cost nothing here and add nothing: that
    part of the sum is the other chips'.

    Returns (out [T, D] = sum over the held chosen experts of
    weight * expert(x), stats): stats holds int32 scalars
    `pairs_total`, `pairs_local`, `experts_hit`, `max_expert_pairs`."""
    import jax.numpy as jnp

    from ..ops.pallas import grouped_matmul as gm
    T, D = x.shape
    k = idx.shape[1]
    Eh = w_gate.shape[0]
    le = idx - expert_rank * Eh
    local = (le >= 0) & (le < Eh) & valid[:, None]            # [T, k]
    key = jnp.where(local, le, Eh).reshape(-1)                # [T*k]
    counts = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)
    order = jnp.argsort(key, stable=True)                     # pair ids
    skey = key[order]
    first = jnp.cumsum(counts) - counts          # group's first sorted
    rank = jnp.arange(T * k, dtype=jnp.int32) - first[skey]
    NT = gm.ragged_num_tiles(T * k, Eh)
    bm = gm.RAGGED_BLOCK_M
    row_start, tile_expert, n_used = gm.ragged_layout(counts[:Eh], NT)
    rows = NT * bm
    # sorted pair -> its row; pairs of absent experts go out of range
    dest_sorted = jnp.where(
        skey < Eh, jnp.append(row_start, 0)[skey] + rank, rows)
    src = jnp.full((rows,), T, jnp.int32).at[dest_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    xs = jnp.where((src < T)[:, None], x[jnp.minimum(src, T - 1)], 0)
    hidden = gm.ragged_expert_matmul(xs, w_gate, tile_expert, n_used,
                                     w_up)
    ys = gm.ragged_expert_matmul(hidden, w_down, tile_expert, n_used)
    dest = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.minimum(dest_sorted, rows - 1).astype(jnp.int32))
    picked = ys[dest].reshape(T, k, D).astype(jnp.float32)
    out = jnp.sum(jnp.where(local[..., None],
                            picked * weights[..., None], 0.0), axis=1)
    stats = {"pairs_total": jnp.sum(valid, dtype=jnp.int32) * k,
             "pairs_local": jnp.sum(local, dtype=jnp.int32),
             "experts_hit": jnp.sum(counts[:Eh] > 0, dtype=jnp.int32),
             "max_expert_pairs": jnp.max(counts[:Eh])}
    return out.astype(x.dtype), stats
