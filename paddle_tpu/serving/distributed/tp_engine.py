"""Tensor-parallel (x expert-parallel) serving engine: the ONE mixed
step, sharded.

`TPServingEngine` runs the exact host loop of `serving.engine`
(scheduler, paged KV bookkeeping, speculation, prefix cache — all
inherited unchanged) while the compiled mixed step executes SPMD over
a 1-D `("mp",)` mesh (`parallel.mp_layers.tp_mesh`) — or, for MoE
decoder stacks, a 2-D `("ep", "mp")` mesh (`mp_layers.tp_ep_mesh`):

* **Heads partitioned on `mp`** — the fused QKV out axis is permuted
  host-side into shard-major order (`mp_layers.shard_major_qkv`) so a
  plain `P(..., "mp")` sharding IS a head split; each shard's step
  body runs `_qkv`/attention with `cfg.num_heads = H // tp` and the
  `ops.pallas.flash_attention` ragged/verify/paged entries see
  per-shard head slices of q and of the pools.
* **KV block pools sharded on the head axis** — `[L, NB, BS, H, Dh]`
  pools carry `P(None, None, None, "mp")`, so each chip holds
  `1/tp` of the KV bytes; block TABLES stay replicated host-side
  numpy exactly as in the single-chip engine (identical block ids on
  every shard — the allocator remains one logical free list).
* **Row-parallel reductions in the body** — the attention out
  projection and ffn2 each hold a head/ff shard of their IN axis; the
  shared `_step_body` (engine.py) emits `lax.psum(..., "mp")` for both
  via `cfg.mp_axis`, after which hidden states are replicated and the
  sampling head runs identically on every shard.
* **Experts partitioned on `ep`** (`expert_parallel > 1`, MoE stacks
  only) — the expert-stacked FFN weights shard their expert axis over
  `ep` (`mp_layers.SERVING_MOE_TP_SPECS`) while each expert's FFN
  keeps the dense column/row mp split, so TP and EP COMPOSE. The token
  set is replicated across shards, so the training-style all_to_all
  degenerates: each shard slices its resident experts out of the
  (identical) `[E, C, D]` dispatch tensor, runs `E/ep` experts at
  capacity `C`, and the combine psums partial mixtures over `ep`
  (`incubate.nn.fused_transformer._ffn_moe_tokens`). Routing, gate
  logits and the MoE statistics are identical on every shard, so
  EP=2 serving is token-identical to EP=1 — same one-compile rule,
  capacity overflow still degrades to the residual path. KV pools
  replicate over `ep` (they shard over `mp` only).

Contracts (tests/test_tp_serving.py + tests/test_moe.py): token parity
with the TP=1/EP=1 engine on the CPU virtual-device mesh (speculation
on and off), still exactly ONE compile per engine, allocator/CoW/
truncate/prefix-cache invariants unchanged per shard.
"""
from __future__ import annotations

from ...parallel import shard_map as _shard_map
from ...parallel.mp_layers import (serving_tp_spec, shard_major_qkv,
                                   tp_ep_mesh, tp_mesh)
from ..engine import ServingEngine


class TPServingEngine(ServingEngine):
    """`ServingEngine` with the mixed step sharded over an `mp` (or
    `ep x mp` for MoE) mesh.

    `tensor_parallel=1` degrades to a 1-device mesh (useful for
    exercising the shard_map plumbing without parallelism);
    `expert_parallel > 1` shards a MoE stack's experts over the extra
    `ep` mesh rows. The host API is identical to the base engine.

    Device-resident multi-tick decode (ISSUE 18) composes for free:
    the base engine wraps the RESULT of `_build_step()` — here the
    shard_map'ed body — in its `lax.while_loop`, so the loop sits
    OUTSIDE the mesh partitioning and the control tail (n_ticks/eos/
    remain/cap[/slot_ad][/draft ring + counts]) rides as replicated
    host inputs like the flat-token data args. On-device speculation
    (ISSUE 19) inherits the same way: the loop's drafter/accept/ring
    math runs on replicated inputs outside shard_map, so a TP=2 spec
    engine traces the IDENTICAL drafter as TP=1. Token identity vs
    N=1 at TP=2 and the one-compile budget are asserted by
    tests/test_multitick.py.
    """

    def __init__(self, model, *, tensor_parallel=2, expert_parallel=1,
                 mesh=None, **kw):
        dec = model.decoder
        tp = int(tensor_parallel)
        ep = int(expert_parallel)
        n_exp = int(getattr(dec, "_num_experts", 0))
        if ep > 1 and not n_exp:
            raise ValueError(
                "expert_parallel > 1 needs a MoE decoder stack "
                "(FusedMultiTransformerMoe)")
        if n_exp and n_exp % ep:
            raise ValueError(
                f"num_experts={n_exp} not divisible by "
                f"expert_parallel={ep}")
        if dec.num_heads % tp:
            raise ValueError(
                f"num_heads={dec.num_heads} not divisible by "
                f"tensor_parallel={tp}")
        if dec.dim_feedforward % tp:
            raise ValueError(
                f"dim_feedforward={dec.dim_feedforward} not divisible "
                f"by tensor_parallel={tp}")
        self.tensor_parallel = tp
        self.expert_parallel = ep
        # MoE stacks always ride the 2-D mesh (the expert param specs
        # name "ep" even at ep=1); dense stacks keep the 1-D mesh the
        # PR 8 contracts pinned
        if mesh is not None:
            self.mesh = mesh
        elif n_exp:
            self.mesh = tp_ep_mesh(tp, ep)
        else:
            self.mesh = tp_mesh(tp)
        want = ("ep", "mp") if n_exp else ("mp",)
        if tuple(self.mesh.axis_names) != want:
            raise ValueError(
                f"serving mesh for this stack must be {want}, got "
                f"{self.mesh.axis_names}")
        super().__init__(model, **kw)
        self._shard_state()

    def _flight_extra(self):
        # the mesh split rides every flight-recorder step record, so a
        # merged fleet chrome trace tells a TP=2/EP=2 replica's step
        # slices from a single-chip sibling's at a glance
        return {"tp": self.tensor_parallel, "ep": self.expert_parallel}

    # ------------------------------------------------------- sharding
    def _pool_spec(self):
        # head axis (index 3) of the [L, NB, BS, H, Dh] pools, in the
        # CANONICAL normal form (analysis.specs): the jit cache keys on
        # input shardings, so the spec the initial device_put places
        # the pools with must be byte-identical to the spec the step's
        # outputs carry — trailing Nones trimmed (the PR 8 lesson) and
        # the size-1 "mp" entry dropped to P() at tp=1 (the PR 10
        # EP-only-mesh lesson, caught by tools/moe_smoke.py) — or the
        # SECOND step pays a silent full recompile. canonicalize_spec
        # is the one shared definition of that form (the recompile-
        # hazard lint rule RH201/RH202 checks against the same logic).
        # Under the 2-D MoE mesh the same spec replicates over ep.
        from jax.sharding import PartitionSpec as P

        from ...analysis.specs import canonicalize_spec
        return canonicalize_spec(P(None, None, None, "mp"), self.mesh)

    def _summary_spec(self):
        # the block-summary pools (ISSUE 15) are [L, NB, H, Dh]: the
        # head axis sits at index 2, one spot earlier than in the
        # [L, NB, BS, H, Dh] payload pools — same canonical-form
        # discipline as _pool_spec
        from jax.sharding import PartitionSpec as P

        from ...analysis.specs import canonicalize_spec
        return canonicalize_spec(P(None, None, "mp"), self.mesh)

    def _array_specs(self):
        """One PartitionSpec per entry of `self._arrays` (the order
        `_gen_tensors` fixes: we, pe, decoder params, ln_f w/b, head —
        embeddings and the lm head replicate; decoder params follow
        `mp_layers.SERVING_TP_SPECS`, MoE experts
        `SERVING_MOE_TP_SPECS`). The ENGINE's name list is the source
        of truth: engine-side expert quantization may have added
        ffn1_s/ffn2_s entries the float model never had."""
        from jax.sharding import PartitionSpec as P
        names = self._names
        moe = self.num_experts > 0
        return ([P(), P()]
                + [serving_tp_spec(n, moe=moe)[0] for n in names]
                + [P(), P(), P()])

    def step_devices(self):
        return list(self.mesh.devices.flat)

    def _adapter_specs(self):
        """PartitionSpec per adapter slot tensor, in
        `AdapterCache.array_names` order (SERVING_LORA_TP_SPECS)."""
        return [serving_tp_spec(n)[0]
                for n in self.adapters.array_names]

    def _shard_state(self):
        """Re-lay out the cast param arrays (shard-major QKV) and
        device_put params + KV pools + adapter slot tensors to their
        mesh shardings, so the first step call compiles against the
        final layouts and never pays a resharding copy."""
        import functools

        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ...analysis.specs import canonicalize_spec

        dec = self.model.decoder
        names = self._names
        H, Dh = dec.num_heads, dec.head_dim
        moe = self.num_experts > 0
        specs = self._array_specs()
        permute = ([False, False]
                   + [serving_tp_spec(n, moe=moe)[1] for n in names]
                   + [False, False, False])
        out = []
        for arr, spec, perm in zip(self._arrays, specs, permute):
            if perm:
                arr = shard_major_qkv(arr, self.tensor_parallel, H, Dh)
            out.append(jax.device_put(
                arr, NamedSharding(self.mesh, spec)))
        self._arrays = out
        # what the host makes a step (the packed plan; counts, a device
        # loop's tail) goes up replicated, and the key sits there from
        # the start, as the step hands it back: the jit cache keys on
        # input shardings, the key's too
        replicated = NamedSharding(self.mesh, canonicalize_spec(
            P(), self.mesh))
        self._upload = functools.partial(jax.device_put,
                                         device=replicated)
        self._rng = jax.device_put(self._rng, replicated)
        if self._ahead:
            # the tokens of the step before: the step hands them back
            # replicated, and takes them so
            self._prev_tokens = jax.device_put(self._prev_tokens,
                                               replicated)
        psh = NamedSharding(self.mesh, self._pool_spec())
        ssh = NamedSharding(self.mesh, self._summary_spec())

        def _place(kv, _psh=psh, _ssh=ssh, _put=jax.device_put):
            kv.k_pool = _put(kv.k_pool, _psh)
            kv.v_pool = _put(kv.v_pool, _psh)
            if kv.quantized:
                # the [L, NB, BS, H] scale pools shard on the same
                # (head) axis — trailing-None-trimmed, P(None, None,
                # None, "mp") happens to be the pool spec verbatim
                kv.k_scale = _put(kv.k_scale, _psh)
                kv.v_scale = _put(kv.v_scale, _psh)
            if kv.summaries:
                # [L, NB, H, Dh] summary pools: head axis at index 2
                kv.k_sum_min = _put(kv.k_sum_min, _ssh)
                kv.k_sum_max = _put(kv.k_sum_max, _ssh)

        _place(self.kv)
        # KV block transport (disaggregated serving): imported pools
        # come out of the scatter executable with whatever sharding
        # GSPMD inferred — re-pin the canonical spec so the next mixed
        # step's input shardings stay byte-identical (a drift here is
        # a silent full recompile, the PR 8/PR 10 lesson)
        self.kv.place_pools = _place
        if self.adapters is not None:
            # adapter slot tensors: column-parallel B shards its out
            # axis (qkv's shard-major-permuted), row-parallel A its in
            # axis — the engine's step body then adds each delta on
            # the same side of the psum as its base matmul
            ad_sharding = {
                n: NamedSharding(self.mesh, canonicalize_spec(
                    spec, self.mesh))
                for n, spec in zip(self.adapters.array_names,
                                   self._adapter_specs())}
            for n in self.adapters.array_names:
                self.adapters._arrays[n] = jax.device_put(
                    self.adapters._arrays[n], ad_sharding[n])
            tp = self.tensor_parallel

            def _prepare(name, arr, _tp=tp, _H=H, _Dh=Dh):
                # host payload re-layout before the slot write: qkv's
                # B out axis must be shard-major like qkv_w so a plain
                # "mp" split IS a head split
                if serving_tp_spec(name)[1]:
                    import numpy as _np
                    return _np.asarray(shard_major_qkv(
                        jax.numpy.asarray(arr), _tp, _H, _Dh))
                return arr

            def _place_adapters(cache, _sh=ad_sharding,
                                _put=jax.device_put):
                # the donated load write's outputs re-pin the
                # canonical shardings (same lesson as place_pools)
                for n in cache.array_names:
                    cache._arrays[n] = _put(cache._arrays[n], _sh[n])

            self.adapters.prepare = _prepare
            self.adapters.place = _place_adapters

    # ------------------------------------------------- fleet weight swap
    def _prep_swap_arrays(self, arrays):
        """TP staging for `swap_weights` (ISSUE 17): the canonical
        model-order checkpoint gets the SAME host-side shard-major QKV
        permute `_shard_state` applies, so a plain "mp" split of the
        swapped arrays is still a head split. Shapes are unchanged —
        the shape gate in `swap_weights` still compares canonically."""
        import jax.numpy as jnp
        import numpy as np

        dec = self.model.decoder
        H, Dh = dec.num_heads, dec.head_dim
        moe = self.num_experts > 0
        permute = ([False, False]
                   + [serving_tp_spec(n, moe=moe)[1]
                      for n in self._names]
                   + [False, False, False])
        out = []
        for arr, perm in zip(arrays, permute):
            if perm:
                arr = np.asarray(shard_major_qkv(
                    jnp.asarray(arr), self.tensor_parallel, H, Dh))
            out.append(np.asarray(arr))
        return out

    def _swap_jit_kwargs(self):
        """Pin the swap cast's outputs to the step's param shardings:
        the jit cache keys on input shardings, so swapped arrays must
        come out byte-identical to what `_shard_state` placed — or the
        next mixed step would pay a silent full recompile (the PR 8
        lesson, applied to upgrades)."""
        from jax.sharding import NamedSharding
        return {"out_shardings": [
            NamedSharding(self.mesh, spec)
            for spec in self._array_specs()]}

    # ------------------------------------------------------ mixed step
    def _step_cfg(self):
        """Per-shard decoder config: local head count + the psum axis
        (engine._step_body emits the row-parallel reductions off it);
        MoE stacks additionally carry the ep axis/size for the
        slice-dispatch + psum-combine in `_ffn_moe_tokens`. Starts
        from the base engine's cfg so engine-side expert quantization
        (moe_quant_bits) composes with sharding."""
        import dataclasses
        cfg = ServingEngine._step_cfg(self)
        rep = dict(num_heads=cfg.num_heads // self.tensor_parallel,
                   mp_axis="mp")
        if self.num_experts:
            rep.update(ep_axis="ep", ep_size=self.expert_parallel)
        return dataclasses.replace(cfg, **rep)

    def _build_step(self):
        from jax.sharding import PartitionSpec as P

        from .. import batcher

        from ...analysis.specs import canonicalize_spec

        body = self._step_body(self._step_cfg())
        pool = self._pool_spec()
        rep = P()
        # quantized pools ride (k_scale, v_scale) right after the
        # pools, sharded on the same head axis; summary-tracking pools
        # add (k_sum_min, k_sum_max) after those with the head axis
        # one spot earlier — the kv_cache._pools() order; the step
        # returns them all
        pools = (pool,) * (4 if self.kv.quantized else 2)
        if self.kv.summaries:
            pools += (self._summary_spec(),) * 2
        # adapter slot tensors follow the pools (engine._step_body's
        # rest-parse order), each under its SERVING_LORA_TP_SPECS
        # sharding; the per-token adapter ids ride the packed plan
        lora_in = tuple(
            canonicalize_spec(s, self.mesh)
            for s in self._adapter_specs()) \
            if self.adapters is not None else ()
        # the packed plan (flat tokens, sample index, block table,
        # adapter ids: one buffer), the optional logit-processor count
        # histogram (ISSUE 19: the [S, Vb] device-updatable form of the
        # old history window) and the key replicate; sampled tokens
        # come off the replicated post-psum hidden state so the token
        # outputs replicate too, and so does the advanced key, the last
        # output (check_vma=False: the checker can't see through the
        # scanned psum)
        # (an engine that can dispatch ahead: the sampled tokens of
        # the step before, between the plan and the key)
        n_data = 2 + (1 if batcher.needs_history(self.sampling) else 0) \
            + (1 if self._ahead else 0)
        data_in = (rep,) * n_data
        # spec-sampling adds the residual-resample + accept matrices
        # to the verify outputs (engine._step_body) — all replicated,
        # like the token outputs
        if self.draft_k:
            tok_out = (rep,) * (4 if self.spec_sampling else 2)
        else:
            tok_out = rep
        # MoE stats (counts/dropped/aux) come off replicated routing
        # inputs, identical on every shard
        stats_out = ({"counts": rep, "dropped": rep, "aux": rep},) \
            if self.num_experts else ()
        return _shard_map(
            body, mesh=self.mesh,
            in_specs=(self._array_specs(),) + pools + lora_in + data_in,
            out_specs=(tok_out,) + pools + stats_out + (rep,),
            check_vma=False)
