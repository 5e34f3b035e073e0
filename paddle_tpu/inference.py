"""paddle.inference — the serving API surface.

Parity: `paddle/fluid/inference/api/` (`AnalysisConfig`,
`AnalysisPredictor`, `create_predictor`, zero-copy tensors). TPU-native:
the "optimized program" is the AOT StableHLO module exported by
`paddle_tpu.jit.save(..., input_spec=...)`; XLA plays the role of the IR
pass pipeline + TensorRT. The predictor wraps `TranslatedLayer` with the
reference's handle-based API so serving code ports.
"""
from __future__ import annotations

import numpy as np

from . import jit as _jit
from .core.tensor import Tensor


class Config:
    """AnalysisConfig parity (the knobs that are meaningful on TPU),
    plus the continuous-batching serving knobs
    (`enable_continuous_batching` -> `create_serving_engine`)."""

    def __init__(self, model_prefix=None, params_file=None):
        self.model_prefix = model_prefix
        self._use_tpu = True
        self._threads = 1
        self._ir_optim = True
        self._serving = None
        self._max_pending = None
        self._tensor_parallel = None
        self._expert_parallel = None
        self._num_replicas = None
        self._router_policy = None
        self._sampling = None
        self._prefill_replicas = None
        self._decode_replicas = None
        self._migration = None

    # -- continuous batching (paddle_tpu.serving) -------------------------
    def enable_continuous_batching(self, max_slots=None, block_size=None,
                                   num_blocks=None, max_seq_len=None,
                                   token_budget=None, eos_token_id=None,
                                   cache_dtype=None, kv_dtype=None,
                                   draft_k=None,
                                   draft_ngram=None, draft_ring=None,
                                   penalty_vocab_bins=None,
                                   prefix_caching=None,
                                   max_pending=None, sampling=None,
                                   tensor_parallel=None,
                                   expert_parallel=None,
                                   num_replicas=None,
                                   router_policy=None,
                                   prefill_replicas=None,
                                   decode_replicas=None,
                                   migration=None,
                                   max_adapters=None, lora_rank=None,
                                   lora_alpha=None,
                                   moe_weight_dtype=None,
                                   sparse_blocks=None,
                                   sparse_recent=None,
                                   ticks_per_dispatch=None):
        """Opt the predictor surface into the paged-KV continuous
        batching engine (docs/SERVING.md). The knobs mirror
        `serving.ServingEngine`; None keeps the engine default.
        `draft_k > 0` turns on speculative multi-token decoding: an
        n-gram prompt-lookup draft proposes up to `draft_k` tokens per
        decode and one verify pass scores them all (greedy verifies by
        token identity, sampling by the rejection rule).
        `prefix_caching=True` enables the radix-tree prefix KV cache
        (cross-request reuse of shared prompt heads).
        `kv_dtype="int8"` stores the paged KV pools quantized with
        per-entry-per-head fp32 scales — roughly 2.7x the resident
        tokens per chip vs fp32 pools at a documented bounded logit
        divergence (docs/SERVING.md "KV quantization"). `max_pending`
        bounds the async frontend's admission queue
        (`create_serving_frontend`) — see docs/SERVING.md.

        Distributed serving (docs/SERVING.md "Distributed serving"):
        `sampling` is a `serving.SamplingConfig` (or a dict of its
        fields — strategy/temperature/top_k/top_p/penalties; every
        strategy composes with speculation). `tensor_parallel > 1`
        shards the mixed step + KV pools over an `mp` mesh
        (`serving.distributed.TPServingEngine`); for MoE decoder
        stacks `expert_parallel > 1` additionally shards the experts
        over the `ep` rows of a 2-D (ep, mp) mesh (docs/MOE.md);
        `num_replicas > 1` plus `create_serving_router` puts a
        prefix-affinity `ReplicaRouter` in front of that many
        frontends (`router_policy`: "affinity" | "round_robin").

        Disaggregated prefill/decode serving (docs/SERVING.md,
        "Disaggregated serving"): `prefill_replicas`/`decode_replicas`
        (both >= 1, replacing `num_replicas`) split the fleet into
        prefill-role replicas — chunked prefill only, requests hand
        off at the first token with their paged KV blocks streamed
        over the block transport — and decode-role replicas that admit
        the migrated requests mid-stream (greedy outputs stay
        token-identical to a monolithic fleet; decode replicas get a
        decode-sized token budget and keep `draft_k` speculation).
        `migration=True` (or a dict of `ReplicaRouter.
        MIGRATION_DEFAULTS` overrides: imbalance/interval/max_per_tick)
        additionally lets loaded decode replicas SHED live requests to
        lighter siblings instead of preempting them.

        Multi-tenant serving (docs/SERVING.md "Multi-tenant serving",
        ISSUE 14): `max_adapters > 0` gives the engine fixed LoRA
        adapter slot tensors (slot 0 reserved for the base model) —
        `engine.register_adapter(...)` + `Request.adapter_id` serve K
        finetunes through the ONE compiled mixed step, with pin/LRU
        slot eviction and near-zero marginal HBM per tenant;
        `lora_rank`/`lora_alpha` size the slots. `moe_weight_dtype`
        ("int8" | "int4") quantizes a float MoE stack's EXPERT weights
        at engine build — int4 packs two nibbles per byte with
        per-(expert, out-channel) fp16 scales, dequantized at the
        matmul tile load (ops/pallas/grouped_matmul.py).

        Long-context serving (docs/SERVING.md "Long-context serving",
        ISSUE 15): `sparse_blocks=B` turns on block-sparse paged
        decode attention — every decode/verify query scores the
        candidate KV blocks against per-block min/max key summaries
        and attends only B top-scoring blocks plus the first block
        (attention sink) and a `sparse_recent`-block recency window;
        `B >= allocated blocks` is token-identical to dense and
        sparsity never recompiles. `kv_dtype="fp8_e4m3"` stores the
        pools as e4m3 bytes under the int8 scale plumbing — half of
        int8's fp32-baseline bytes again, composable with sparsity,
        TP sharding, transport and the prefix cache.

        Device-resident decode (docs/SERVING.md "Device-resident
        decode", ISSUE 18/19): `ticks_per_dispatch=N` runs up to N
        decode ticks per host dispatch inside ONE on-device
        `lax.while_loop` (token-identical to N=1; still exactly one
        compiled mixed step), `"auto"` lets the engine pace N from its
        measured host-gap/tick-time ratio. Speculation and penalized
        sampling ride INSIDE the loop: `draft_ring=W` sizes the
        per-slot device token ring the in-loop n-gram drafter scans
        (default 64; >= 2 when drafting), and `penalty_vocab_bins=Vb`
        sizes the per-slot token-count histogram the repetition/
        presence penalties read (default: full vocab = exact HF
        semantics; smaller Vb trades penalty precision for state via
        `token % Vb` binning). Impossible combos raise ValueError at
        engine build rather than silently degrading. In a
        disaggregated fleet, prefill replicas are pinned to 1 tick and
        decode replicas default to 4."""
        # validate BEFORE any assignment: a raising call must leave the
        # config exactly as it was (callers catch and retry)
        if kv_dtype is not None:
            from .serving.kv_cache import KV_DTYPES
            if str(kv_dtype) not in KV_DTYPES:
                raise ValueError(
                    f"kv_dtype={kv_dtype!r} not supported; pick one "
                    f"of {sorted(KV_DTYPES)}")
        if (prefill_replicas is not None) != (decode_replicas is not None):
            raise ValueError(
                "prefill_replicas and decode_replicas come as a pair "
                "(a disaggregated fleet needs both roles)")
        if prefill_replicas is not None and num_replicas is not None:
            raise ValueError(
                "pass either num_replicas (monolithic fleet) or "
                "prefill_replicas/decode_replicas (disaggregated), "
                "not both")
        if ticks_per_dispatch is not None and ticks_per_dispatch != "auto":
            if not isinstance(ticks_per_dispatch, int) \
                    or isinstance(ticks_per_dispatch, bool) \
                    or ticks_per_dispatch < 1:
                raise ValueError(
                    f"ticks_per_dispatch={ticks_per_dispatch!r} must be "
                    "an int >= 1 or 'auto'")
        if draft_k is not None and (not isinstance(draft_k, int)
                                    or isinstance(draft_k, bool)
                                    or draft_k < 0):
            raise ValueError(f"draft_k={draft_k!r} must be an int >= 0")
        if draft_ring is not None and (not isinstance(draft_ring, int)
                                       or isinstance(draft_ring, bool)
                                       or draft_ring < 2):
            raise ValueError(
                f"draft_ring={draft_ring!r} must be an int >= 2 (the "
                "n-gram scan needs at least one earlier token besides "
                "the tail)")
        if penalty_vocab_bins is not None \
                and (not isinstance(penalty_vocab_bins, int)
                     or isinstance(penalty_vocab_bins, bool)
                     or penalty_vocab_bins < 1):
            raise ValueError(
                f"penalty_vocab_bins={penalty_vocab_bins!r} must be "
                "an int >= 1")
        self._serving = dict(
            max_slots=max_slots, block_size=block_size,
            num_blocks=num_blocks, max_seq_len=max_seq_len,
            token_budget=token_budget, eos_token_id=eos_token_id,
            cache_dtype=cache_dtype, kv_dtype=kv_dtype, draft_k=draft_k,
            draft_ngram=draft_ngram, draft_ring=draft_ring,
            penalty_vocab_bins=penalty_vocab_bins,
            prefix_caching=prefix_caching,
            max_adapters=max_adapters, lora_rank=lora_rank,
            lora_alpha=lora_alpha, moe_weight_dtype=moe_weight_dtype,
            sparse_blocks=sparse_blocks, sparse_recent=sparse_recent,
            ticks_per_dispatch=ticks_per_dispatch)
        self._max_pending = max_pending
        self._tensor_parallel = tensor_parallel
        self._expert_parallel = expert_parallel
        self._num_replicas = num_replicas
        self._router_policy = router_policy
        self._sampling = sampling
        self._prefill_replicas = prefill_replicas
        self._decode_replicas = decode_replicas
        self._migration = migration
        return self

    def continuous_batching_enabled(self):
        return self._serving is not None

    def serving_config(self):
        return dict(self._serving) if self._serving else None

    # gpu/trt/mkldnn switches accepted as no-ops: XLA owns optimization
    def enable_use_gpu(self, memory_mb=100, device_id=0):
        pass

    def disable_gpu(self):
        pass

    def enable_tensorrt_engine(self, *a, **k):
        pass

    def enable_mkldnn(self):
        pass

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def set_cpu_math_library_num_threads(self, n):
        self._threads = n

    def enable_memory_optim(self):
        pass


class _IOTensor:
    """zero-copy paddle_infer.Tensor handle parity."""

    def __init__(self, name, store, idx):
        self.name = name
        self._store = store
        self._idx = idx

    def copy_from_cpu(self, arr):
        self._store[self._idx] = np.ascontiguousarray(arr)

    def reshape(self, shape):
        pass

    def copy_to_cpu(self):
        return np.asarray(self._store[self._idx])


class Predictor:
    def __init__(self, config: Config):
        if config.model_prefix is None:
            raise ValueError("Config needs a model path prefix")
        self._layer = _jit.load(config.model_prefix)
        n_inputs = len(self._layer.meta.get("input_spec") or [1])
        self._inputs = [None] * n_inputs
        self._outputs = []

    def get_input_names(self):
        return [f"input_{i}" for i in range(len(self._inputs))]

    def get_input_handle(self, name):
        idx = int(name.rsplit("_", 1)[-1]) if name.startswith("input_") \
            else 0
        return _IOTensor(name, self._inputs, idx)

    def run(self, inputs=None):
        if inputs is not None:
            self._inputs = [np.asarray(a) for a in inputs]
        outs = self._layer(*self._inputs)
        self._outputs = [o.numpy() if isinstance(o, Tensor) else
                         np.asarray(o) for o in outs]
        return self._outputs

    def get_output_names(self):
        return [f"output_{i}" for i in range(len(self._outputs) or 1)]

    def get_output_handle(self, name):
        idx = int(name.rsplit("_", 1)[-1]) if name.startswith("output_") \
            else 0
        return _IOTensor(name, self._outputs, idx)


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def _resolve_sampling(config: Config, sampling):
    if sampling is not None:
        return sampling
    sc = config._sampling
    if sc is None:
        return None
    if isinstance(sc, dict):
        from .serving.batcher import SamplingConfig
        return SamplingConfig(**sc)
    return sc


def create_serving_engine(config: Config, model, sampling=None, seed=0,
                          mesh=None, **overrides):
    """Build a continuous-batching `serving.ServingEngine` from an
    `enable_continuous_batching()` config and a causal-LM serving model
    (`models.gpt.GPTForGeneration` or anything exposing the same
    `_gen_tensors`/decoder contract). This is the batch-serving mode of
    the AnalysisPredictor surface: one resident engine, many concurrent
    requests, instead of one `Predictor.run` per fixed-shape batch.

    With `tensor_parallel > 1` on the config the engine is a
    `serving.distributed.TPServingEngine`: same host loop, mixed step
    and KV pools sharded over an `mp` mesh (`mesh` overrides the
    default `parallel.mp_layers.tp_mesh` device pick). `overrides`
    replace individual engine kwargs after the config — the
    disaggregated `create_serving_router` uses this to give each
    replica its role (and prefill replicas `draft_k=0`)."""
    if not config.continuous_batching_enabled():
        raise ValueError(
            "call config.enable_continuous_batching(...) first")
    kw = {k: v for k, v in config.serving_config().items()
          if v is not None}
    kw.update(overrides)
    sampling = _resolve_sampling(config, sampling)
    tp = int(config._tensor_parallel or 1)
    ep = int(config._expert_parallel or 1)
    if tp > 1 or ep > 1:
        from .serving.distributed.tp_engine import TPServingEngine
        return TPServingEngine(model, tensor_parallel=tp,
                               expert_parallel=ep, mesh=mesh,
                               sampling=sampling, seed=seed, **kw)
    from .serving.engine import ServingEngine
    return ServingEngine(model, sampling=sampling, seed=seed, **kw)


def create_serving_router(config: Config, model, sampling=None, seed=0):
    """Build the multi-replica serving stack: `num_replicas` engines
    (tensor-parallel when `tensor_parallel > 1`; replica r takes the
    next `tp` local devices — one device at tp=1 — wrapping around)
    each behind a
    `ServingFrontend`, fronted by a prefix-affinity
    `serving.distributed.ReplicaRouter`. `async with router:` starts
    every replica's step loop plus the health prober;
    `submit()`/`stream()` dispatch with affinity, load balancing and
    failover (docs/SERVING.md "Distributed serving").

    With `prefill_replicas`/`decode_replicas` on the config the fleet
    is DISAGGREGATED instead: prefill-role engines (chunked prefill
    only, `draft_k` forced to 0) hand requests off at the first token
    over the KV block transport to decode-role engines (decode-sized
    token budgets, speculation kept), and `migration=` enables
    router-driven load shedding between decode replicas
    (docs/SERVING.md "Disaggregated serving")."""
    if not config.continuous_batching_enabled():
        raise ValueError(
            "call config.enable_continuous_batching(...) first")
    roles = None
    if config._prefill_replicas is not None:
        p, d = int(config._prefill_replicas), int(config._decode_replicas)
        if p < 1 or d < 1:
            raise ValueError(
                f"a disaggregated fleet needs prefill_replicas >= 1 "
                f"and decode_replicas >= 1, got {p}/{d}")
        roles = ["prefill"] * p + ["decode"] * d
        n = p + d
    else:
        n = int(config._num_replicas or 1)
        if n < 1:
            raise ValueError(f"num_replicas must be >= 1, got {n}")
    from .serving.distributed.router import ReplicaRouter
    from .serving.frontend import ServingFrontend
    import jax
    tp = int(config._tensor_parallel or 1)
    ep = int(config._expert_parallel or 1)
    devices = jax.devices()
    meshes = [None] * n
    if tp > 1 or ep > 1:
        from .parallel.mp_layers import tp_ep_mesh, tp_mesh
        world = tp * ep
        picks = [[devices[(r * world + i) % len(devices)]
                  for i in range(world)] for r in range(n)]
        # MoE stacks always serve over the 2-D (ep, mp) mesh, even at
        # expert_parallel=1 (the expert param specs name the ep axis)
        moe = bool(getattr(getattr(model, "decoder", None),
                           "_num_experts", 0))
        if ep > 1 or moe:
            meshes = [tp_ep_mesh(tp, ep, devices=d) for d in picks]
        else:
            meshes = [tp_mesh(tp, devices=d) for d in picks]
    fkw = {}
    if config._max_pending is not None:
        fkw["max_pending"] = int(config._max_pending)

    def _overrides(r):
        ov = _role_overrides(r)
        if meshes[r] is None:
            # a one-chip replica lives on ITS chip: replica r takes
            # local device r (wrapping around), like the tp picks above
            ov["device"] = devices[r % len(devices)]
        return ov

    def _role_overrides(r):
        if roles is None:
            return {}
        if roles[r] == "prefill":
            # prefill replicas never decode past the first token, so
            # speculation would only waste the reserved verify region
            # — and in a block-sparse fleet they likewise skip the
            # sparse decode region while still MAINTAINING the block
            # summaries (track_summaries), so their exported blocks
            # match a sparse decode replica's kv_meta geometry
            # ... and a chunked-prefill-only replica never has a
            # pure-decode plan, so multi-tick dispatches would just
            # stage dead control tensors: pin it to 1 tick
            ov = {"role": "prefill", "draft_k": 0,
                  "ticks_per_dispatch": 1}
            if (config.serving_config() or {}).get("sparse_blocks"):
                ov.update(sparse_blocks=None, track_summaries=True)
            return ov
        ov = {"role": "decode"}
        if (config.serving_config() or {}).get(
                "ticks_per_dispatch") is None:
            # decode replicas are where the host-dispatch gap lives —
            # default them onto the device-resident loop
            ov["ticks_per_dispatch"] = 4
        return ov

    frontends = [ServingFrontend(
        create_serving_engine(config, model, sampling=sampling,
                              seed=seed, mesh=meshes[r],
                              **_overrides(r)), **fkw)
        for r in range(n)]
    rkw = {}
    if config._router_policy is not None:
        rkw["policy"] = config._router_policy
    if roles is not None:
        rkw["roles"] = roles
    if config._migration is not None:
        rkw["migration"] = config._migration
    return ReplicaRouter(frontends, **rkw)


def create_serving_frontend(config: Config, model, sampling=None,
                            seed=0):
    """Build the asyncio multi-tenant ingress over a fresh serving
    engine: `await frontend.start()` (or `async with frontend:`) spawns
    the background step-loop task; `submit()`/`stream()` are the
    per-request API (bounded admission, per-tenant fairness, deadlines,
    cancellation — docs/SERVING.md). `max_pending` from
    `enable_continuous_batching` bounds the admission queue."""
    engine = create_serving_engine(config, model, sampling=sampling,
                                   seed=seed)
    from .serving.frontend import ServingFrontend
    kw = {}
    if config._max_pending is not None:
        kw["max_pending"] = int(config._max_pending)
    return ServingFrontend(engine, **kw)


def create_fleet_controller(config: Config, model, sampling=None,
                            seed=0, *, bundle=None, bundle_root=None,
                            version="v1", spill_dir=None,
                            export=True):
    """Build the fleet control plane (ISSUE 17) over a
    `create_serving_router` fleet: a `serving.fleet.FleetController`
    that can AOT-boot replicas from a versioned bundle with zero
    mixed-step compiles, roll weight upgrades through the router's
    quiesce plane, and actuate the SLO autoscaler's decisions.

    `bundle` names an existing bundle directory (or passes a loaded
    `FleetBundle`); otherwise, with `export=True`, a bundle for
    `version` is exported under `bundle_root` (required then: a bundle
    is a deployment artifact, the caller says where it goes) from
    replica 0's engine.
    Returns `(router, controller)` — boot the fleet with
    `async with router:`, then drive `controller.boot_replica()` /
    `rolling_upgrade()` / an attached `SLOAutoscaler`
    (docs/DEPLOYMENT.md)."""
    from .serving.fleet import (FleetBundle, FleetController,
                                export_bundle)
    router = create_serving_router(config, model, sampling=sampling,
                                   seed=seed)
    if bundle is None and export:
        if bundle_root is None:
            raise ValueError(
                "create_fleet_controller(export=True) needs bundle_root "
                "(or pass bundle=, or export=False)")
        bdir = export_bundle(router.frontends[0].engine,
                             bundle_root, version=str(version),
                             seed=seed)
        bundle = FleetBundle(bdir)
    kw = {}
    if config._max_pending is not None:
        kw["max_pending"] = int(config._max_pending)
    return router, FleetController(router, bundle,
                                   spill_dir=spill_dir, **kw)
