"""Versioned AOT boot bundles for serving replicas (ISSUE 17 (a)).

A bundle is one directory per checkpoint version:

    <root>/<version>/
        manifest.json       model config + engine knobs + kv_meta +
                            weight manifest + executable index, and
                            beside each executable the signature of
                            the step it was compiled for
        weights.npz         canonical model-order host arrays
                            (pre-compute-dtype-cast, `w00000`, ...)
        step__<role>__tp<n>.bin
                            pickled (payload, in_tree, out_tree) from
                            `jax.experimental.serialize_executable`
                            for the jitted mixed step, lowered against
                            `engine.example_step_args()`

There is no default root: a bundle is a deployment artifact, and the
caller says where it goes.

Boot path: `boot_engine_from_bundle` reconstructs the model from the
manifest, injects the bundled weights into the model tensors BEFORE
engine construction (so the engine's own compute-dtype cast / MoE
quantization / TP shard layout all apply unchanged — a booted engine
is bit-identical to the exporting one), then installs the
deserialized executable via `engine.install_aot_step`, loaded onto the
devices that engine lives on. The replica performs ZERO
`serving_mixed_step` jit compiles — watchdog-assertable with
`guards.sanitize(budgets={"serving_mixed_step": 0})` — and serves its
first token straight off the deserialized executable. A bundle
exported with `include_executable=False` (or for another `(role, tp)`)
carries config + weights only and boots through the ordinary jit path;
`FleetBundle.has_executable` tells the two apart. So does a bundle
whose executable was compiled for ANOTHER argument list than the
booting engine passes (`step_signature`; the manifest's engine knobs
pin the constructor, not the order of the step's arguments): its
executable is left alone and the replica compiles afresh.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pickle

import numpy as np

MANIFEST = "manifest.json"
WEIGHTS = "weights.npz"
FORMAT = 1


def _exec_key(role, tp):
    return f"{role}-tp{int(tp)}"


def _exec_file(role, tp):
    return f"step__{role}__tp{int(tp)}.bin"


def model_config(model):
    """Recoverable GPTForGeneration constructor kwargs (+ the flags a
    faithful rebuild needs). Exotic stacks can bypass this entirely
    with `boot_engine_from_bundle(model_factory=...)`."""
    dec = model.decoder
    cfg = {
        "vocab_size": int(model.vocab_size),
        "hidden_size": int(model.hidden_size),
        "num_layers": int(dec.num_layers),
        "num_attention_heads": int(dec.num_heads),
        "intermediate_size": int(dec.dim_feedforward),
        "max_position_embeddings": int(model.max_position_embeddings),
        "compute_dtype": str(getattr(model, "_compute_dtype",
                                     "float32")),
        "weight_only": "WeightOnly" in type(dec).__name__,
    }
    n_exp = int(getattr(dec, "_num_experts", 0))
    if n_exp:
        cfg["moe"] = {"num_expert": n_exp,
                      "top_k": int(getattr(dec, "_top_k", 2))}
    return cfg


def engine_config(engine):
    """The engine-constructor knobs a replica boot must replay; the
    bundle pins them so every booted replica shares the exporting
    engine's compiled-step signature."""
    kv = engine.kv
    cfg = {
        "max_slots": int(kv.max_slots),
        "block_size": int(engine.block_size),
        "num_blocks": int(kv.num_blocks),
        "max_seq_len": int(kv.max_blocks_per_slot * kv.block_size),
        "token_budget": int(engine.token_budget),
        "eos_token_id": engine.eos_token_id,
        "cache_dtype": str(kv.dtype),
        "kv_dtype": kv.kv_dtype,
        "draft_k": int(engine.draft_k),
        "draft_ngram": int(engine.draft_ngram),
        "prefix_caching": engine.prefix_cache is not None,
        "role": engine.role,
        "max_adapters": (int(engine.adapters.max_adapters)
                         if engine.adapters is not None else 0),
        "lora_rank": (int(engine.adapters.rank)
                      if engine.adapters is not None else 8),
        "lora_alpha": (float(engine.adapters.alpha)
                       if engine.adapters is not None else None),
        "moe_weight_dtype": engine.moe_weight_dtype,
        "sparse_blocks": engine.sparse_blocks,
        "sparse_recent": (int(engine._sparse_recent)
                          if engine._sparse else 2),
        "track_summaries": bool(engine._track_summaries),
        "sampling": dataclasses.asdict(engine.sampling),
        "tensor_parallel": int(getattr(engine, "tensor_parallel", 1)),
        "expert_parallel": int(getattr(engine, "expert_parallel", 1)),
    }
    return cfg


def step_signature(engine):
    """The compiled mixed step's argument list as the engine passes it
    NOW: the plan layout's fields with their offsets, and after the
    weights every argument's dtype and shape, in order. An executable
    runs only under the signature it was compiled for."""
    import jax
    leaves = jax.tree.leaves(list(engine.example_step_args()[1:]))
    return {
        "plan": [[name, at, list(shape)] for name, (at, shape)
                 in engine.plan_layout.fields.items()],
        "args": [f"{np.dtype(a.dtype).name}{list(np.shape(a))}"
                 for a in leaves]}


def _serialize_step(engine):
    """Lower + AOT-compile the engine's jitted mixed step against its
    own example arguments and serialize the executable. The AOT path
    neither populates the instrumented wrapper's jit cache nor ticks
    the compile watchdog, so exporting from inside a sanitized test
    costs no budget. The executable must come neither out of the
    persistent cache nor from the engine's own jit, which may have
    loaded it from there (`core.compile_cache.compile_fresh` says why)."""
    from jax.experimental import serialize_executable

    from ...core.compile_cache import compile_fresh
    payload, in_tree, out_tree = serialize_executable.serialize(
        compile_fresh(engine._step_fn, *engine.example_step_args()))
    return pickle.dumps({"payload": payload, "in_tree": in_tree,
                         "out_tree": out_tree},
                        protocol=pickle.HIGHEST_PROTOCOL)


def export_bundle(engine, path, *, version="v1", seed=0,
                  include_executable=True):
    """Write `engine`'s boot bundle for `version`; returns the bundle
    directory. Weights are the CANONICAL model tensors (pre-cast,
    pre-quantization, pre-TP-permute, `model._gen_tensors()` order):
    the boot replays the engine constructor's own transforms, which
    keeps one weights file valid for every (role, TP) executable in
    the bundle."""
    bdir = os.path.join(path, str(version))
    os.makedirs(bdir, exist_ok=True)
    tensors = list(engine.model._gen_tensors())
    arrays = [np.asarray(t._data) for t in tensors]
    np.savez(os.path.join(bdir, WEIGHTS),
             **{f"w{i:05d}": a for i, a in enumerate(arrays)})
    manifest = {
        "format": FORMAT,
        "version": str(version),
        "seed": int(seed),
        "model": model_config(engine.model),
        "engine": engine_config(engine),
        "kv_meta": engine.kv.kv_meta(),
        "weights": [{"index": i, "shape": list(a.shape),
                     "dtype": str(a.dtype)}
                    for i, a in enumerate(arrays)],
        "executables": {},
        "step_signatures": {},
    }
    mpath = os.path.join(bdir, MANIFEST)
    if include_executable:
        role = engine.role
        tp = int(getattr(engine, "tensor_parallel", 1))
        fname = _exec_file(role, tp)
        with open(os.path.join(bdir, fname), "wb") as f:
            f.write(_serialize_step(engine))
        manifest["executables"][_exec_key(role, tp)] = fname
        manifest["step_signatures"][_exec_key(role, tp)] = \
            step_signature(engine)
    if os.path.exists(mpath):
        # re-export for another (role, TP): merge executable indices,
        # keep the shared config/weights freshly written above
        with open(mpath) as f:
            old = json.load(f)
        for index in ("executables", "step_signatures"):
            merged = dict(old.get(index, {}))
            merged.update(manifest[index])
            manifest[index] = merged
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    return bdir


class FleetBundle:
    """A loaded boot bundle: manifest + lazy weights + executables."""

    def __init__(self, path):
        self.path = str(path)
        with open(os.path.join(self.path, MANIFEST)) as f:
            self.manifest = json.load(f)
        if self.manifest.get("format") != FORMAT:
            raise ValueError(
                f"bundle format {self.manifest.get('format')} != "
                f"supported {FORMAT} ({self.path})")
        self._weights = None

    @classmethod
    def load(cls, path):
        return cls(path)

    @property
    def version(self):
        return self.manifest["version"]

    def weights(self):
        """Canonical model-order host arrays (cached)."""
        if self._weights is None:
            z = np.load(os.path.join(self.path, WEIGHTS))
            self._weights = [z[f"w{i:05d}"]
                             for i in range(len(z.files))]
        return self._weights

    def has_executable(self, role="mixed", tp=1):
        return _exec_key(role, tp) in self.manifest["executables"]

    def step_signature(self, role="mixed", tp=1):
        """What `step_signature(engine)` read when the (role, tp)
        executable was exported; None for a bundle from before the
        manifest said."""
        return self.manifest.get("step_signatures", {}).get(
            _exec_key(role, tp))

    def executable(self, devices, role="mixed", tp=1):
        """Deserialize the (role, tp) step executable onto `devices` —
        the devices of the engine that will run it, in the engine's
        order — into a callable that runs WITHOUT compiling; None when
        the bundle carries no executable for that key."""
        from jax.experimental import serialize_executable
        fname = self.manifest["executables"].get(_exec_key(role, tp))
        if fname is None:
            return None
        with open(os.path.join(self.path, fname), "rb") as f:
            d = pickle.load(f)
        return serialize_executable.deserialize_and_load(
            d["payload"], d["in_tree"], d["out_tree"],
            execution_devices=list(devices))

    def build_model(self):
        """Reconstruct the model from the manifest and inject the
        bundled weights into its tensors BEFORE any engine sees it —
        the engine constructor then applies its own cast/quantize/
        shard transforms, identical to the exporting engine's."""
        import jax.numpy as jnp

        from ...models.gpt import GPTForGeneration
        model = GPTForGeneration(**self.manifest["model"])
        tensors = list(model._gen_tensors())
        weights = self.weights()
        if len(tensors) != len(weights):
            raise ValueError(
                f"bundle holds {len(weights)} tensors, rebuilt model "
                f"has {len(tensors)} — manifest/model drift")
        for t, w in zip(tensors, weights):
            if tuple(t._data.shape) != tuple(w.shape):
                raise ValueError(
                    f"bundle tensor {tuple(w.shape)} != model tensor "
                    f"{tuple(t._data.shape)}")
            t._data = jnp.asarray(w)
        return model


def boot_engine_from_bundle(bundle, *, aot=True, warm_prefix=None,
                            name=None, model_factory=None,
                            clock=None, **overrides):
    """Construct a ServingEngine (or TPServingEngine for bundles
    exported from one) from a bundle. With `aot=True` and a matching
    executable in the bundle, the deserialized compiled step is
    installed and the replica performs ZERO mixed-step jit compiles.
    `warm_prefix` names a `RadixPrefixCache.spill` file to re-adopt
    (warm boot). Returns the engine, with `weights_version` stamped
    from the bundle."""
    if isinstance(bundle, str):
        bundle = FleetBundle(bundle)
    model = (model_factory() if model_factory is not None
             else bundle.build_model())
    ecfg = dict(bundle.manifest["engine"])
    tp = int(ecfg.pop("tensor_parallel", 1))
    ep = int(ecfg.pop("expert_parallel", 1))
    sampling_cfg = ecfg.pop("sampling", None)
    if sampling_cfg is not None:
        from ..batcher import SamplingConfig
        ecfg["sampling"] = SamplingConfig(**sampling_cfg)
    ecfg["seed"] = int(bundle.manifest.get("seed", 0))
    if clock is not None:
        ecfg["clock"] = clock
    if name is not None:
        ecfg["name"] = name
    ecfg.update(overrides)
    role = ecfg.get("role", "mixed")
    if tp > 1 or ep > 1:
        from ..distributed.tp_engine import TPServingEngine
        engine = TPServingEngine(model, tensor_parallel=tp,
                                 expert_parallel=ep, **ecfg)
    else:
        from ..engine import ServingEngine
        engine = ServingEngine(model, **ecfg)
    engine.weights_version = bundle.version
    if aot and bundle.has_executable(role, tp) and \
            bundle.step_signature(role, tp) == step_signature(engine):
        # an executable compiled for another argument list is not
        # loaded: the replica compiles its own step, as with no
        # executable in the bundle
        engine.install_aot_step(
            bundle.executable(engine.step_devices(), role, tp))
    if warm_prefix is not None and engine.prefix_cache is not None \
            and os.path.exists(warm_prefix):
        engine.prefix_cache.restore(warm_prefix)
    return engine
