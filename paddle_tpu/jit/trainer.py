"""Compiled whole-step trainer: forward + backward + optimizer update in ONE
donated `jax.jit` executable.

This subsumes the reference's static-graph executor stack for training
(SURVEY.md §3.3: `StandaloneExecutor` → `InterpreterCore` instruction
stream): XLA's scheduler replaces stream_analyzer/workqueues, buffer
donation replaces the memory_optimize/inplace passes, and the fused
optimizer update replaces `coalesce_grad_tensor_pass` + merged_adam.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import autograd
from ..core import random as rng_mod
from ..core.tensor import Tensor
from .functional import bind_arrays, instrumented_jit, split_state
from ..optimizer.optimizer import _clip_spec


def shard_batch_dp(arrays, mesh, batch_axis=0):
    """Shard each array's `batch_axis` over the mesh's dp axis (arrays
    whose batch dim doesn't divide dp stay as-is). The single source of
    the dp-sharding rule for hapi's per-step and grouped paths."""
    if mesh is None:
        return arrays
    dp = mesh.shape.get("dp", 1)
    out = []
    for a in arrays:
        nd = getattr(a, "ndim", 0)
        if nd > batch_axis and a.shape[batch_axis] % dp == 0:
            spec = P(*([None] * batch_axis), "dp",
                     *([None] * (nd - batch_axis - 1)))
            out.append(jax.device_put(a, NamedSharding(mesh, spec)))
        else:
            out.append(a)
    return out


def _zero_mesh(optimizer):
    """Mesh for ZeRO sharding when group_sharded is enabled (sharding.py
    sets optimizer._zero_stage; stage>=1 shards master params + moments
    across the whole mesh during the update — reduce-scatter/all-gather
    generated by GSPMD)."""
    if getattr(optimizer, "_zero_stage", 0) < 1:
        return None
    try:
        from ..parallel.topology import get_hybrid_communicate_group
        mesh = get_hybrid_communicate_group().mesh()
        if mesh.size > 1:
            return mesh
    except Exception:
        pass
    return None


class CompiledTrainStep:
    """train_step(params, buffers, accums, lr, t, key, *batch) compiled once
    per input-shape signature."""

    def __init__(self, model, loss_fn, optimizer, n_labels=1,
                 donate=True, input_grads=False):
        # input_grads=True additionally returns d loss / d inputs — the
        # PS pull/train/push cycle needs the embedding-activation
        # gradient to push back into the host tables
        self.input_grads = input_grads
        # fleet's HybridParallelOptimizer delegates attribute READS only;
        # state mutations (_step_count, _accumulators) must land on the
        # real optimizer, so unwrap it here.
        optimizer = getattr(optimizer, "_inner_opt", optimizer)
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.n_labels = n_labels
        (self.p_names, self.p_tensors,
         self.b_names, self.b_tensors) = split_state(model)
        self._offload = bool(getattr(optimizer, "_zero_offload", False))
        self._zmesh = None if self._offload else _zero_mesh(optimizer)
        self._accum_shapes = {}
        if self._offload:
            # ZeRO offload (group_sharded offload=True parity,
            # group_sharded_stage3.py:60 / offload_helper.py): optimizer
            # state (fp32 master params + moments) lives in HOST memory
            # between steps; the device runs fwd/bwd only, the update
            # runs on the CPU backend, and only the refreshed working
            # params transfer back. Device memory holds params + grads;
            # the 3x-params fp32 optimizer footprint moves to host.
            try:
                cpu = jax.devices("cpu")[0]
            except RuntimeError as e:
                raise RuntimeError(
                    "ZeRO offload keeps the optimizer state on jax's "
                    "CPU backend, and this process has none beside the "
                    "accelerator: start it with the cpu platform "
                    "enabled too (JAX_PLATFORMS=tpu,cpu)") from e
            self._cpu_dev = cpu
            self._host_master = [
                jax.device_put(jnp.asarray(p._data, jnp.float32), cpu)
                for p in self.p_tensors]
            # identity shadow of the device arrays we last wrote: an
            # externally replaced param (checkpoint load, set_value)
            # refreshes its master copy instead of being overwritten
            self._written = [p._data for p in self.p_tensors]
            for p in self.p_tensors:
                acc = optimizer._get_accums(p)
                for k, a in list(acc.items()):
                    acc[k] = jax.device_put(a, cpu)
        if self._zmesh is not None:
            # store accumulators flat+padded so they live sharded between
            # steps (ZeRO memory saving); remember the logical shapes so
            # restore_accums()/state_dict can unflatten
            from ..parallel.zero import flat_pad_sharded
            for p in self.p_tensors:
                acc = optimizer._get_accums(p)
                shapes = {}
                for k, a in list(acc.items()):
                    shapes[k] = (a.shape, a.dtype)
                    acc[k] = flat_pad_sharded(a, self._zmesh)
                self._accum_shapes[id(p)] = shapes
            optimizer._zero_accum_shapes = self._accum_shapes
        # ensure accumulators exist
        self.accum_template = [optimizer._get_accums(p)
                               for p in self.p_tensors]

        clip_kind, clip_value = _clip_spec(optimizer._grad_clip)
        single = optimizer._single_update
        # ASP maintain-sparsity (incubate.asp.decorate): the fused
        # update multiplies each param's 2:4 mask back in after the
        # optimizer rule, inside the executable
        asp_masks = getattr(optimizer, "_asp_masks", None) or {}
        param_masks = [
            jnp.asarray(asp_masks[id(p)]) if id(p) in asp_masks else None
            for p in self.p_tensors]
        from ..optimizer.optimizer import _wd_coeff
        wds = tuple(
            p.optimize_attr.get("weight_decay", optimizer._weight_decay)
            if p.regularizer is None else _wd_coeff(p.regularizer)
            for p in self.p_tensors)
        lr_mults = tuple(p.optimize_attr.get("learning_rate", 1.0)
                         for p in self.p_tensors)
        trainable = tuple(not p.stop_gradient for p in self.p_tensors)
        model_ref = model
        loss_ref = loss_fn
        p_tensors = self.p_tensors
        b_tensors = self.b_tensors
        n_lab = n_labels
        # dy2static: models with data-dependent python control flow get
        # the lax.cond/while_loop rewrite so Model.fit compiles them too
        # (not just @to_static); unchanged models pass through identity
        from . import _to_static_enabled, dy2static
        fwd_fn = None
        # the rewrite calls forward directly, so the TOP-level model's
        # forward pre/post hooks would be bypassed — skip it when hooks
        # are registered (sublayer hooks still run: sublayer calls go
        # through their own __call__)
        has_hooks = bool(getattr(model, "_forward_pre_hooks", None)) or \
            bool(getattr(model, "_forward_post_hooks", None))
        if _to_static_enabled[0] and not has_hooks:
            try:
                tf = dy2static.transform_function(model.forward)
                if getattr(tf, "__func__", tf) is not \
                        getattr(model.forward, "__func__", model.forward):
                    fwd_fn = tf
            except Exception:
                fwd_fn = None

        want_in_grads = input_grads

        def make_loss_of(buffers, labels, key):
            """The (params, inputs) -> (loss, aux) closure shared by the
            on-device step and the offload fwd/bwd."""
            def loss_of(plist, in_list):
                wrapped_in = [Tensor(a) for a in in_list]
                wrapped_lab = [Tensor(a) for a in labels]
                with bind_arrays(p_tensors, plist), \
                        bind_arrays(b_tensors, buffers), \
                        rng_mod.functional_rng(key), autograd.no_grad():
                    out = fwd_fn(*wrapped_in) if fwd_fn is not None \
                        else model_ref(*wrapped_in)
                    outs = out if isinstance(out, (list, tuple)) else [out]
                    if loss_ref is not None:
                        loss = loss_ref(*outs, *wrapped_lab)
                    else:
                        loss = outs[0]
                    new_buf = [b._data for b in b_tensors]
                loss_arr = loss._data if isinstance(loss, Tensor) else loss
                out_arrs = [o._data if isinstance(o, Tensor) else o
                            for o in outs]
                return loss_arr.astype(jnp.float32), (new_buf, out_arrs)
            return loss_of

        def apply_clip(grads):
            """grad clip (global-norm inside the compiled step) — one
            definition for both the on-device and offload paths."""
            if clip_kind == "global_norm":
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g, tr in zip(grads, trainable) if tr) + 1e-12)
                scale = jnp.minimum(1.0, clip_value / (gnorm + 1e-6))
                return [g * scale.astype(g.dtype) for g in grads]
            if clip_kind == "value":
                return [jnp.clip(g, -clip_value, clip_value)
                        for g in grads]
            return grads

        def step(params, buffers, accums, lr, t, key, *batch):
            inputs, labels = batch[:len(batch) - n_lab], \
                batch[len(batch) - n_lab:]
            loss_of = make_loss_of(buffers, labels, key)

            if want_in_grads:
                (loss, (new_buffers, outs)), (grads, in_grads) = \
                    jax.value_and_grad(loss_of, (0, 1), has_aux=True)(
                        list(params), list(inputs))
            else:
                (loss, (new_buffers, outs)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(list(params), list(inputs))
                in_grads = []
            grads = apply_clip(grads)
            # fusion fence: without it XLA cross-fuses backward work
            # (dropout RNG, activation reads) into the per-param
            # optimizer-update fusions, running them 4-5x over roofline
            # (measured on BERT-base AMP: 16ms -> ~5ms update phase)
            grads = jax.lax.optimization_barrier(grads)

            zmesh = self._zmesh
            new_params, new_accums = [], []
            for p, g, acc, wd, lm, tr, msk in zip(params, grads, accums,
                                                  wds, lr_mults,
                                                  trainable, param_masks):
                if not tr:
                    new_params.append(p)
                    new_accums.append(acc)
                    continue
                if zmesh is not None:
                    from ..parallel.zero import flat_pad_sharded
                    pf = flat_pad_sharded(p, zmesh)
                    gf = flat_pad_sharded(g, zmesh)
                    np_f, nacc = single(pf, gf, acc, lr * lm, t, wd)
                    np_ = np_f[:p.size].reshape(p.shape)
                else:
                    np_, nacc = single(p, g, acc, lr * lm, t, wd)
                if msk is not None:
                    np_ = np_ * msk.astype(np_.dtype)
                new_params.append(np_)
                new_accums.append(nacc)
            return (loss, outs, new_params, new_buffers, new_accums,
                    in_grads)

        donate_argnums = (0, 2) if donate else ()
        self._jit_step = instrumented_jit(
            step, f"train_step/{type(model).__name__}",
            donate_argnums=donate_argnums)
        self._step_fn = step
        self._donate = donate
        self._jit_multi = {}

        if self._offload:
            def fwd_bwd(params, buffers, key, *batch):
                inputs, labels = batch[:len(batch) - n_lab], \
                    batch[len(batch) - n_lab:]
                loss_of = make_loss_of(buffers, labels, key)
                (loss, (new_buffers, outs)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(list(params), list(inputs))
                return loss, outs, new_buffers, apply_clip(grads)

            self._jit_fwd_bwd = instrumented_jit(
                fwd_bwd, f"fwd_bwd_offload/{type(model).__name__}")
            self._update_cfg = (single, wds, lr_mults, trainable)

    def _run_offload(self, batch_arrays):
        """Device fwd/bwd; host-resident (CPU backend) optimizer state +
        update; refreshed working params stream back to the device."""
        import numpy as np
        opt = self.optimizer
        for i, p in enumerate(self.p_tensors):
            if self._written[i] is not p._data:
                # param replaced outside the trainer -> resync master
                self._host_master[i] = jax.device_put(
                    jnp.asarray(p._data, jnp.float32), self._cpu_dev)
                self._written[i] = p._data
        params = [p._data for p in self.p_tensors]
        buffers = [b._data for b in self.b_tensors]
        lr = opt.get_lr()
        t = float(opt._step_count + 1)
        key = rng_mod.next_key()
        loss, outs, new_buffers, grads = self._jit_fwd_bwd(
            params, buffers, key, *batch_arrays)
        single, wds, lr_mults, trainable = self._update_cfg
        cpu = self._cpu_dev
        with jax.default_device(cpu):
            for i, p in enumerate(self.p_tensors):
                if not trainable[i]:
                    continue
                g = jax.device_put(grads[i], cpu).astype(jnp.float32)
                acc = opt._accumulators[id(p)]
                new_p, new_acc = single(
                    self._host_master[i], g, acc,
                    jnp.float32(lr * lr_mults[i]), jnp.float32(t),
                    wds[i])
                self._host_master[i] = new_p
                opt._accumulators[id(p)] = new_acc
                # re-enter the device UNCOMMITTED (like a freshly built
                # param): a committed single-device array cannot be
                # mixed with mesh-sharded batches in the next jit call
                host_np = np.asarray(new_p.astype(p._data.dtype))
                p._data = jnp.asarray(host_np)
                self._written[i] = p._data
        for b, nb in zip(self.b_tensors, new_buffers):
            b._data = nb
        opt._step_count += 1
        return Tensor(loss), [Tensor(o) for o in outs]

    def _get_multi(self, k, with_in_grads=False, donate=True):
        """K chained steps in ONE executable (lax.scan over stacked
        batches). Grouping amortises per-dispatch host latency (not
        measured on the direct backend) K-fold for small models whose
        step is shorter than a dispatch (LeNet fit).
        with_in_grads additionally stacks d loss/d inputs per step (the
        PS pull/train/push cycle's embedding gradients).

        (An r5 deferred-donation variant — first call per group size
        undonated so a failed execution leaves buffers alive for the
        per-step replay — was measured and reverted: it moves one full
        compile per group size into the steady-state epoch, collapsing
        the LeNet fit bench 189 -> 6 steps/s. Donation stays on; hapi
        detects consumed buffers after a failure and raises cleanly
        instead of replaying garbage.)"""
        cache_key = (k, with_in_grads, donate)
        if cache_key in self._jit_multi:
            return self._jit_multi[cache_key]
        step_fn = self._step_fn

        def multi(params, buffers, accums, lr, t0, key0, *stacked):
            # per-step keys derived inside the executable: a host-side
            # next_key() per step would be k extra dispatches
            keys = jax.random.split(key0, k)

            def body(carry, xs):
                params, buffers, accums, t = carry
                key, batch = xs[0], xs[1:]
                loss, _outs, params, buffers, accums, in_g = step_fn(
                    params, buffers, accums, lr, t, key, *batch)
                ys = (loss, tuple(in_g)) if with_in_grads else loss
                return (params, buffers, accums, t + 1.0), ys

            (params, buffers, accums, _), ys = jax.lax.scan(
                body, (params, buffers, accums, t0), (keys, *stacked))
            if with_in_grads:
                losses, in_grads = ys
            else:
                losses, in_grads = ys, None
            return losses, params, buffers, accums, in_grads

        jit_multi = instrumented_jit(
            multi, f"train_many/{type(self.model).__name__}",
            donate_argnums=(0, 2) if (self._donate and donate) else ())
        self._jit_multi[cache_key] = jit_multi
        return jit_multi

    def run_many(self, batches, mesh=None, with_in_grads=False):
        """Run len(batches) train steps in one dispatch; returns the
        per-step losses as ONE device array [K] (no host sync), or
        (losses, in_grads) with with_in_grads=True (each in_grad
        stacked [K, ...] — the PS cycle's embedding gradients). Model
        outputs are not returned — callers needing per-step outputs
        (metrics) must use run(). With `mesh`, each stacked batch is
        dp-sharded on its per-step batch dim (axis 1)."""
        import numpy as np
        if with_in_grads and not self.input_grads:
            raise ValueError(
                "run_many(with_in_grads=True) requires a trainer built "
                "with input_grads=True")
        k = len(batches)
        if k == 1 and not with_in_grads:
            loss, _ = self.run(*shard_batch_dp(batches[0], mesh))
            return Tensor(loss._data.reshape(1))
        opt = self.optimizer
        params = [p._data for p in self.p_tensors]
        buffers = [b._data for b in self.b_tensors]
        accums = [opt._accumulators[id(p)] for p in self.p_tensors]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        t0 = jnp.asarray(opt._step_count + 1, jnp.float32)
        key0 = rng_mod.next_key()
        stacked = []
        for i in range(len(batches[0])):
            items = [b[i] for b in batches]
            if isinstance(items[0], jax.Array):
                # device-resident batches (DeviceCacheLoader): stack ON
                # the device — np.stack would round-trip through host
                stacked.append(jnp.stack(items))
            else:
                stacked.append(np.stack([np.asarray(a) for a in items]))
        if mesh is not None:
            stacked = shard_batch_dp(stacked, mesh, batch_axis=1)
        else:
            stacked = [jnp.asarray(a) for a in stacked]
        want_ig = with_in_grads and self.input_grads
        (losses, new_params, new_buffers, new_accums,
         in_grads) = self._get_multi(k, want_ig)(
            params, buffers, accums, lr, t0, key0, *stacked)
        for p, np_ in zip(self.p_tensors, new_params):
            p._data = np_
        for b, nb in zip(self.b_tensors, new_buffers):
            b._data = nb
        for p, nacc in zip(self.p_tensors, new_accums):
            opt._accumulators[id(p)] = nacc
        opt._step_count += k
        if want_ig:
            return Tensor(losses), [Tensor(g) for g in in_grads]
        return Tensor(losses)

    def restore_accums(self):
        """Undo the ZeRO flat layout / host offload placement (used
        before falling back to the eager optimizer path)."""
        if self._offload:
            for p in self.p_tensors:
                acc = self.optimizer._accumulators.get(id(p))
                if acc:
                    for k, a in list(acc.items()):
                        acc[k] = jax.device_put(
                            a, next(iter(p._data.devices())))
            self._offload = False
            return
        if self._zmesh is None:
            return
        from ..parallel.zero import unflatten
        for p in self.p_tensors:
            shapes = self._accum_shapes.get(id(p))
            if not shapes:
                continue
            acc = self.optimizer._accumulators.get(id(p))
            if acc is None:
                continue
            for k, (shape, dtype) in shapes.items():
                if acc[k].ndim == 1 and tuple(acc[k].shape) != tuple(shape):
                    acc[k] = unflatten(acc[k], shape, dtype)
        self._zmesh = None
        self.optimizer._zero_accum_shapes = {}

    def run(self, *batch_arrays):
        if self._offload:
            return self._run_offload(batch_arrays)
        opt = self.optimizer
        params = [p._data for p in self.p_tensors]
        buffers = [b._data for b in self.b_tensors]
        accums = [opt._accumulators[id(p)] for p in self.p_tensors]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        t = jnp.asarray(opt._step_count + 1, jnp.float32)
        key = rng_mod.next_key()
        (loss, outs, new_params, new_buffers, new_accums,
         in_grads) = self._jit_step(
            params, buffers, accums, lr, t, key, *batch_arrays)
        for p, np_ in zip(self.p_tensors, new_params):
            p._data = np_
        for b, nb in zip(self.b_tensors, new_buffers):
            b._data = nb
        for p, nacc in zip(self.p_tensors, new_accums):
            opt._accumulators[id(p)] = nacc
        opt._step_count += 1
        if self.input_grads:
            return (Tensor(loss), [Tensor(o) for o in outs],
                    [Tensor(g) for g in in_grads])
        return Tensor(loss), [Tensor(o) for o in outs]


class CompiledEvalStep:
    def __init__(self, model, loss_fn=None, n_labels=1):
        self.model = model
        (self.p_names, self.p_tensors,
         self.b_names, self.b_tensors) = split_state(model)
        model_ref = model
        loss_ref = loss_fn
        p_tensors, b_tensors = self.p_tensors, self.b_tensors
        n_lab = n_labels

        def step(params, buffers, key, *batch):
            inputs = batch[:len(batch) - n_lab] if loss_ref is not None \
                else batch
            labels = batch[len(batch) - n_lab:] if loss_ref is not None \
                else ()
            wrapped_in = [Tensor(a) for a in inputs]
            wrapped_lab = [Tensor(a) for a in labels]
            with bind_arrays(p_tensors, params), \
                    bind_arrays(b_tensors, buffers), \
                    rng_mod.functional_rng(key), autograd.no_grad():
                out = model_ref(*wrapped_in)
                outs = out if isinstance(out, (list, tuple)) else [out]
                loss_arr = None
                if loss_ref is not None:
                    loss = loss_ref(*outs, *wrapped_lab)
                    loss_arr = loss._data if isinstance(loss, Tensor) \
                        else loss
            out_arrs = [o._data if isinstance(o, Tensor) else o
                        for o in outs]
            return loss_arr, out_arrs

        self._jit_step = instrumented_jit(
            step, f"eval_step/{type(model).__name__}")

    def run(self, *batch_arrays):
        params = [p._data for p in self.p_tensors]
        buffers = [b._data for b in self.b_tensors]
        key = rng_mod.next_key()
        loss, outs = self._jit_step(params, buffers, key, *batch_arrays)
        return (Tensor(loss) if loss is not None else None,
                [Tensor(o) for o in outs])
