"""paddle.Model — the Keras-like high-level API.

Parity: `python/paddle/hapi/model.py:1016` (`Model`), `fit:1708`,
`prepare:1631`, `DynamicGraphAdapter.train_batch:783`,
`prepare_distributed_context:202`.

TPU-native execution: `train_batch` runs a whole-step compiled executable
(forward+backward+fused update in one donated jax.jit — jit/trainer.py)
instead of per-op eager dispatch; this is where the reference needed the
static Program path for speed. Falls back to pure eager when tracing fails
(data-dependent python control flow in the model).
"""
from __future__ import annotations

import os
import pickle
import warnings

import jax
import numpy as np

from ..core.tensor import Tensor
from ..core import autograd
from .. import ops
from ..io import DataLoader
from ..jit.trainer import CompiledTrainStep, CompiledEvalStep
from .callbacks import config_callbacks


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape=None, dtype="float32", name=None):
        self.shape = shape
        self.dtype = dtype
        self.name = name


def _to_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _arrays(batch):
    import jax
    out = []
    for b in _to_list(batch):
        if isinstance(b, Tensor):
            out.append(b._data)
        elif isinstance(b, jax.Array):
            out.append(b)   # device-resident (DeviceCacheLoader): keep
        else:
            out.append(np.asarray(b))
    return out


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = _to_list(inputs)
        self._labels = _to_list(labels)
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._train_step = None
        self._eval_step = None
        self._jit_ok = True
        self._group_ok = [True]  # grouped-dispatch health (fit)
        self.stop_training = False

    # ------------------------------------------------------------ prepare
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        self._train_step = None
        self._eval_step = None
        self._dist_mesh = None
        # amp_configs parity: {'level': 'O1'|'O2', 'dtype': ...} or 'O2'
        if amp_configs:
            from .. import amp as amp_mod
            if isinstance(amp_configs, str):
                amp_configs = {"level": amp_configs}
            level = amp_configs.get("level", "O1")
            dtype = amp_configs.get("dtype", "bfloat16")
            if level == "O2":
                amp_mod.decorate(self.network, level="O2", dtype=dtype)
            self._amp_level = level
            self._amp_dtype = dtype
        from ..parallel import env as dist_env
        if dist_env.get_world_size() > 1:
            dist_env.init_parallel_env()
            from ..parallel.topology import get_hybrid_communicate_group
            from ..parallel.mp_layers import place_model_on_mesh
            mesh = get_hybrid_communicate_group().mesh()
            if mesh.size > 1:
                self._dist_mesh = mesh
                place_model_on_mesh(self.network, mesh)
        return self

    # ------------------------------------------------------------- batch
    def _n_labels(self):
        return max(len(self._labels), 1)

    def _amp_context(self):
        """O1 auto_cast context from prepare(amp_configs=...) — must wrap
        the forward (incl. the compiled step's tracing call)."""
        if getattr(self, "_amp_level", None) == "O1":
            from .. import amp as amp_mod
            return amp_mod.auto_cast(level="O1",
                                     dtype=getattr(self, "_amp_dtype",
                                                   "bfloat16"))
        import contextlib
        return contextlib.nullcontext()

    def _maybe_shard(self, arrays):
        """Shard batch dim 0 over the dp mesh axis (DataParallel: the
        EagerReducer capability folds into the compiled step's GSPMD grad
        reduction)."""
        from ..jit.trainer import shard_batch_dp
        return shard_batch_dp(arrays, getattr(self, "_dist_mesh", None))

    def _train_batch_inner(self, inputs, labels, update=True):
        """Returns ([loss_tensor], metrics) WITHOUT host synchronisation
        (the fit loop materialises losses lazily at log points, because a
        fetch per step stalls async dispatch; per-dispatch host latency,
        not measured on the direct backend)."""
        self.network.train()
        inputs = _to_list(inputs)
        labels = _to_list(labels)
        batch = self._maybe_shard(_arrays(inputs) + _arrays(labels))
        amp_ctx = self._amp_context()
        if self._jit_ok:
            try:
                if self._train_step is None:
                    self._train_step = CompiledTrainStep(
                        self.network, self._loss, self._optimizer,
                        n_labels=len(labels) or 1)
                with amp_ctx:  # active during first-call tracing (O1)
                    loss, outs = self._train_step.run(*batch)
                metrics = self._update_metrics(outs, labels)
                return [loss], metrics
            except jax.errors.JAXTypeError as e:
                # the one failure eager execution can cure: the forward
                # reads a traced value on the host (data-dependent
                # python control flow). Anything else — a compile
                # error, a device fault, a bug — raises.
                warnings.warn(
                    f"compiled train step failed ({type(e).__name__}: {e}); "
                    "falling back to eager execution")
                if self._train_step is not None:
                    # undo the ZeRO flat accumulator layout so the eager
                    # optimizer path sees logical shapes again
                    self._train_step.restore_accums()
                self._jit_ok = False
        # eager path (DynamicGraphAdapter.train_batch parity)
        with self._amp_context():
            outs = self.network(*[t if isinstance(t, Tensor) else Tensor(t)
                                  for t in inputs])
            outs_l = _to_list(outs)
            lbl = [t if isinstance(t, Tensor) else Tensor(t)
                   for t in labels]
            loss = self._loss(*outs_l, *lbl) if self._loss else outs_l[0]
        loss = loss.astype("float32") if loss.dtype != np.float32 else loss
        loss.backward()
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
        metrics = self._update_metrics(outs_l, labels)
        return [loss], metrics

    def train_batch(self, inputs, labels=None, update=True):
        losses, metrics = self._train_batch_inner(inputs, labels, update)
        np_losses = [l.numpy() for l in losses]
        return np_losses if not metrics else (np_losses, metrics)

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = _to_list(inputs)
        labels = _to_list(labels)
        batch = self._maybe_shard(_arrays(inputs) + _arrays(labels))
        if self._eval_step is None:
            self._eval_step = CompiledEvalStep(
                self.network, self._loss, n_labels=len(labels) or 1)
        loss, outs = self._eval_step.run(*batch)
        metrics = self._update_metrics(outs, labels)
        res = [loss.numpy()] if loss is not None else []
        return (res, metrics) if metrics else res

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = _to_list(inputs)
        with autograd.no_grad():
            outs = self.network(*[t if isinstance(t, Tensor) else Tensor(t)
                                  for t in inputs])
        return [o.numpy() for o in _to_list(outs)]

    def _update_metrics(self, outs, labels):
        metric_vals = []
        lbl = [t if isinstance(t, Tensor) else Tensor(t) for t in labels]
        for m in self._metrics:
            state = m.compute(*_to_list(outs), *lbl)
            r = m.update(*_to_list(state))
            metric_vals.append(r)
        return metric_vals

    # --------------------------------------------------------------- fit
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None):
        from ..io import DeviceCacheLoader
        if isinstance(train_data, (DataLoader, DeviceCacheLoader)):
            loader = train_data
        else:
            loader = DataLoader(train_data, batch_size=batch_size,
                                shuffle=shuffle, drop_last=drop_last,
                                num_workers=num_workers)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs,
                                steps=steps, log_freq=log_freq,
                                verbose=verbose, save_freq=save_freq,
                                save_dir=save_dir,
                                metrics=self._metrics_name(),
                                do_eval=eval_data is not None)
        cbks.on_train_begin()
        # throughput timer (python/paddle/profiler/timer.py parity):
        # paddle.profiler.benchmark().step_info() reports reader/batch
        # cost + ips for this fit loop
        from ..profiler.timer import benchmark as _benchmark
        _bm = _benchmark()
        _bm.begin()
        self.stop_training = False
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            logs = {}
            res = None
            # Step grouping: with no metrics and a static learning rate,
            # K consecutive steps run as ONE device dispatch (lax.scan
            # in CompiledTrainStep.run_many) to amortise per-dispatch
            # host latency (not measured on the direct backend), which
            # bounds small models whatever their compute. Groups never
            # span a log point, so logged losses are exact for their
            # step. Per-step LR schedulers disable grouping (each step
            # must see its own lr); callback begin/end pairs fire in
            # order at flush time (after the async dispatch — same
            # visibility as the per-step path, whose device work has not
            # finished at on_train_batch_end either).
            pending = []       # [(step, batch_arrays)]
            last_loss = [None]
            group_ok = self._group_ok   # persists across epochs

            def flush():
                if not pending:
                    return
                steps_, arrs_ = zip(*pending)
                pending.clear()
                try:
                    with self._amp_context():  # O1 must wrap tracing
                        losses = self._train_step.run_many(
                            list(arrs_),
                            mesh=getattr(self, "_dist_mesh", None))
                except Exception as e:
                    # ADVICE r4 #4: the grouped executable donates
                    # params/accums — if it failed at EXECUTION time the
                    # buffers may already be consumed, and a per-step
                    # replay would read deleted arrays. Detect and raise
                    # cleanly instead of crashing mid-replay.
                    if any(getattr(p._data, "is_deleted",
                                   lambda: False)()
                           for p in self._train_step.p_tensors):
                        raise RuntimeError(
                            "grouped train step failed after buffer "
                            "donation; parameter state was consumed and "
                            "cannot be replayed. Re-initialise the "
                            "model/optimizer (or set "
                            "model._fit_group_max = 1 to train "
                            "per-step)") from e
                    warnings.warn(
                        f"grouped train steps failed ({type(e).__name__}:"
                        f" {e}); replaying per-step and disabling "
                        "grouping")
                    group_ok[0] = False
                    for s, arrs in zip(steps_, arrs_):
                        cbks.on_train_batch_begin(s)
                        n_in = len(arrs) - self._n_labels()
                        res = self._train_batch_inner(
                            list(arrs[:n_in]), list(arrs[n_in:]))
                        last_loss[0] = ("plain", res[0][0])
                        if s % max(log_freq, 1) == 0:
                            cbks.on_train_batch_end(s,
                                                    self._make_logs(res))
                        else:
                            cbks.on_train_batch_end(s, {})
                    return
                # keep the stacked losses; index lazily (an eager slice
                # is a device dispatch — only pay it at log points)
                last_loss[0] = ("stacked", losses)
                for i, s in enumerate(steps_):
                    cbks.on_train_batch_begin(s)
                    if s % max(log_freq, 1) == 0:
                        lg = self._make_logs(([losses[i]], []))
                        cbks.on_train_batch_end(s, lg)
                    else:
                        cbks.on_train_batch_end(s, {})

            # group size cap: larger groups amortise per-dispatch host
            # latency further but compile one executable per distinct
            # size — raise via model._fit_group_max for small models
            group_max = getattr(self, "_fit_group_max", 8)
            shapes = None
            static_lr = not hasattr(
                getattr(self._optimizer, "_learning_rate", 0.0), "step")
            for step, batch in enumerate(loader):
                _bm.after_reader()
                ins, lbs = self._split_batch(batch)
                _bs = next((int(x.shape[0]) for x in _to_list(ins)
                            if hasattr(x, "shape") and len(x.shape)), 1)
                can_group = (group_ok[0] and self._jit_ok
                             and not self._metrics and static_lr
                             and self._train_step is not None
                             and not self._train_step.input_grads
                             and not self._train_step._offload)
                if can_group:
                    arrs = _arrays(ins) + _arrays(lbs)
                    bshapes = tuple(getattr(a, "shape", ()) for a in arrs)
                    if pending and bshapes != shapes:
                        flush()
                    shapes = bshapes
                    pending.append((step, arrs))
                    is_last = (num_iters is not None
                               and step + 1 >= num_iters)
                    next_is_log = (step + 1) % max(log_freq, 1) == 0
                    if len(pending) >= group_max or next_is_log or \
                            is_last:
                        _n = len(pending)
                        flush()
                        _bm.after_step(num_samples=_n * _bs,
                                       num_steps=_n)
                    if is_last:
                        break
                    continue
                flush()
                cbks.on_train_batch_begin(step)
                res = self._train_batch_inner(ins, lbs)
                _bm.after_step(num_samples=_bs)
                last_loss[0] = ("plain", res[0][0])
                # lazy logging: only materialise the loss (device->host
                # sync) at log points so steps pipeline on the device;
                # non-log steps hand callbacks an EMPTY dict rather than
                # stale values (per-step consumers set log_freq=1)
                if step % max(log_freq, 1) == 0:
                    logs = self._make_logs(res)
                    cbks.on_train_batch_end(step, logs)
                else:
                    cbks.on_train_batch_end(step, {})
                if num_iters is not None and step + 1 >= num_iters:
                    break
            flush()
            if last_loss[0] is not None:
                kind, val = last_loss[0]
                logs = self._make_logs(
                    ([val[-1] if kind == "stacked" else val], []))
            cbks.on_epoch_end(epoch, logs)
            if eval_data is not None and (epoch + 1) % eval_freq == 0:
                self.evaluate(eval_data, batch_size=batch_size,
                              verbose=verbose, callbacks=cbks,
                              _inner=True)
            if self.stop_training:
                break
        cbks.on_train_end()

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None,
                 _inner=False):
        from ..io import DeviceCacheLoader
        if isinstance(eval_data, (DataLoader, DeviceCacheLoader)):
            loader = eval_data
        else:
            loader = DataLoader(eval_data, batch_size=batch_size,
                                num_workers=num_workers)
        cbks = callbacks if _inner else config_callbacks(
            callbacks, model=self, verbose=verbose,
            metrics=self._metrics_name())
        for m in self._metrics:
            m.reset()
        cbks.on_eval_begin()
        logs = {}
        for step, batch in enumerate(loader):
            cbks.on_eval_batch_begin(step)
            ins, lbs = self._split_batch(batch)
            res = self.eval_batch(ins, lbs)
            logs = self._make_logs(res, prefix="eval_")
            cbks.on_eval_batch_end(step, logs)
        cbks.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        from ..io import DeviceCacheLoader
        if isinstance(test_data, (DataLoader, DeviceCacheLoader)):
            loader = test_data
        else:
            loader = DataLoader(test_data, batch_size=batch_size,
                                num_workers=num_workers)
        outputs = []
        for batch in loader:
            ins, _ = self._split_batch(batch, predict=True)
            outputs.append(self.predict_batch(ins))
        if stack_outputs and outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    def _split_batch(self, batch, predict=False):
        batch = _to_list(batch)
        if predict or self._loss is None:
            if self._inputs:
                return batch[:len(self._inputs)], []
            # no spec: feed as many tensors as network.forward accepts
            import inspect
            try:
                sig = inspect.signature(self.network.forward)
                n_in = len([p for p in sig.parameters.values()
                            if p.kind in (p.POSITIONAL_ONLY,
                                          p.POSITIONAL_OR_KEYWORD)
                            and p.default is p.empty])
                if 0 < n_in < len(batch):
                    return batch[:n_in], []
            except (TypeError, ValueError):
                pass
            return batch, []
        n_lab = self._n_labels()
        return batch[:-n_lab], batch[-n_lab:]

    def _metrics_name(self):
        names = ["loss"]
        for m in self._metrics:
            n = m.name()
            names.extend(n if isinstance(n, list) else [n])
        return names

    def _make_logs(self, res, prefix=""):
        logs = {}
        if isinstance(res, tuple):
            losses, metrics = res
        else:
            losses, metrics = res, []
        if losses:
            logs[prefix + "loss"] = float(np.asarray(losses[0]).reshape(-1)[0])
        idx = 0
        for m in self._metrics:
            names = m.name()
            names = names if isinstance(names, list) else [names]
            acc = m.accumulate()
            accs = acc if isinstance(acc, list) else [acc]
            for n, a in zip(names, accs):
                logs[prefix + n] = a
            idx += 1
        return logs

    # ------------------------------------------------------------- state
    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def save(self, path, training=True):
        dirname = os.path.dirname(path)
        if dirname:
            os.makedirs(dirname, exist_ok=True)
        from ..framework_io import save as psave
        psave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            psave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework_io import load as pload
        state = pload(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(pload(opt_path))

    def summary(self, input_size=None, dtype=None):
        n_params = sum(p.size for p in self.network.parameters())
        info = {"total_params": n_params,
                "trainable_params": sum(
                    p.size for p in self.network.parameters()
                    if not p.stop_gradient)}
        print(f"Total params: {n_params}")
        return info
