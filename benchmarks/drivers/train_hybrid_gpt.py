"""Driver for training cells: `parallel.hybrid_gpt.HybridGPT`, built as
`chip_smoke.py train_phase` and `bench.py` build it, stepped on
device-resident batches made from the seed.

The measured window dispatches steps back to back and blocks only on
the loss of the step BEFORE the newest, so the host never drains the
device; the clock stops after `block_until_ready` on the last step.
The traced run first times blocking steps (`trainer.step_ms_p50`), then
profiles a few steps dispatched as the measured window dispatches them.
"""
from __future__ import annotations

import contextlib
import math
import shutil
import time
import types

from harness import kernels, stats, trace_reduce
from harness.files import load_module
from harness.traffic import fixed_batches

DISPATCH, WAIT = "dispatch", "wait_loss"


class Driver:
    def __init__(self, env):
        self.env = env
        self.log = env.log

    def setup(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.parallel.hybrid_gpt import GPTConfig, HybridGPT

        env, cfg = self.env, self.env.config
        kw = dict(cfg["model"], **cfg.get("trainer", {}))
        if "compute_dtype" in kw:
            kw["compute_dtype"] = jnp.dtype(kw["compute_dtype"])
        if env.rehearse:
            kw["seq_len"] = int(env.traffic["seq_len"])
        self.gcfg = GPTConfig(**kw)
        if int(env.traffic["seq_len"]) != self.gcfg.seq_len:
            raise ValueError("the mix's seq_len is not the model's")
        t0 = time.monotonic()
        self.trainer = HybridGPT(self.gcfg, devices=env.devices)
        self.params, self.opt = self.trainer.init(
            jax.random.PRNGKey(env.seed % (2 ** 31 - 1)))
        specs = fixed_batches(env.traffic, env.seed)
        vocab = self.gcfg.vocab_size
        shape = (specs[0]["batch"], specs[0]["seq_len"])

        def make(seeds):
            def one(s):
                k1, k2 = jax.random.split(jax.random.PRNGKey(s))
                return (jax.random.randint(k1, shape, 0, vocab,
                                           jnp.int32),
                        jax.random.randint(k2, shape, 0, vocab,
                                           jnp.int32))
            return jax.vmap(one)(seeds)

        # every batch in one jitted call on the device, from the seed
        tok, lab = jax.jit(make)(jnp.asarray(
            [s["key_seed"] for s in specs], jnp.int32))
        self.batches = [self.trainer.shard_data(tok[i], lab[i])
                        for i in range(len(specs))]
        jax.block_until_ready((self.params, self.opt, self.batches))
        self.tokens_per_step = shape[0] * shape[1]
        self.log(f"trainer, parameters, Adam state and "
                 f"{len(self.batches)} batches of {shape} on the device "
                 f"in {time.monotonic() - t0:.1f} s")
        t1 = time.monotonic()
        lr = jnp.asarray(self.gcfg.learning_rate, jnp.float32)
        traced = self.trainer._step._jitted.trace(
            self.params, self.opt, *self.batches[0], lr,
            jnp.asarray(1.0, jnp.float32))
        self.kernels_missing, found = kernels.check_step(
            traced, cfg["kernels"], env.rehearse)
        self.log(f"train step kernels: {found} "
                 f"({time.monotonic() - t1:.1f} s to trace and lower)")
        self.step_num = 0

    def _step(self, batch):
        self.step_num += 1
        self.params, self.opt, loss = self.trainer.train_step(
            self.params, self.opt, *batch, step_num=self.step_num)
        return loss

    def warm(self):
        """Compile, and hold the trainer to the plain float32
        reference (`configs/<config>_reference.py`): the losses of the
        warm-up steps, which go through the backward pass and the
        optimiser, and a forward loss on sequences it did not train on.

        The warm-up batch is a few sequences of batch 0 repeated to the
        batch's size: its mean loss and its gradient are those of the
        few, so the reference takes the same steps on the few alone."""
        import jax
        import jax.numpy as jnp
        env, g = self.env, self.gcfg
        rc = env.config["reference"]
        ref = load_module("configs", env.config_name + "_reference")
        steps = int(env.traffic.get("warm_steps", 5))
        k = int(rc["step_sequences"])
        tok, lab = (a[:k] for a in self.batches[0])
        reps = self.batches[0][0].shape[0] // k
        batch = self.trainer.shard_data(jnp.tile(tok, (reps, 1)),
                                        jnp.tile(lab, (reps, 1)))
        # the reference first: the trainer's steps donate the parameters
        t0 = time.monotonic()
        hp = {n: float(getattr(g, n)) for n in (
            "learning_rate", "beta1", "beta2", "eps", "weight_decay",
            "grad_clip")}
        self.ref_steps = [float(x) for x in jax.device_get(
            ref.train_losses(self.params, tok, lab, g.n_heads, steps,
                             hp))]
        t1 = time.monotonic()
        self.warm_losses = [float(jax.device_get(self._step(batch)))
                            for _ in range(steps)]
        t2 = time.monotonic()
        self.step_gap = max(abs(a - b) for a, b in zip(
            self.warm_losses, self.ref_steps))
        self.log(f"warm-up: {steps} steps on {k} sequences x {reps}, "
                 f"losses {' '.join(f'{x:.4f}' for x in self.warm_losses)}"
                 f" in {t2 - t1:.1f} s, compile or cache load included; "
                 f"step compiles {self.trainer._step.compile_count()}")
        self.log(f"reference: the same steps in plain float32, losses "
                 f"{' '.join(f'{x:.4f}' for x in self.ref_steps)} "
                 f"({t1 - t0:.1f} s); widest difference "
                 f"{self.step_gap:.5f} nats")
        # the forward loss on sequences of a batch the warm-up did not
        # train on, at the parameters the warm-up left
        n = int(rc["sequences"])
        tok, lab = (a[:n] for a in self.batches[-1])
        self.ref_loss = float(jax.jit(ref.loss, static_argnums=3)(
            self.params, tok, lab, g.n_heads))
        self.sys_loss = float(jax.device_get(self.trainer.loss(
            self.params, *self.trainer.shard_data(tok, lab))))
        self.log(f"reference: forward loss on {n} sequences "
                 f"{self.sys_loss:.5f}, plain float32 reference "
                 f"{self.ref_loss:.5f}, difference "
                 f"{abs(self.sys_loss - self.ref_loss):.5f} nats "
                 f"({time.monotonic() - t2:.1f} s)")

    def _window(self, seconds, max_steps=None, note=False):
        """Steps dispatched back to back for `seconds` (or `max_steps`);
        returns (t0, t1, losses) with t1 taken after the last step is
        ready."""
        import jax
        span = jax.profiler.TraceAnnotation if note \
            else (lambda _: contextlib.nullcontext())
        losses = []
        t0 = time.monotonic()
        while True:
            with span(DISPATCH):
                losses.append(self._step(
                    self.batches[len(losses) % len(self.batches)]))
            if len(losses) >= 2:
                with span(WAIT):
                    losses[-2].block_until_ready()
            if max_steps is not None and len(losses) >= max_steps:
                break
            if max_steps is None and time.monotonic() - t0 >= seconds:
                break
        with span(WAIT):
            losses[-1].block_until_ready()
        return t0, time.monotonic(), losses

    def run(self, seconds, trace):
        import jax
        env = self.env
        c0 = env.compiles.total
        ctx = None
        if not trace:
            t0, t1, losses = self._window(seconds)
        else:
            # blocking steps for the per-step time, for most of the
            # window; then the profiled slice
            t0 = time.monotonic()
            losses, step_s = [], []
            n_prof = int(env.traffic.get("profile_steps", 3))
            while time.monotonic() - t0 < seconds or len(step_s) < 3:
                s0 = time.monotonic()
                loss = self._step(
                    self.batches[len(losses) % len(self.batches)])
                loss.block_until_ready()
                step_s.append(time.monotonic() - s0)
                losses.append(loss)
            shutil.rmtree(env.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(env.trace_dir, profiler_options=opts)
            _, t1, more = self._window(0, max_steps=n_prof, note=True)
            jax.profiler.stop_trace()
            losses += more
            device, host = trace_reduce.read_profile(
                trace_reduce.find_xplane(env.trace_dir), env.rehearse)
            reduced = trace_reduce.reduce_events(
                device, host, labels=(DISPATCH, WAIT))
            if not env.keep_trace:
                shutil.rmtree(env.trace_dir, ignore_errors=True)
            self.log(f"traced: {len(step_s)} blocking steps, then "
                     f"{n_prof} profiled; slice {reduced.window_s:.3f} s"
                     f", device busy {reduced.busy_s:.3f} s, programs "
                     f"{ {k: v[1] for k, v in reduced.modules.items()} }")
            ctx = types.SimpleNamespace(
                trace=reduced, spans=[], flight=[], steps=step_s,
                counters={"profiled_steps": n_prof,
                          "steps": len(losses)},
                config=env.config, traffic=env.traffic, peaks=env.peaks,
                log=self.log)
        self.compiles_in_window = env.compiles.total - c0
        self.losses = [float(x) for x in jax.device_get(losses)]
        window = t1 - t0
        rate = len(losses) * self.tokens_per_step / window
        g = self.gcfg
        fpt = stats.gpt_train_flops_per_token(
            g.d_model, g.n_layers, g.seq_len, g.vocab_size)
        self.log(f"window {window:.3f} s: {len(losses)} steps of "
                 f"{self.tokens_per_step} tokens, {rate:.1f} tokens/s, "
                 f"{window / len(losses) * 1e3:.2f} ms/step; "
                 f"{fpt / 1e9:.3f} GFLOP/token, MFU "
                 f"{stats.mfu(rate, fpt, env.peaks['bf16_flops_per_s'], env.chips):.4f}"
                 f" of {env.peaks['bf16_flops_per_s'] / 1e12:.0f} TFLOP/s"
                 f" ({env.peaks['source']}); loss {self.losses[0]:.4f} "
                 f"-> {self.losses[-1]:.4f}")
        out = {"window_start": t0,
               "end_to_end": {} if trace else
               {"train_tokens_per_s": rate},
               "attempted": len(losses),
               "failed": sum(not math.isfinite(x) for x in self.losses),
               "compiles_in_window": self.compiles_in_window}
        if ctx is not None:
            out["ctx"] = ctx
        return out

    def check(self):
        w = self.warm_losses
        bad = [x for x in self.losses if not math.isfinite(x)]
        n = self.trainer._step.compile_count()
        checks = {
            "compiles in the window": self.compiles_in_window
            and f"{self.compiles_in_window} executables were built or "
                "loaded inside the measured window",
            "train step compiles": n != 1 and f"{n}, wanted 1",
            "kernels": self.kernels_missing
            and f"{self.kernels_missing} are not in the train step: an "
                "XLA fallback ran in their place",
            # on two sequences at this learning rate the loss does not
            # fall step by step (nor does the reference's): it has to
            # be finite and to get under its first value
            "warm-up loss": not (all(map(math.isfinite, w))
                                 and min(w) < w[0])
            and f"the loss did not fall in the warm-up steps: {w}",
            "reference": not abs(self.sys_loss - self.ref_loss)
            <= self.env.config["reference"]["loss_tolerance"]
            and f"forward loss {self.sys_loss} against the float32 "
                f"reference's {self.ref_loss}",
            "reference steps": not self.step_gap
            <= self.env.config["reference"]["step_loss_tolerance"]
            and f"warm-up losses {w} against the float32 reference's "
                f"{self.ref_steps}",
            "window losses": bad and f"{len(bad)} non-finite losses",
        }
        return {k: v or None for k, v in checks.items()}
