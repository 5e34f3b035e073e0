"""Driver for serving cells of an Olmo-Hybrid configuration:
`serve_frontend`'s `Driver` — the same frontend, load, window, traced
context and checks — with the model built by `models.olmo_hybrid` from
the configuration's `config.json`-style keys, and the rows of logits
the sentinel's tokens were taken from held against the Olmo-Hybrid
reference. What a model-provided block needs of a driver whatever its
architecture (the sentinel served by `engine.step` so that
`engine.sample_logits` can be read, the profiled slice's bounds for the
readers, the check on the logits' error) is `serve_frontend_afmoe`'s,
inherited; there is no routing here, so one pass of the reference, no
near-ties, no swaps.
"""
from __future__ import annotations

import time

from harness import kernels
from harness.files import load_module
from harness.traffic import RequestSource

_block = load_module("drivers", "serve_frontend_afmoe")


def reference_cfg(arch):
    """What the plain reference needs of the architecture, as numbers."""
    return dict(num_heads=arch.num_heads, head_dim=arch.head_dim,
                linear_heads=arch.linear_heads,
                linear_key_dim=arch.linear_key_dim,
                linear_value_dim=arch.linear_value_dim, eps=arch.eps,
                allow_neg_eigval=arch.allow_neg_eigval,
                layer_kinds=arch.layer_kinds)


class Driver(_block.Driver):
    def setup(self):
        from paddle_tpu import inference
        from paddle_tpu.models import olmo_hybrid
        from paddle_tpu.serving import tracing

        env, cfg = self.env, self.env.config
        engine_kw = dict(cfg["engine"])
        t0 = time.monotonic()
        arch = olmo_hybrid.arch_from_config(
            cfg, compute_dtype=cfg["compute_dtype"],
            delta_chunk=cfg["delta_chunk"])
        model = olmo_hybrid.OlmoHybridForGeneration(arch, seed=env.seed)
        t1 = time.monotonic()
        sampling = engine_kw.pop("sampling", None)
        icfg = inference.Config().enable_continuous_batching(
            sampling=sampling, **engine_kw)
        self.frontend = inference.create_serving_frontend(
            icfg, model, seed=env.seed % (2 ** 31 - 1))
        self.engine = e = self.frontend.engine
        t2 = time.monotonic()
        self.model = model
        self.vocab = arch.vocab_rows
        self.source = RequestSource(
            env.traffic, self.vocab,
            min(arch.max_positions,
                int(engine_kw.get("max_seq_len", 1 << 30))), env.seed)
        kv = e.kv
        self.log(f"model built in {t1 - t0:.1f} s, engine in "
                 f"{t2 - t1:.1f} s: {len(arch.layer_kinds)} layers "
                 f"{[k[0] for k in arch.layer_kinds]}, {kv.max_slots} "
                 f"slots, block {e.block_size}, token budget "
                 f"{e.token_budget}, {kv.num_blocks} blocks a full layer "
                 f"({len(kv.attention_layers)} of them), "
                 f"{kv.state_bytes / 1e9:.3f} GB of recurrent state and "
                 f"convolution tails for {len(kv.linear_layers)} linear "
                 f"layers, chunks of {arch.delta_chunk}, sampling "
                 f"{e.sampling.strategy}")
        # a request's spans: an event a token and a prefill chunk; the
        # tracer's default of 512 a request is under this traffic's
        # longest
        tr = env.traffic
        tracing.TRACER.max_events = max(
            tracing.TRACER.max_events,
            2 * (int(tr["output_len"]["max"]) + 64
                 + int(tr["prompt_len"]["max"]) // e.token_budget))
        traced = e._step_fn._jitted.trace(*e.example_step_args())
        self.kernels_missing, found = kernels.check_step(
            traced, cfg["kernels"], env.rehearse)
        self.log(f"mixed step kernels: {found} "
                 f"({time.monotonic() - t2:.1f} s to trace and lower)")

    def compare(self, prompt, answer, rows, tokens=None, cfg=None):
        """`rows [N, V]`, the logits a computation gave at the N
        positions that follow `prompt` teacher-forced along `answer`,
        and the `tokens` it took from them (default: `answer`), against
        the plain float32 reference (`configs/<config>_reference.py`),
        one pass. At each position the error is the root mean square of
        (row - reference row) in standard deviations of the reference
        row. Returns {"err": [N], "margin": [N] (how far under the
        reference row's largest logit the token lies, in its sigmas)}."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        ref = load_module("configs", self.env.config_name + "_reference")
        cfg = cfg or reference_cfg(self.model.arch)
        tokens = np.asarray(answer if tokens is None else tokens)
        ids = jnp.asarray(list(prompt) + list(answer[:-1]), jnp.int32)
        N = len(answer)
        z = np.asarray(jax.jit(lambda w, i: ref.logits(
            w, i, cfg, last=N))(self.model.weights, ids))
        sigma = z.std(-1)
        err = np.sqrt(np.mean((np.asarray(rows) - z) ** 2, -1)) / sigma
        margin = (z.max(-1) - z[np.arange(N), tokens]) / sigma
        return {"err": err, "margin": margin}

    def _against_reference(self, prompt, answer):
        """As the base driver's, on more: the sentinel's tokens through
        the frontend are its tokens through the engine alone, and the
        logits they were taken from lie within `logit_err_sigmas` of
        the float32 reference at every position (`compare`). Returns
        (share of exact matches, widest margin in sigmas); the logits'
        widest error is kept for `check`."""
        import numpy as np
        got = self.compare(prompt, self.direct, self.rows)
        self.ref_err = float(got["err"].max())
        self.ref_err_mean = float(got["err"].mean())
        if list(answer) != self.direct:
            self.ref_err = float("inf")
            self.log(f"the sentinel through the frontend {list(answer)} "
                     f"is not the sentinel through the engine "
                     f"{self.direct}")
        self.log("reference, by position: logits' error / token's "
                 "margin, in sigma: " + " ".join(
                     f"{e:.4f}/{m:.3f}"
                     for e, m in zip(got["err"], got["margin"])))
        self.log(f"reference: the sentinel's {len(answer)} rows of "
                 f"{self.rows.shape[1]} logits lie within "
                 f"{self.ref_err:.4f} sigma (rms) of the float32 "
                 f"reference's, mean {self.ref_err_mean:.4f}; limits "
                 f"{self.env.config['reference']['logit_err_sigmas']} a "
                 f"row, "
                 f"{self.env.config['reference']['logit_err_mean_sigmas']}"
                 f" the mean")
        return float((got["margin"] == 0).mean()), \
            float(np.max(got["margin"]))

    def check(self):
        """The inherited checks (the worst row against
        `logit_err_sigmas` among them), and the MEAN over the rows
        against `logit_err_mean_sigmas`: the worst of 32 rows has a
        tail (one row in ten runs reads twice the others) and needs
        room, the mean is the same to 3% from seed to seed and tells a
        small systematic deviation (a rounded state, a dropped carry)
        from the configuration's own rounding."""
        checks = super().check()
        limit = self.env.config["reference"]["logit_err_mean_sigmas"]
        checks["reference logits, mean"] = (
            f"the sentinel's rows of logits lie {self.ref_err_mean} "
            f"sigma (rms, mean over the rows) from the float32 "
            f"reference's, over {limit}"
            if self.ref_err_mean > limit else None)
        return checks
