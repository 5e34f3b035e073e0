"""Driver for serving cells of an AFMoE configuration:
`serve_frontend`'s `Driver` — the same frontend, load, window, traced
context and checks — with the model built by `models.afmoe` from the
configuration's `config.json`-style keys, and the sentinel held against
the AFMoE reference.
"""
from __future__ import annotations

import time

from harness import kernels
from harness.files import load_module
from harness.traffic import RequestSource

_base = load_module("drivers", "serve_frontend")


def reference_cfg(arch):
    """What the plain reference needs of the architecture, as numbers."""
    return dict(num_heads=arch.num_heads, num_kv_heads=arch.num_kv_heads,
                head_dim=arch.head_dim, window=arch.window, eps=arch.eps,
                rope_theta=arch.rope_theta, layer_kinds=arch.layer_kinds,
                top_k=arch.moe.top_k, route_scale=arch.moe.route_scale,
                expert_rank=arch.moe.expert_rank)


class Driver(_base.Driver):
    def setup(self):
        from paddle_tpu import inference
        from paddle_tpu.models import afmoe

        env, cfg = self.env, self.env.config
        engine_kw = dict(cfg["engine"])
        t0 = time.monotonic()
        # the source's keys with the router at its published width; the
        # chip's share beside them
        source = dict(cfg, num_experts=cfg["num_experts_published"])
        arch = afmoe.arch_from_config(
            source, experts_held=cfg["num_experts"],
            expert_rank=cfg["expert_rank"], vocab_rows=cfg["vocab_size"],
            compute_dtype=cfg["compute_dtype"])
        model = afmoe.AfmoeForGeneration(arch, seed=env.seed)
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
        held = sum(1 for l in arch.layers if l.ffn == afmoe.MOE) \
            * arch.moe.experts_held
        self.log(f"model built in {t1 - t0:.1f} s, engine in "
                 f"{t2 - t1:.1f} s: {len(arch.layers)} layers "
                 f"{[l.attention[0] + '/' + l.ffn for l in arch.layers]}, "
                 f"{held} experts held, {e.kv.max_slots} slots, block "
                 f"{e.block_size}, token budget {e.token_budget}, "
                 f"{e.kv.num_blocks} full-layer blocks and "
                 f"{e.kv.num_window_blocks} window-layer blocks a layer, "
                 f"sampling {e.sampling.strategy}")
        # a request's spans: an event a token and a prefill chunk; the
        # tracer's default of 512 a request is under this traffic's
        # longest (PADDLE_TPU_TRACE_EVENTS_MAX is what a deployer sets)
        from paddle_tpu.serving import tracing
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

    def _context(self, w0, w1, steps):
        """The base driver's context, and the profiled slice's bounds
        on the host's clock beside it, so that a reader can hold a
        kernel's time against the counts of the same steps."""
        ctx = super()._context(w0, w1, steps)
        ctx.slice = tuple(self.slice) if self.slice else None
        return ctx

    def warm(self):
        """The sentinel first goes through the engine directly, before
        the frontend's loop starts, so that the rows of logits its
        tokens were taken from (`engine.sample_logits`) can be read
        step by step; then as the base driver's, through the frontend."""
        self.direct, self.rows = self.sentinel_rows()
        super().warm()

    def sentinel_rows(self):
        """-> (the sentinel's greedy tokens, the float32 logits row each
        was the largest of [tokens, V]), served alone by `engine.step`."""
        import numpy as np
        e, s = self.engine, self.source.sentinel()
        req = e.submit(list(s.prompt), max_new_tokens=s.max_new_tokens)
        rows, slot = [], -1
        while e.scheduler.has_work:
            n = len(req.output)
            e.step()
            slot = req.slot if req.slot >= 0 else slot
            if len(req.output) > n:
                rows.append(np.asarray(e.sample_logits[slot]))
        return list(req.output), np.stack(rows)

    def compare(self, prompt, answer, rows, tokens=None, cfg=None):
        """`rows [N, V]`, the logits a computation gave at the N
        positions that follow `prompt` teacher-forced along `answer`,
        and the `tokens` it took from them (default: `answer`), against
        the plain float32 reference (`configs/<config>_reference.py`).

        At each position the error is the root mean square of (row -
        reference row) in standard deviations of the reference row.
        Top-k routing is discontinuous: where, in an expert layer, an
        expert the reference chose and one it did not score within
        `tie_gap` of each other (one of them held here), a computation
        that rounds differently may take the other, and both are
        correct. So a position whose error is over the limit is held
        against the reference's other answers too: one such pair
        swapped at a time, the closest first, from the first layer down
        (a swap changes what the later layers see, so their near-ties
        are read from the swapped pass), at most `max_passes` passes of
        the reference in all; it keeps the least error found. No
        position is left out. Returns {"err": [N], "err0": [N]
        (against the reference's own choice), "margin": [N] (how far
        under the best reference row's largest logit the token lies, in
        its sigmas), "swaps": [N] tuples of (expert layer, rank out,
        rank in) in the row kept, "passes": passes made}."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        ref = load_module("configs", self.env.config_name + "_reference")
        rc = self.env.config["reference"]
        cfg = cfg or reference_cfg(self.model.arch)
        tokens = np.asarray(answer if tokens is None else tokens)
        ids = jnp.asarray(list(prompt) + list(answer[:-1]), jnp.int32)
        N, S = len(answer), len(prompt) + len(answer) - 1
        L = sum("router" in lw for lw in self.model.weights["layers"])
        k = cfg["top_k"]
        R = min(ref.EDGE, k)
        run = jax.jit(lambda w, i, sw: ref.logits(w, i, cfg, last=N,
                                                  swap=sw))
        err, err0 = np.full(N, np.inf), np.full(N, np.inf)
        margin, swaps = np.full(N, np.inf), [()] * N
        queue = [[()] for _ in range(N)]
        passes = 0
        while any(queue) and passes < rc["max_passes"]:
            cand = [q.pop(0) if q else None for q in queue]
            out = np.full((L, S), -1, np.int32)
            into = out.copy()
            for p, c in enumerate(cand):
                for layer, o, i in c or ():
                    out[layer, S - N + p], into[layer, S - N + p] = o, i
            z, scores, held = (np.asarray(a) for a in run(
                self.model.weights, ids,
                (jnp.asarray(out), jnp.asarray(into))))
            passes += 1
            for p, c in enumerate(cand):
                if c is None:
                    continue
                sigma = z[p].std()
                e = float(np.sqrt(np.mean((rows[p] - z[p]) ** 2)) / sigma)
                if not c:
                    err0[p] = e
                if e < err[p]:
                    err[p], swaps[p] = e, c
                    margin[p] = (z[p].max() - z[p, tokens[p]]) / sigma
                if e <= rc["logit_err_sigmas"]:
                    queue[p] = []
                    continue
                # near-ties of the layers below the last one swapped
                more = [(scores[l, p, o] - scores[l, p, i], (l, k - R + o,
                                                             k - R + i))
                        for l in range(c[-1][0] + 1 if c else 0, L)
                        for o in range(R) for i in range(R, 2 * R)
                        if held[l, p, o] or held[l, p, i]]
                queue[p] += [c + (s,) for g, s in sorted(more)
                             if g < rc["tie_gap"]]
        return {"err": err, "err0": err0, "margin": margin,
                "swaps": swaps, "passes": passes}

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
        if list(answer) != self.direct:
            self.ref_err = float("inf")
            self.log(f"the sentinel through the frontend {list(answer)} "
                     f"is not the sentinel through the engine "
                     f"{self.direct}")
        swapped = {p: (c, round(float(got["err0"][p]), 4))
                   for p, c in enumerate(got["swaps"]) if c}
        self.log("reference, by position: logits' error / token's "
                 "margin, in sigma: " + " ".join(
                     f"{e:.4f}/{m:.3f}"
                     for e, m in zip(got["err"], got["margin"])))
        self.log(f"reference: the sentinel's {len(answer)} rows of "
                 f"{self.rows.shape[1]} logits lie within "
                 f"{self.ref_err:.4f} sigma (rms) of the float32 "
                 f"reference's, mean {got['err'].mean():.4f}, limit "
                 f"{self.env.config['reference']['logit_err_sigmas']}; "
                 f"{got['passes']} passes of the reference; positions "
                 f"that took a near-tie's other answer ((expert layer, "
                 f"rank out, rank in)s, error against the reference's "
                 f"own choice): "
                 f"{swapped or 'none'}")
        return float((got["margin"] == 0).mean()), \
            float(np.max(got["margin"]))

    def check(self):
        checks = super().check()
        limit = self.env.config["reference"]["logit_err_sigmas"]
        checks["reference logits"] = (
            f"a row of the sentinel's logits lies {self.ref_err} sigma "
            f"(rms) from the float32 reference's, over {limit}"
            if self.ref_err > limit else None)
        return checks
