"""Driver for serving cells of an A.X-K1 configuration (multi-head latent
attention over a paged latent cache, group-limited sigmoid routing):
`serve_frontend_afmoe`'s `Driver` — the same frontend, load, window,
traced context and checks; the sentinel served by `engine.step` alone
first, the float32 rows of logits its tokens were taken from
(`engine.sample_logits`), the profiled slice's bounds — with the model
built by `models.axk1` from the configuration's `config.json`-style
keys.

What decides `correct` here, beside the inherited checks: the
sentinel's rows (the prefill's last row and every decode row, through
the latent pool) are held against the plain float32 reference in the
EXPANDED form over prompt + greedy tokens, teacher-forced (`compare`):
the reference makes ONE pass over the positions before the first
compared row and keeps their latent rows, then passes over the N rows
alone, in which the routers' near-ties are searched (an expert pair at
the top-k boundary, or a kept group and one left out at the group
boundary, within `tie_gap`); and the engine's cached latent rows of
layer 0, which no router precedes, are held against the reference's
own (`cache_err_layer0`). `Driver.faults` says what the configuration's
limits make of a comparison.
"""
from __future__ import annotations

import time

from harness import kernels
from harness.files import load_module
from harness.traffic import RequestSource

_block = load_module("drivers", "serve_frontend_afmoe")


def reference_cfg(arch, ref):
    """What the plain reference needs of the architecture, as numbers
    (the rotary and the scale it derives itself, `ref.numbers`)."""
    factor, orig, beta_fast, beta_slow, _, mscale_all = arch.yarn
    m = arch.moe
    return ref.numbers(dict(
        num_heads=arch.num_heads, nope_dim=arch.nope_dim,
        rope_dim=arch.rope_dim, v_dim=arch.v_dim,
        kv_lora_rank=arch.kv_lora_rank, eps=arch.eps,
        rope_theta=arch.rope_theta, factor=factor,
        original_positions=orig, beta_fast=beta_fast,
        beta_slow=beta_slow, mscale_all_dim=mscale_all, top_k=m.top_k,
        n_group=m.n_group, topk_group=m.topk_group,
        route_scale=m.route_scale, expert_rank=m.expert_rank))


class Driver(_block.Driver):
    def setup(self):
        from paddle_tpu import inference
        from paddle_tpu.models import axk1
        from paddle_tpu.serving import tracing

        env, cfg = self.env, self.env.config
        engine_kw = dict(cfg["engine"])
        t0 = time.monotonic()
        # the source's keys with the router at its published width; the
        # chip's share beside them
        source = dict(cfg, n_routed_experts=cfg["n_routed_experts_published"])
        arch = axk1.arch_from_config(
            source, experts_held=cfg["n_routed_experts"],
            expert_rank=cfg["expert_rank"], vocab_rows=cfg["vocab_size"],
            compute_dtype=cfg["compute_dtype"])
        model = axk1.AXK1Model(arch, seed=env.seed)
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
        self.log(f"model built in {t1 - t0:.1f} s, engine in "
                 f"{t2 - t1:.1f} s: {arch.num_layers} layers "
                 f"({arch.first_dense} dense), {arch.num_heads} heads of "
                 f"latent attention (latent {arch.kv_lora_rank} + rope "
                 f"{arch.rope_dim}, scale {arch.scale:.6f}), "
                 f"{arch.moe.experts_held} of {arch.moe.num_experts} "
                 f"experts held (top {arch.moe.top_k} in "
                 f"{arch.moe.topk_group} of {arch.moe.n_group} groups); "
                 f"{e.kv.max_slots} slots, block {e.block_size}, token "
                 f"budget {e.token_budget}, {e.kv.num_blocks} blocks a "
                 f"layer ({e.kv.kv_bytes_per_token} B a cached token), "
                 f"sampling {e.sampling.strategy}")
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

    def sentinel_rows(self):
        """As the block drivers', and, once the sentinel is done, what
        its slot's pages hold of layer 0 (`self.cache0`: the latent rows
        `[c | kpe]` the engine cached for its positions, float32)."""
        import jax.numpy as jnp
        import numpy as np
        e, s = self.engine, self.source.sentinel()
        req = e.submit(list(s.prompt), max_new_tokens=s.max_new_tokens)
        rows, slot, table = [], -1, None
        while e.scheduler.has_work:
            n = len(req.output)
            e.step()
            slot = req.slot if req.slot >= 0 else slot
            if req.slot >= 0:
                table = e.kv.block_tables[slot].copy()
            if len(req.output) > n:
                rows.append(np.asarray(e.sample_logits[slot]))
        # the freed pages keep what was written: nothing ran since
        S = len(s.prompt) + len(req.output) - 1
        pages = jnp.asarray(table[:-(-S // e.block_size)])
        arch = self.model.arch
        pool = e.kv.latent_pools[0]
        self.cache0 = np.asarray(
            pool[pages].reshape(-1, pool.shape[-1])[
                :S, :arch.kv_lora_rank + arch.rope_dim].astype(jnp.float32))
        return list(req.output), np.stack(rows)

    def compare(self, prompt, answer, rows, tokens=None, ref=None,
                cache0=None):
        """`rows [N, V]`, the logits a computation gave at the N
        positions that follow `prompt` teacher-forced along `answer`
        (its tokens: `tokens`, default `answer`), and `cache0`, the
        latent rows it cached in layer 0 (default: the sentinel's),
        against the plain float32 reference (`configs/<config>_
        reference.py`, or the module `ref`).

        The reference makes ONE pass over the n positions before the
        first compared row (its own latent rows), then passes over the
        N rows alone. A row's error is the root mean square of (row -
        reference row) in standard deviations of the reference row.
        Top-k routing is discontinuous twice: where, in an expert layer,
        a chosen and an unchosen candidate (one of them held here) score
        within `tie_gap`, or a kept group and one left out do (of the
        `EDGE` ranks either side of the group boundary: three groups
        can tie, and the one that gives way need not be the last one
        kept), a computation that rounds differently may take the other
        side, and both are correct. So a row over
        `logit_err_sigmas` is held against the reference's other
        answers too: one such swap more at a time, the closest first,
        from the first layer down (after a group swap, an expert swap
        among the layer's new candidates may follow in the same layer),
        at most `max_swaps` swaps a row and `max_passes` passes in all;
        it keeps the least error found. -> {"err", "err0" (against the
        reference's own choice), "margin" (how far under the best
        reference row's largest logit the token lies, in its sigmas),
        "swaps" ((expert layer, rank out, rank in)s; (layer, -1 - group
        rank out, -1 - group rank in) a group swap), "passes",
        "cache_err0" (the largest relative error of a layer-0 latent row
        before the compared rows)}."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        ref = ref or load_module(
            "configs", self.env.config_name + "_reference")
        rc = self.env.config["reference"]
        cfg = reference_cfg(self.model.arch, ref)
        w = self.model.weights
        tokens = np.asarray(answer if tokens is None else tokens)
        ids = jnp.asarray(list(prompt) + list(answer[:-1]), jnp.int32)
        N, S = len(answer), len(prompt) + len(answer) - 1
        n = S - N
        L = sum("router" in lw for lw in w["layers"])
        k, kg = cfg["top_k"], cfg["topk_group"]
        R, RG = min(ref.EDGE, k), ref.group_edge(cfg)
        own = ref.prefix(w, ids[:n], cfg)
        run = jax.jit(lambda w, c, i, sw: ref.forward(
            w, i, cfg, cache=c, swap=sw)[:4])
        c0 = self.cache0 if cache0 is None else cache0
        a, b = np.asarray(c0[:n]), np.asarray(own[0])
        cache_err0 = float((np.linalg.norm(a - b, axis=1) / (
            np.linalg.norm(b, axis=1) + 1e-30)).max())
        err, err0 = np.full(N, np.inf), np.full(N, np.inf)
        margin, swaps = np.full(N, np.inf), [()] * N
        queue = [[()] for _ in range(N)]
        passes = 0
        while any(queue) and passes < rc["max_passes"]:
            cand = [q.pop(0) if q else None for q in queue]
            swap = np.full((4, L, N), -1, np.int32)
            for p, c in enumerate(cand):
                for layer, o, i in c or ():
                    if o < 0:
                        swap[2:, layer, p] = -1 - o, -1 - i
                    else:
                        swap[:2, layer, p] = o, i
            z, scores, held, groups = (np.asarray(x) for x in run(
                w, own, ids[n:], tuple(jnp.asarray(x) for x in swap)))
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
                if len(c) >= rc["max_swaps"]:
                    continue
                # near-ties of the layers below the last one swapped: an
                # expert pair (one of them held here), or a kept group
                # and one left out; a group swap gives its layer other
                # candidates, whose expert boundary may lie at a
                # near-tie of its own
                below = c[-1][0] + 1 if c else 0
                same = below - 1 if c and c[-1][1] < 0 else below
                more = [(scores[l, p, o] - scores[l, p, i],
                         (l, k - R + o, k - R + i))
                        for l in range(same, L)
                        for o in range(R) for i in range(R, 2 * R)
                        if held[l, p, o] or held[l, p, i]]
                more += [(groups[l, p, o] - groups[l, p, i],
                          (l, -1 - (kg - RG + o), -1 - (kg - RG + i)))
                         for l in range(below, L)
                         for o in range(RG) for i in range(RG, 2 * RG)]
                queue[p] += [c + (s,) for g, s in sorted(more)
                             if g < rc["tie_gap"]]
        return {"err": err, "err0": err0, "margin": margin,
                "swaps": swaps, "passes": passes, "cache_err0": cache_err0}

    def faults(self, got):
        """What the configuration's limits make of a `compare`: {name of
        the limit: words}, empty where it is correct."""
        rc = self.env.config["reference"]
        bad = {}
        for name, value in (
                ("logit_err_sigmas", got["err"].max()),
                ("margin_sigmas", got["margin"].max()),
                ("cache_err_layer0", got["cache_err0"])):
            if value > rc[name]:
                bad[name] = f"{value:.4f} over {rc[name]}"
        return bad

    def _against_reference(self, prompt, answer):
        """As `serve_frontend_afmoe`'s, and the layer-0 cache."""
        import numpy as np
        got = self.compare(prompt, self.direct, self.rows)
        self.ref_err = float(got["err"].max())
        self.ref_faults = self.faults(got)
        if list(answer) != self.direct:
            # every limit above was read off the engine stepped alone:
            # the timed path has to give the tokens that were compared
            self.ref_err = float("inf")
            self.ref_faults["sentinel through the frontend"] = (
                f"{list(answer)} is not the sentinel through the engine "
                f"alone {self.direct}, the only rows held against the "
                f"reference")
        swapped = {p: (c, round(float(got["err0"][p]), 4))
                   for p, c in enumerate(got["swaps"]) if c}
        rc = self.env.config["reference"]
        self.log("reference, by position: logits' error / token's "
                 "margin, in sigma: " + " ".join(
                     f"{e:.4f}/{m:.3f}"
                     for e, m in zip(got["err"], got["margin"])))
        self.log(f"reference: the sentinel's {len(answer)} rows of "
                 f"{self.rows.shape[1]} logits lie within "
                 f"{self.ref_err:.4f} sigma (rms) of the float32 "
                 f"reference's, mean {got['err'].mean():.4f}, limit "
                 f"{rc['logit_err_sigmas']}; layer 0's cached latent rows "
                 f"within {got['cache_err0']:.4f} (relative, limit "
                 f"{rc['cache_err_layer0']}); {got['passes']} passes of "
                 f"the reference; positions that took a near-tie's other "
                 f"answer ((expert layer, rank out, rank in)s, (layer, -1 "
                 f"- group rank out, -1 - group rank in) the groups'; "
                 f"error against the reference's own choice): "
                 f"{swapped or 'none'}")
        return float((got["margin"] == 0).mean()), \
            float(np.max(got["margin"]))

    def check(self):
        checks = super().check()
        checks.pop("reference logits", None)
        for name, words in self.ref_faults.items():
            checks["reference " + name] = words
        return checks
