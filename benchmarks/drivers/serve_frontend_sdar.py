"""Driver for serving cells of an SDAR-MoE configuration (generation by
diffusion over blocks): `serve_frontend`'s `Driver` — the same
frontend, load, window, traced context and checks — with the model
built by `models.sdar_moe` from the configuration's `config.json`-style
keys and its `generation` group. What a model-provided block needs of a
driver whatever its architecture is `serve_frontend_afmoe`'s,
inherited.

What decides `correct` here, beside the inherited checks: the sentinel
is served by `engine.step` alone, through the frontend alone, mid-window
in company and alone again after the drain, and the four give the same
tokens and the same PASS-BY-PASS block states (the engine's
`on_block_pass` hook notes, for every pass that fed one of the
sentinel's blocks, the block's first position, the L ids fed, which
positions were decided and which the pass decided). For every pass of
the first of them the engine's float32 rows `[L, V]`
(`engine.sample_logits`) are held against the plain reference's
`denoise_pass` fed the ENGINE's block state of that pass
(teacher-forced), and every decision (which positions, which tokens)
against the reference's rows (`compare`).
"""
from __future__ import annotations

import math
import time

from harness import kernels
from harness.files import load_module
from harness.traffic import RequestSource

_block = load_module("drivers", "serve_frontend_afmoe")


def reference_cfg(arch):
    """What the plain reference needs of the architecture, as numbers."""
    return dict(num_heads=arch.num_heads, num_kv_heads=arch.num_kv_heads,
                head_dim=arch.head_dim, eps=arch.eps,
                rope_theta=arch.rope_theta, top_k=arch.top_k,
                norm_topk=arch.norm_topk,
                block_length=arch.block_decoding.block_length)


def decision_faults(bd, masked, take, logc, n_pass, gap):
    """What is wrong with a denoise pass's choice of positions, given
    the REFERENCE's log-confidence `logc[i]` of each position, with
    `gap` of room for a near-tie: at least n positions are decided (n
    the rule's count for the pass); a decided position lies within
    `gap` of the n-th largest among the masked, or of the dynamic
    rule's threshold; a masked position left alone lies no more than
    `gap` above the threshold, nor (where no more than n were decided)
    above the least confident decided one. -> list of words."""
    n = min(bd.transfers(n_pass), len(masked))
    kth = sorted((logc[i] for i in masked), reverse=True)[n - 1]
    thr = math.log(bd.threshold) \
        if bd.rule == "low_confidence_dynamic" else math.inf
    least = min((logc[i] for i in take), default=math.inf)
    bad = []
    if len(take) < n:
        bad.append(f"{len(take)} positions decided, the rule wants {n}")
    for i in masked:
        if i in take and logc[i] < min(kth, thr) - gap:
            bad.append(f"position {i} decided at log-confidence "
                       f"{logc[i]:.4f}, the {n}-th largest is {kth:.4f}")
        if i not in take and (logc[i] > thr + gap or (
                len(take) <= n and logc[i] > least + gap)):
            bad.append(f"position {i} left masked at log-confidence "
                       f"{logc[i]:.4f}, the least decided is {least:.4f}")
    return bad


class Driver(_block.Driver):
    def setup(self):
        from paddle_tpu import inference
        from paddle_tpu.models import sdar_moe
        from paddle_tpu.serving import tracing

        env, cfg = self.env, self.env.config
        engine_kw = dict(cfg["engine"])
        t0 = time.monotonic()
        arch = sdar_moe.arch_from_config(
            cfg, generation=cfg["generation"],
            compute_dtype=cfg["compute_dtype"])
        model = sdar_moe.SdarMoeForGeneration(arch, seed=env.seed)
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
        bd = arch.block_decoding
        self.log(f"model built in {t1 - t0:.1f} s, engine in "
                 f"{t2 - t1:.1f} s: {arch.num_layers} layers, "
                 f"{arch.num_experts} experts of {arch.expert_width} "
                 f"(top {arch.top_k}) a layer, {e.kv.max_slots} slots, "
                 f"block {e.block_size}, token budget {e.token_budget}, "
                 f"{e.kv.num_blocks} blocks a layer; decodes by blocks of "
                 f"{bd.block_length} ({bd.rule}, threshold {bd.threshold}, "
                 f"{bd.denoising_steps} steps, mask id {bd.mask_token_id})"
                 f", sampling {e.sampling.strategy}")
        # every pass that feeds a block of the sentinel's, by request
        # (the sentinel made ONCE: the hook runs a slot pass, in the
        # measured window too)
        self.passes = {}
        self._sentinel_like = {}
        s = self.source.sentinel()
        self._sentinel_key = (len(s.prompt), s.max_new_tokens, s.prompt)
        e.on_block_pass = self._note_pass
        # a request's spans: an event a delivery, a committed block and
        # a prefill chunk
        tr = env.traffic
        tracing.TRACER.max_events = max(
            tracing.TRACER.max_events,
            3 * (int(tr["output_len"]["max"]) + 64
                 + int(tr["prompt_len"]["max"]) // e.token_budget))
        traced = e._step_fn._jitted.trace(*e.example_step_args())
        self.kernels_missing, found = kernels.check_step(
            traced, cfg["kernels"], env.rehearse)
        self.log(f"mixed step kernels: {found} "
                 f"({time.monotonic() - t2:.1f} s to trace and lower)")

    def _note_pass(self, req, start, fed, was, take, tokens):
        """The engine's hook: a pass fed `req`'s block from `start` the
        ids `fed` with the positions `was` decided, and decided `take`
        as `tokens` (a commit: nothing). Kept for the sentinel alone:
        two ints tell the others apart, in the measured window too."""
        length, horizon, prompt = self._sentinel_key
        if len(req.prompt) != length or req.max_new_tokens != horizon:
            return
        like = self._sentinel_like.get(req.req_id)
        if like is None:
            like = self._sentinel_like[req.req_id] = \
                list(req.prompt) == prompt
        if like:
            self.passes.setdefault(req.req_id, []).append(
                (int(start), tuple(fed), tuple(was), tuple(take),
                 tuple(tokens)))

    def sentinel_rows(self):
        """-> (the sentinel's tokens, its passes: (block start, ids fed,
        positions decided before, positions the pass decided, their
        tokens, the engine's float32 rows [L, V] of the pass)), served
        alone by `engine.step`."""
        import numpy as np
        e, s = self.engine, self.source.sentinel()
        req = e.submit(list(s.prompt), max_new_tokens=s.max_new_tokens)
        out, slot = [], -1
        while e.scheduler.has_work:
            n = len(self.passes.get(req.req_id, ()))
            slot = req.slot if req.slot >= 0 else slot
            e.step()
            slot = req.slot if req.slot >= 0 else slot
            for p in self.passes.get(req.req_id, ())[n:]:
                out.append(p + (np.asarray(e.sample_logits[slot]),))
        return list(req.output), out

    def compare(self, prompt, passes, cfg=None, ref=None,
                rows_only=False):
        """The engine's `passes` of one request (as `sentinel_rows`
        gives them) against the plain float32 reference
        (`configs/<config>_reference.py`): for every pass, the
        reference's `denoise_pass` over a cache of its own K/V of the
        blocks before, fed the ids the ENGINE fed. A row's error is the
        root mean square of (row - reference row) in standard deviations
        of the reference row. Top-k routing is discontinuous, and with
        128 experts a near-tie at the top-8 boundary is the rule, not
        the exception: a row that reads over `logit_search_sigmas` (what
        a row whose routing agrees with the reference's stays under) is
        held against the reference's other answers too, as
        `serve_frontend_afmoe` does: one near-tie (two experts
        either side of the top-k boundary whose log-probabilities lie
        within `tie_gap`) swapped at a time, the closest first, from the
        first layer down; a swap that lowers the row's error is followed
        first (the near-ties of the layers below it, read from the
        swapped pass), one that does not is dropped; at most
        `max_passes` reference passes an engine pass; the least error
        stands. Every DECISION is held
        against the row kept: the token within `margin_sigmas` of its
        largest logit, the positions by `decision_faults`. Returns
        {"err": [passes, L], "err0" (the reference's own choice),
        "margin": [decisions], "faults": [words], "swaps": {(pass, row):
        ((layer, rank out, rank in), ...)}, "passes": reference passes
        made}. A block the engine left without a commit pass is
        committed by the reference all the same (its final ids, its own
        K/V), so that a program that skips the commit is held against a
        reference that does not. `rows_only`: just the reference's own
        rows `[L, V]`, a pass (what a control feeds back in as a
        computation's rows)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        ref = ref or load_module("configs",
                                 self.env.config_name + "_reference")
        rc = self.env.config["reference"]
        cfg = cfg or reference_cfg(self.model.arch)
        bd = self.model.arch.block_decoding
        L, k = bd.block_length, cfg["top_k"]
        R = min(ref.EDGE, k)
        w = self.model.weights
        layers = len(w["layers"])
        n0 = len(prompt) // L * L
        length = max(p[0] for p in passes) + L
        cache = jax.jit(lambda w, i: ref.prefix(w, i, cfg, length))(
            w, jnp.asarray(prompt[:n0], jnp.int32))
        run = jax.jit(lambda w, c, n, i, sw: ref.denoise_pass(
            w, c, n, i, cfg, sw))
        append = jax.jit(ref.append)
        err = np.full((len(passes), L), np.inf)
        err0 = err.copy()
        margins, faults, swaps, made = [], [], {}, 0
        n_pass = {}                       # denoise passes a block has had
        none = jnp.full((layers, L), -1, jnp.int32)
        pending, own_rows = None, []
        for pi, (start, fed, was, take, tokens, rows) in enumerate(passes):
            if pending is not None and pending[0] != start:
                kvs = run(w, cache, pending[0], jnp.asarray(
                    pending[1], jnp.int32), (none, none))[2]
                cache, pending = append(cache, pending[0], kvs), None
            ids = jnp.asarray(fed, jnp.int32)
            if rows_only:
                z, _, own = run(w, cache, start, ids, (none, none))
                own_rows.append(np.asarray(z))
                if all(was):
                    cache, pending = append(cache, start, own), None
                else:
                    pending = (start, self._filled(fed, take, tokens))
                continue
            # (swaps, the error of the row under all but the last of them)
            queue = [[((), np.inf)] for _ in range(L)]
            logc, zmax, ztok, sig = ([None] * L for _ in range(4))
            own, tries = None, 0
            while any(queue) and tries < rc["max_passes"]:
                cand = [q.pop(0) if q else None for q in queue]
                out = np.full((layers, L), -1, np.int32)
                into = out.copy()
                for r, c in enumerate(cand):
                    for layer, o, i in (c[0] if c else ()):
                        out[layer, r], into[layer, r] = o, i
                z, edge, kvs = run(w, cache, start, ids,
                                   (jnp.asarray(out), jnp.asarray(into)))
                z, edge = np.asarray(z), np.asarray(edge)
                own = kvs if own is None else own
                tries += 1
                for r, c in enumerate(cand):
                    if c is None:
                        continue
                    c, before = c
                    sigma = z[r].std()
                    e = float(np.sqrt(np.mean((rows[r] - z[r]) ** 2))
                              / sigma)
                    if not c:
                        err0[pi, r] = e
                    if e < err[pi, r]:
                        err[pi, r] = e
                        swaps[(pi, r)] = c
                        top = z[r].max()
                        zmax[r], sig[r] = top, sigma
                        # the reference's confidence: its largest
                        # softmax probability
                        logc[r] = -float(np.log(
                            np.sum(np.exp(z[r] - top))))
                        if r in take:
                            ztok[r] = z[r, tokens[take.index(r)]]
                    if e <= rc["logit_search_sigmas"]:
                        queue[r] = []
                        continue
                    if e >= before:
                        continue    # a swap that did not help: no more
                    # a swap that helped is followed FIRST: the near-ties
                    # of the layers below it, the closest first (a row
                    # with a flip in two layers is two swaps deep)
                    more = [(edge[l, r, o] - edge[l, r, i],
                             (l, k - R + o, k - R + i))
                            for l in range(c[-1][0] + 1 if c else 0, layers)
                            for o in range(R) for i in range(R, 2 * R)]
                    queue[r][:0] = [(c + (s,), e) for g, s in sorted(more)
                                    if g < rc["tie_gap"]]
            made += tries
            masked = [i for i in range(L) if not was[i]]
            if not masked:
                # the commit: the block is final, its K/V (the
                # reference's own) joins the cache
                cache, pending = append(cache, start, own), None
                continue
            pending = (start, self._filled(fed, take, tokens))
            for i in take:
                margins.append(float((zmax[i] - ztok[i]) / sig[i]))
            for word in decision_faults(
                    bd, masked, take, logc, n_pass.get(start, 0),
                    rc["confidence_tie_gap"]):
                faults.append(f"pass {pi} (block at {start}): {word}")
            n_pass[start] = n_pass.get(start, 0) + 1
        if rows_only:
            return own_rows
        return {"err": err, "err0": err0, "margin": np.asarray(margins),
                "faults": faults, "passes": made,
                "swaps": {key: c for key, c in swaps.items() if c}}

    def _against_reference(self, prompt, answer):
        """As the base driver's, on more: the sentinel's tokens through
        the frontend are its tokens through the engine alone, every row
        of logits of every pass lies within `logit_err_sigmas` of the
        float32 reference's (`compare`), and so does every decision.
        Returns (share of decided tokens that are the reference's
        largest logit, widest margin in sigmas); the rest is kept for
        `check`."""
        import numpy as np
        got = self.compare(prompt, self.rows)
        err = got["err"]
        self.ref_err = float(err.max())
        self.ref_err_mean = float(err.mean())
        self.decision_faults = got["faults"]
        if list(answer) != self.direct:
            self.ref_err = float("inf")
            self.log(f"the sentinel through the frontend {list(answer)} "
                     f"is not the sentinel through the engine "
                     f"{self.direct}")
        rc = self.env.config["reference"]
        self.log("reference, by pass: the rows' worst error in sigma: "
                 + " ".join(f"{e:.4f}" for e in err.max(-1)))
        self.log(f"reference: {err.shape[0]} passes of "
                 f"{err.shape[1]} rows of {self.rows[0][5].shape[1]} "
                 f"logits lie within {self.ref_err:.4f} sigma (rms) of "
                 f"the float32 reference's, mean "
                 f"{self.ref_err_mean:.4f}; limits "
                 f"{rc['logit_err_sigmas']} a row, "
                 f"{rc['logit_err_mean_sigmas']} the mean (rows over "
                 f"{rc['logit_search_sigmas']} searched); "
                 f"{got['passes']} passes of the reference; rows that "
                 f"took a near-tie's other answer ((pass, row): (layer, "
                 f"rank out, rank in)s): {got['swaps'] or 'none'}; "
                 f"against the reference's own choice the worst row "
                 f"reads {float(got['err0'].max()):.4f}; "
                 f"{len(got['margin'])} decisions, faults: "
                 f"{got['faults'] or 'none'}")
        margin = got["margin"]
        return float((margin == 0).mean()), float(np.max(margin))

    def check(self):
        """The inherited checks (the worst row against
        `logit_err_sigmas`, the tokens' margin, the sentinel alone and
        in company among them), the mean over the rows, the decisions,
        and the block states pass by pass: every serving of the
        sentinel made the same passes."""
        checks = super().check()
        rc = self.env.config["reference"]
        checks["reference logits, mean"] = (
            f"the sentinel's rows of logits lie {self.ref_err_mean} "
            f"sigma (rms, mean over the rows) from the float32 "
            f"reference's, over {rc['logit_err_mean_sigmas']}"
            if self.ref_err_mean > rc["logit_err_mean_sigmas"] else None)
        checks["decisions"] = "; ".join(self.decision_faults[:3]) or None
        served = [self.passes[k] for k in sorted(self.passes)]
        differ = [i for i, p in enumerate(served) if p != served[0]]
        checks["block states"] = (
            f"{len(served)} servings of the sentinel, wanted 4 (engine "
            "alone, frontend alone, in company, after the drain)"
            if len(served) != 4 else
            f"serving {differ[0]} of the sentinel made other passes than "
            f"the first: {self._first_difference(served[0], served[differ[0]])}"
            if differ else None)
        return checks

    @staticmethod
    def _filled(fed, take, tokens):
        """The ids a pass fed, with what it decided."""
        ids = list(fed)
        for i, t in zip(take, tokens):
            ids[i] = t
        return ids

    @staticmethod
    def _first_difference(a, b):
        for i, (x, y) in enumerate(zip(a, b)):
            if x != y:
                return f"pass {i}: {x} against {y}"
        return f"{len(a)} passes against {len(b)}"
