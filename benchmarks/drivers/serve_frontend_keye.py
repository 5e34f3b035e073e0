"""Driver for serving cells of a Keye-VL-2.0 configuration (attention
through a learned selection): `serve_frontend`'s `Driver` — the same
frontend, load, window, traced context and checks — with the model
built by `models.keye_vl2` from the configuration's `config.json`-style
keys. What a model-provided block needs of a driver whatever its
architecture is `serve_frontend_afmoe`'s, inherited: the sentinel
served by `engine.step` alone first, the float32 rows of logits its
tokens were taken from (`engine.sample_logits`), the profiled slice's
bounds.

What decides `correct` here, beside the inherited checks: the sentinel's
rows (the prefill's last row and every decode row, through the paged
cache and the indexer-key pool) are held against the plain float32
reference's rows over prompt + greedy tokens, teacher-forced
(`compare`); beside each row the engine leaves the SELECTION it
attended (`engine.sample_selection`, every sparse layer), which is held
against the reference's own over the same cached keys: a member may
differ only where its reference score lies within `select_tie_gap` of
the row's k-th largest; and what the sentinel's pages hold (K, V,
indexer keys) is held against the reference's own cache by the median
row. `Driver.faults` says what the configuration's limits make of it.
Every one of those limits is computed from the sentinel served by
`engine.step` alone; what ties them to the timed path is that the
sentinel served through the `ServingFrontend` gives the SAME tokens, so
a difference there is a fault of its own (`check`: "reference sentinel
through the frontend").
"""
from __future__ import annotations

import time

from harness import kernels
from harness.files import load_module
from harness.traffic import RequestSource

_block = load_module("drivers", "serve_frontend_afmoe")


def reference_cfg(arch):
    """What the plain reference needs of the architecture, as numbers."""
    sel = arch.selection
    return dict(num_heads=arch.num_heads, num_kv_heads=arch.num_kv_heads,
                head_dim=arch.head_dim, eps=arch.eps,
                rope_theta=arch.rope_theta, top_k=arch.top_k,
                norm_topk=arch.norm_topk, idx_heads=sel.num_heads,
                idx_dim=sel.head_dim, idx_rope_dims=sel.rope_dims,
                idx_scale=sel.scale, topk=sel.topk)


def selection_faults(scores, own, given, topk, gap):
    """What is wrong with the selections `given [layers, N, K]` (bool, a
    computation's) beside the reference's `own` and its float32 `scores`
    (-inf: no candidate): a row with no more than `topk` candidates
    takes them all; else `topk` members, none outside the candidates,
    and a member that differs from the reference's lies within `gap`
    (in standard deviations of the row's candidate scores) of the
    row's k-th largest score. -> (list of words, most members that
    differ in a row, the widest gap among them in sigmas)."""
    import numpy as np
    bad, most, widest = [], 0, 0.0
    for l in range(scores.shape[0]):
        for p in range(scores.shape[1]):
            sc, ref, got = scores[l, p], own[l, p], given[l, p]
            cand = np.isfinite(sc)
            n = int(cand.sum())
            if (got & ~cand).any() or int(got.sum()) != min(topk, n):
                bad.append(f"layer {l} row {p}: {int(got.sum())} members"
                           f", {int((got & ~cand).sum())} of them no "
                           f"candidates, of {n} candidates")
                continue
            diff = got != ref
            if not diff.any():
                continue
            kth = sc[ref].min()
            sigma = sc[cand].std() + 1e-30
            far = float(np.abs(sc[diff] - kth).max() / sigma)
            most, widest = max(most, int(diff.sum()) // 2), max(widest, far)
            if far > gap:
                bad.append(f"layer {l} row {p}: {int(diff.sum()) // 2} "
                           f"members differ, one {far:.4f} sigma from "
                           f"the row's k-th largest score")
    return bad, most, widest


def settle_routers(run, error, shape, rc):
    """The routers' near-ties of the N compared rows, searched as
    `serve_frontend_afmoe` does (one swapped pair at a time, the closest
    first, from the first layer down) until ONE pass holds every row at
    the answer kept for it. `run(swaps)`: one pass over the rows, row p
    taking `swaps[p]` ((layer, rank out, rank in)s) -> (logits [N, V],
    the routers' log-probabilities at the ranks around the boundary
    [layers, N, 2R], ...); `error(z, p)`: row p's error in that pass;
    `shape` = (N, layers, top_k, R).

    The rows are not apart: a compared row attends, and scores, the
    keys the reference makes for the compared rows BEFORE it, so the
    answer of an earlier row's near-tie tips a later row's. A row that
    is left at the first pass, while the rows before it still take the
    reference's first answers, may sit on the other side of a near-tie
    of its own once they take the computation's (seed 730233921: row 5
    swaps in layers 0 and 1, and row 15's layer-1 router, which read
    0.0044 sigma beside the unswapped row 5, reads 0.0246 beside the
    swapped one; its selections of layers 2-5 then differ in 131, 62,
    45, 41 members). So a round ends with a pass in which every row
    routes as kept, the rows' errors are THAT pass's, and a row that it
    reads over `logit_search_sigmas`, and whose earlier rows route
    otherwise than when its search began, is searched again beside
    them. Row p settles by round p + 1 at the latest (row 0 has no row
    before it). A round makes at most `max_passes` passes.
    -> (swaps, the rows' errors in the last pass, their errors before
    any search, passes made, what the last pass gave)."""
    import numpy as np
    N, L, k, R = shape
    err, err_sel = np.full(N, np.inf), np.full(N, np.inf)
    swaps, queue = [()] * N, [[()] for _ in range(N)]
    began = [None] * N       # how the rows before it routed then
    passes = 0
    while True:
        for p in range(N):
            if queue[p]:
                began[p] = tuple(swaps[:p])
        stop = passes + rc["max_passes"] - 1
        while any(queue) and passes < stop:
            cand = [q.pop(0) if q else None for q in queue]
            z, scores = (np.asarray(a) for a in run(
                [swaps[p] if c is None else c
                 for p, c in enumerate(cand)])[:2])
            passes += 1
            for p, c in enumerate(cand):
                if c is None:
                    continue
                e = error(z, p)
                if not c and not np.isfinite(err_sel[p]):
                    err_sel[p] = e
                if e < err[p]:
                    err[p], swaps[p] = e, c
                if e <= rc["logit_search_sigmas"]:
                    queue[p] = []
                    continue
                # near-ties of the layers below the last one swapped
                more = [(scores[l, p, o] - scores[l, p, i],
                         (l, k - R + o, k - R + i))
                        for l in range(c[-1][0] + 1 if c else 0, L)
                        for o in range(R) for i in range(R, 2 * R)]
                queue[p] += [c + (s,) for g, s in sorted(more)
                             if g < rc["tie_gap"]]
        last = run(swaps)
        passes += 1
        z = np.asarray(last[0])
        err = np.asarray([error(z, p) for p in range(N)])
        again = [p for p in range(N)
                 if err[p] > rc["logit_search_sigmas"]
                 and began[p] != tuple(swaps[:p])]
        if not again:
            return swaps, err, err_sel, passes, last
        for p in again:
            queue[p] = [()]


class Driver(_block.Driver):
    def setup(self):
        from paddle_tpu import inference
        from paddle_tpu.models import keye_vl2
        from paddle_tpu.serving import tracing

        env, cfg = self.env, self.env.config
        engine_kw = dict(cfg["engine"])
        t0 = time.monotonic()
        arch = keye_vl2.arch_from_config(
            cfg, compute_dtype=cfg["compute_dtype"])
        model = keye_vl2.KeyeModel(arch, seed=env.seed)
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
        sel = arch.selection
        self.log(f"model built in {t1 - t0:.1f} s, engine in "
                 f"{t2 - t1:.1f} s: {arch.num_layers} layers, "
                 f"{arch.num_experts} experts of {arch.expert_width} "
                 f"(top {arch.top_k}) a layer, an indexer of "
                 f"{sel.num_heads} heads x {sel.head_dim} a layer, the "
                 f"{sel.topk} best keys attended; {e.kv.max_slots} slots,"
                 f" block {e.block_size}, token budget {e.token_budget}, "
                 f"{e.kv.num_blocks} blocks a layer "
                 f"({e.kv.kv_bytes_per_token} B a cached token, "
                 f"{e.kv.idx_bytes_per_token} of them indexer keys), "
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
        """As the block drivers': the sentinel served alone by
        `engine.step`; beside each token's row of logits the selections
        of that row, bool [sparse layers, positions] (kept in
        `self.selections`, in the tokens' order), and, once it is done,
        what its slot's pages hold (`self.cache`: the K, V and indexer
        keys the engine cached for its positions, a layer, float32)."""
        import jax.numpy as jnp
        import numpy as np
        e, s = self.engine, self.source.sentinel()
        req = e.submit(list(s.prompt), max_new_tokens=s.max_new_tokens)
        rows, sels, slot, table = [], [], -1, None
        while e.scheduler.has_work:
            n = len(req.output)
            e.step()
            slot = req.slot if req.slot >= 0 else slot
            if req.slot >= 0:
                table = e.kv.block_tables[slot].copy()
            if len(req.output) > n:
                rows.append(np.asarray(e.sample_logits[slot]))
                sels.append(np.asarray(e.sample_selection[:, slot]))
        self.selections = np.stack(sels)
        # the freed pages keep what was written: nothing ran since
        S = len(s.prompt) + len(req.output) - 1
        pages = jnp.asarray(table[:-(-S // e.block_size)])
        Di = self.model.arch.selection.head_dim
        self.cache = [tuple(
            a[pages].reshape((-1,) + a.shape[2:])[:S, ..., :width]
            .astype(jnp.float32)
            for a, width in ((k, None), (v, None), (i, Di)))
            for k, v, i in zip(e.kv.k_pools, e.kv.v_pools,
                               e.kv.idx_pools)]
        return list(req.output), np.stack(rows)

    def compare(self, prompt, answer, rows, tokens=None, selections=None,
                cache=None, ref=None, rows_only=False):
        """`rows [N, V]`, the logits a computation gave at the N
        positions that follow `prompt` teacher-forced along `answer`
        (the tokens it took from them: `tokens`, default `answer`);
        `selections [N, layers, >= S]` bool, the keys each of those rows
        attended in every sparse layer; `cache`, the K, V and indexer
        keys it cached for the S positions, a layer (defaults: the
        sentinel's, from `sentinel_rows`), against the plain float32
        reference (`configs/<config>_reference.py`, or the module
        `ref`).

        The reference makes ONE whole pass over the positions before
        the first compared row (`prefix`: its own cache), then passes
        over the N rows alone (`rows`):

        (a) over its OWN cache with its own selections: the reference's
        full forward. A row's error (`err_fwd`) is the root mean square
        of (row - reference row) in standard deviations of the
        reference row. The routers' top-8 of 128 flip at near-ties in
        every layer for a share of the 8k cached tokens, and under
        seeded weights an expert layer's output outweighs the residual
        it is added to, so a flipped token's keys of the NEXT layer are
        other keys: the two caches differ in whole rows, hundreds of a
        row's 2048 members differ for that reason alone, and this error
        has a floor far over rounding. Its LARGEST row is held to a
        loose limit and its MEAN over the rows to a closer one (the
        floor moves single rows, a lower precision moves them all: the
        one number that tells float8 by the logits with nothing of the
        computation's fed to the reference), and the caches are held
        against each other by the MEDIAN row (`cache_err`: a layer's
        median over the positions of the relative error of its K, V and
        indexer-key rows; layer 0, which no router precedes, by its
        largest). `cache_err` reads the positions BEFORE the first
        compared row alone: `ref.rows` hands back no cache, it makes
        the compared rows' K, V and indexer keys itself in both passes
        (the cache it is given masks every position from n on), so what
        the computation cached at a decode position is held through the
        later rows that attend and score it (`err`, the selections: how
        the stale-key control is told).
        (b) over the COMPUTATION's cache, fed the computation's
        selections: the row's error proper (`err`), the routers'
        near-ties of the compared rows searched as
        `serve_frontend_afmoe` does (`settle_routers`: one swapped pair
        at a time, the closest first, from the first layer down, and
        again for a row that the answers of the rows before it tipped,
        `max_passes` passes a round). The LAST pass, every row taking
        the side of its routers' near-ties that the search kept, gives
        the rows' errors and margins and, layer by layer,
        the selection the REFERENCE would make at that layer from the
        same cached keys and the same inputs (every layer before it
        attended the computation's selections and routed as the
        computation did, in the row itself AND in the compared rows
        before it, whose keys it scores; so its rows are the
        computation's but for rounding): the computation's selections
        are held against these (`selection_faults`); a member may
        differ only inside `select_tie_gap`. (Held against the
        reference's own pass (a), hundreds of members differ for a
        correct computation: a few swapped members of 2048 move the
        mean of 2048 random values, the row's attention output, by a
        tenth, and the next layer's scores with it.)

        `rows_only`: -> (the reference's own rows [N, V], its own
        selections [N, layers, S], its own cache), for a caller that
        holds another computation of the reference against it."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        ref = ref or load_module(
            "configs", self.env.config_name + "_reference")
        rc = self.env.config["reference"]
        cfg = reference_cfg(self.model.arch)
        w = self.model.weights
        ids = jnp.asarray(list(prompt) + list(answer[:-1]), jnp.int32)
        N, S = len(answer), len(prompt) + len(answer) - 1
        n = S - N
        L, k = len(w["layers"]), cfg["top_k"]
        R = min(ref.EDGE, k)
        own_cache = jax.jit(lambda w, i: ref.prefix(w, i, cfg, S))(
            w, ids[:n])
        run = jax.jit(lambda w, c, i, sw, sel: ref.rows(
            w, c, n, i, cfg, swap=sw, select=sel))

        def rows_pass(c, cand, given):
            """One pass over the N rows against cache `c`: `cand[p]`
            the swaps of row p."""
            out = np.full((L, N), -1, np.int32)
            into = out.copy()
            for p, sw in enumerate(cand):
                for layer, o, i in sw or ():
                    out[layer, p], into[layer, p] = o, i
            return run(w, c, ids[n:],
                       (jnp.asarray(out), jnp.asarray(into)), given)

        def keys(sel):
            """[N, layers, positions] -> [layers, N, the cache's S rows
            + the N rows], as `ref.rows` lays a row's keys."""
            sel = np.asarray(sel)[:, :, :S].transpose(1, 0, 2)
            return np.concatenate(
                [sel & (np.arange(S) < n), sel[:, :, n:]], -1)

        z_fwd, _, _, own = (np.asarray(a) for a in rows_pass(
            own_cache, [()] * N, None))
        if rows_only:
            pos = np.concatenate([own[:, :, :n], own[:, :, S:]], -1)
            return z_fwd, pos.transpose(1, 0, 2), own_cache
        cache = [tuple(a[:S] for a in kv)
                 for kv in (self.cache if cache is None else cache)]

        def rel(a, b):
            a, b = (np.asarray(x[:n]).reshape(n, -1) for x in (a, b))
            return np.linalg.norm(a - b, axis=1) / (
                np.linalg.norm(b, axis=1) + 1e-30)

        cache_err = [max(float(np.median(rel(a, b)) if li else
                               rel(a, b).max())
                         for a, b in zip(cache[li], own_cache[li]))
                     for li in range(L)]
        moved = [float((rel(cache[li][0], own_cache[li][0]) > 0.05).mean())
                 for li in range(L)]
        given = keys(self.selections if selections is None
                     else selections)
        tokens = np.asarray(answer if tokens is None else tokens)

        def error(z, p):
            return float(np.sqrt(np.mean((rows[p] - z[p]) ** 2))
                         / z[p].std())

        err_fwd = np.asarray([error(z_fwd, p) for p in range(N)])
        swaps, err, err_sel, passes, last = settle_routers(
            lambda sw: rows_pass(cache, sw, jnp.asarray(given)), error,
            (N, L, k, R), rc)
        z, _, sc, own = (np.asarray(a) for a in last)
        margin = np.asarray([(z[p].max() - z[p, tokens[p]]) / z[p].std()
                             for p in range(N)])
        sel_faults, members, gap = selection_faults(
            sc, own, given, cfg["topk"], rc["select_tie_gap"])
        return {"err": err, "err_fwd": err_fwd, "err_sel": err_sel,
                "margin": margin, "swaps": swaps, "passes": passes + 1,
                "sel_faults": sel_faults, "sel_members": members,
                "sel_gap": gap, "cache_err": cache_err,
                "cache_moved": moved}

    def faults(self, got):
        """What the configuration's limits make of a `compare`: {name of
        the limit: words}, empty where it is correct."""
        rc = self.env.config["reference"]
        bad = {}
        for name, value in (
                ("logit_err_sigmas", got["err"].max()),
                ("logit_err_forward_sigmas", got["err_fwd"].max()),
                ("logit_err_forward_mean_sigmas", got["err_fwd"].mean()),
                ("margin_sigmas", got["margin"].max()),
                ("cache_err_layer0", got["cache_err"][0]),
                ("cache_err_median", max(got["cache_err"][1:] or [0]))):
            if value > rc[name]:
                bad[name] = f"{value:.4f} over {rc[name]}"
        if got["sel_faults"]:
            bad["select_tie_gap"] = (
                f"{len(got['sel_faults'])} rows' selections are not the "
                f"reference's but for near-ties: {got['sel_faults'][:3]}")
        return bad

    def _against_reference(self, prompt, answer):
        """As `serve_frontend_afmoe`'s, and the selections and the
        cache."""
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
            self.log("the sentinel through the frontend "
                     + self.ref_faults["sentinel through the frontend"])
        swapped = {p: (c, round(float(got["err_sel"][p]), 4))
                   for p, c in enumerate(got["swaps"]) if c}
        self.log("reference, by position: logits' error / against the "
                 "reference's full forward / token's margin, in sigma: "
                 + " ".join(f"{e:.4f}/{e0:.4f}/{m:.3f}" for e, e0, m in
                            zip(got["err"], got["err_fwd"],
                                got["margin"])))
        rc = self.env.config["reference"]
        self.log(f"reference: the sentinel's {len(answer)} rows of "
                 f"{self.rows.shape[1]} logits lie within "
                 f"{self.ref_err:.4f} sigma (rms) of the float32 "
                 f"reference's over the engine's cache and selections, "
                 f"mean {got['err'].mean():.4f}, limit "
                 f"{rc['logit_err_sigmas']}; against the reference's "
                 f"full forward worst {got['err_fwd'].max():.4f} (limit "
                 f"{rc['logit_err_forward_sigmas']}) mean "
                 f"{got['err_fwd'].mean():.4f} (limit "
                 f"{rc['logit_err_forward_mean_sigmas']}); {got['passes']} "
                 f"passes over the rows; positions that took a router's "
                 f"near-tie's other answer ((layer, rank out, rank in)s,"
                 f" error before): {swapped or 'none'}")
        self.log(f"selections: of {self.selections.shape[1]} layers x "
                 f"{len(answer)} rows, at most {got['sel_members']} "
                 f"members of a row differ from the reference's over the "
                 f"same cache, the widest {got['sel_gap']:.4f} sigma of "
                 f"the row's scores from its k-th largest, limit "
                 f"{rc['select_tie_gap']}; faults: "
                 f"{got['sel_faults'][:3] or 'none'}")
        self.log("cache: the engine's K, V and indexer keys against the "
                 "reference's own, relative error of a row: layer 0 "
                 f"largest {got['cache_err'][0]:.4f} (limit "
                 f"{rc['cache_err_layer0']}), later layers' medians "
                 f"{[round(v, 4) for v in got['cache_err'][1:]]} (limit "
                 f"{rc['cache_err_median']}); share of K rows over 0.05 "
                 f"{[round(v, 3) for v in got['cache_moved']]}")
        return float((got["margin"] == 0).mean()), \
            float(np.max(got["margin"]))

    def check(self):
        checks = super().check()
        checks.pop("reference logits", None)
        for name, words in self.ref_faults.items():
            checks["reference " + name] = words
        return checks
