"""Driver for serving cells: `inference.create_serving_frontend` ->
`ServingFrontend` -> `ServingEngine` over `GPTForGeneration`, loaded by
one process through `ServingFrontend.stream()`.

Latencies are taken on the CLIENT side of `stream()` with the host's
monotonic clock (the clock the engine's spans use, so the traced run
can lay one over the other). The engine's own spans and flight records
are read only by the per-layer readers, in the traced run.
"""
from __future__ import annotations

import asyncio
import contextlib
import math
import shutil
import time
import types

from harness import kernels, stats, trace_reduce
from harness.files import load_module
from harness.traffic import RequestSource

CLIENT_IDLE = "client_idle"


class Record:
    __slots__ = ("req", "start", "submitted", "times", "tokens", "error",
                 "late")

    def __init__(self, req, start, submitted):
        self.req = req
        self.start = start            # when it was due (= submitted in
        self.submitted = submitted    # a closed loop)
        self.times = []
        self.tokens = []
        self.error = None
        self.late = submitted - start


class Driver:
    def __init__(self, env):
        self.env = env
        self.log = env.log
        self.loop = asyncio.new_event_loop()
        self.records = []
        self.in_flight = 0
        self._idle_note = None

    # ------------------------------------------------------------ set-up
    def setup(self):
        import paddle_tpu as paddle
        from paddle_tpu import inference
        from paddle_tpu.models.gpt import GPTForGeneration

        env, cfg = self.env, self.env.config
        model_kw, engine_kw = dict(cfg["model"]), dict(cfg["engine"])
        t0 = time.monotonic()
        paddle.seed(env.seed)          # the weights come from --seed
        model = GPTForGeneration(**model_kw)
        model.eval()
        t1 = time.monotonic()
        sampling = engine_kw.pop("sampling", None)
        icfg = inference.Config().enable_continuous_batching(
            sampling=sampling, **engine_kw)
        self.frontend = inference.create_serving_frontend(
            icfg, model, seed=env.seed % (2 ** 31 - 1))
        self.engine = self.frontend.engine
        t2 = time.monotonic()
        self.model = model
        self.vocab = int(model_kw["vocab_size"])
        self.source = RequestSource(
            env.traffic, self.vocab,
            min(int(model_kw["max_position_embeddings"]),
                int(engine_kw.get("max_seq_len", 1 << 30))), env.seed)
        e = self.engine
        self.log(f"model built in {t1 - t0:.1f} s, engine in "
                 f"{t2 - t1:.1f} s: {e.kv.max_slots} slots, block "
                 f"{e.block_size}, token budget {e.token_budget}, "
                 f"{e.kv.num_blocks} blocks, ticks/dispatch "
                 f"{e.ticks_per_dispatch}, sampling "
                 f"{e.sampling.strategy}")
        traced = e._step_fn._jitted.trace(*e.example_step_args())
        self.kernels_missing, found = kernels.check_step(
            traced, cfg["kernels"], env.rehearse)
        self.log(f"mixed step kernels: {found} "
                 f"({time.monotonic() - t2:.1f} s to trace and lower)")

    def warm(self):
        """The one compiled shape: the sentinel, served alone."""
        t0 = time.monotonic()
        self.loop.run_until_complete(self.frontend.start())
        self.sentinel = self.source.sentinel()
        rec = self.loop.run_until_complete(self._serve(self.sentinel))
        self.alone = list(rec.tokens)
        self.records.clear()
        t1 = time.monotonic()
        self.ref_match, self.ref_margin = self._against_reference(
            self.sentinel.prompt, self.alone)
        self.log(f"reference: the sentinel's {len(self.alone)} greedy "
                 f"tokens are the float32 reference's largest logit in "
                 f"{self.ref_match:.4f} of positions; widest margin "
                 f"{self.ref_margin:.4f} sigma of the position's logits "
                 f"({time.monotonic() - t1:.1f} s)")
        self.log(f"warm-up: sentinel ({len(self.sentinel.prompt)} -> "
                 f"{len(self.alone)} tokens) alone in "
                 f"{time.monotonic() - t0:.1f} s, compile or cache load "
                 f"included; mixed-step compiles "
                 f"{self.engine.step_compile_count()}")

    def _against_reference(self, prompt, answer):
        """The engine's greedy tokens against the plain float32
        reference (`configs/<config>_reference.py`), teacher-forced: the
        reference scores prompt + answer in one pass, and at each
        position the engine's next token must be the reference's
        largest logit, or within a margin of it — random weights put
        the top logits within rounding of each other, so the margin is
        counted in standard deviations of that position's logits.
        Returns (share of exact matches, widest margin in sigmas)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        ref = load_module("configs", self.env.config_name + "_reference")
        m = self.model
        arrays = [t._data for t in m._gen_tensors()]
        we, pe, dec, lnw, lnb, head = m._split_arrays(arrays)
        rename = {"ln_s": "ln1_w", "ln_b": "ln1_b", "ffn_ln_s": "ln2_w",
                  "ffn_ln_b": "ln2_b"}
        w = {rename.get(n, n): a for n, a in zip(m._dec_names, dec)}
        w.update(tok_emb=we, pos_emb=pe, lnf_w=lnw, lnf_b=lnb, head=head)
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        ids = jnp.asarray(list(prompt) + list(answer[:-1]), jnp.int32)
        heads = int(self.env.config["model"]["num_attention_heads"])
        z = np.asarray(jax.jit(ref.logits, static_argnums=2)(
            w, ids, heads))[len(prompt) - 1:]
        got = z[np.arange(len(answer)), np.asarray(answer)]
        margins = (z.max(-1) - got) / z.std(-1)
        return float((margins == 0).mean()), float(margins.max())

    # ------------------------------------------------------------- load
    def _note_in_flight(self, delta):
        """`client_idle` spans the time in which no request is in
        flight, in the profiler's trace (loop thread only)."""
        self.in_flight += delta
        if self.in_flight == 0 and self._idle_note is None:
            import jax
            self._idle_note = jax.profiler.TraceAnnotation(CLIENT_IDLE)
            self._idle_note.__enter__()
        elif self.in_flight > 0 and self._idle_note is not None:
            self._idle_note.__exit__(None, None, None)
            self._idle_note = None

    async def _serve(self, req, due=None):
        clock = time.monotonic
        now = clock()
        rec = Record(req, now if due is None else due, now)
        self.records.append(rec)
        self._note_in_flight(+1)
        try:
            async for tok in self.frontend.stream(
                    req.prompt, max_new_tokens=req.max_new_tokens):
                rec.times.append(clock())
                rec.tokens.append(int(tok))
        except Exception as e:   # noqa: BLE001 — a failed request is data
            rec.error = repr(e)
        finally:
            self._note_in_flight(-1)
        return rec

    async def _closed_loop(self, stop):
        async def client():
            while not stop.is_set():
                if self._inject:
                    req = self._inject.pop()
                else:
                    req = self.source.request(self._next)
                    self._next += 1
                await self._serve(req)
        await asyncio.gather(*[client() for _ in range(
            int(self.env.traffic["clients"]))])

    async def _poisson(self, stop):
        t0, tasks = time.monotonic(), []
        while not stop.is_set():
            if self._inject:
                req, due = self._inject.pop(), time.monotonic()
            else:
                req = self.source.request(self._next)
                self._next += 1
                due = t0 + req.due
                delay = due - time.monotonic()
                if delay > 0:
                    with contextlib.suppress(asyncio.TimeoutError):
                        await asyncio.wait_for(stop.wait(), delay)
                    if stop.is_set():
                        break
            tasks.append(asyncio.ensure_future(self._serve(req, due)))
        await asyncio.gather(*tasks)

    async def _occupancy(self, stop, every=0.05):
        """The KV pool's blocks in use, read every 50 ms of the window
        from the loop's side (an int the engine keeps; nothing is
        hooked): a pool the traffic does not fill is padding, and what
        it costs a step is the cost of padding."""
        kv = self.engine.kv
        while not stop.is_set():
            self.occupancy.append(int(kv.blocks_in_use))
            await asyncio.sleep(every)

    async def _profile(self, delay, length):
        """The profiler on for a short slice mid-window; start and stop
        run off the loop so that the clients keep being served."""
        import jax
        loop = asyncio.get_running_loop()
        await asyncio.sleep(delay)
        shutil.rmtree(self.env.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(
                self.env.trace_dir, profiler_options=opts))
        self.slice = [time.monotonic(), None]
        await asyncio.sleep(length)
        self.slice[1] = time.monotonic()
        await loop.run_in_executor(None, jax.profiler.stop_trace)

    async def _run(self, seconds, trace):
        from paddle_tpu.serving import tracing
        tr = self.env.traffic
        ramp = float(tr.get("ramp_seconds", 0))
        stop = asyncio.Event()
        self._next, self._inject, self.slice = 0, [], None
        self.occupancy = []
        if trace:
            tracing.TRACER.reset()
            tracing.enable()
        load = asyncio.ensure_future(
            {"closed_loop": self._closed_loop,
             "poisson": self._poisson}[tr["generator"]](stop))
        # the ramp fills the slots; it is warm-up, and counts as set-up
        await asyncio.sleep(ramp)
        w0 = time.monotonic()
        c0, s0 = self.env.compiles.total, self.engine.steps_run
        self.preempted = -self.engine.scheduler.preemption_count
        watch = asyncio.ensure_future(self._occupancy(stop))
        prof = None
        if trace:
            length = float(tr.get("profile_slice_seconds", 3))
            prof = asyncio.ensure_future(self._profile(
                max(0.0, 0.4 * seconds - length / 2), length))
        await asyncio.sleep(seconds / 2)
        self._inject.append(self.sentinel)       # once, in company
        await asyncio.sleep(max(0.0, w0 + seconds - time.monotonic()))
        w1 = time.monotonic()
        c1, s1 = self.env.compiles.total, self.engine.steps_run
        self.preempted += self.engine.scheduler.preemption_count
        stop.set()
        await watch
        if prof is not None:
            await prof
        await load                               # the drain
        if trace:
            tracing.disable()
        return w0, w1, c1 - c0, s1 - s0

    def run(self, seconds, trace):
        w0, w1, compiles, steps = self.loop.run_until_complete(
            self._run(seconds, trace))
        self.compiles_in_window = compiles
        recs = self.records
        window = w1 - w0
        in_win = [r for r in recs if w0 <= r.start < w1]
        tokens = sum(1 for r in recs for t in r.times if w0 <= t < w1)
        ttft = [(r.times[0] - r.start) * 1e3
                if r.times and not r.error else math.inf for r in in_win]
        gaps = [(b - a) * 1e3 for r in recs
                for a, b in zip(r.times, r.times[1:]) if w0 <= b < w1]
        late = [r.late * 1e3 for r in in_win]
        self.failed = [r for r in recs if r.error
                       or len(r.tokens) != r.req.max_new_tokens
                       or not all(0 <= t < self.vocab for t in r.tokens)]
        company = [r for r in recs if r.req.sentinel]
        self.match = None
        if company and company[0].tokens:
            got = company[0].tokens
            self.match = sum(a == b for a, b in zip(got, self.alone)) \
                / max(len(self.alone), 1)
        e2e = {"serve_tokens_per_s": tokens / window,
               "ttft_p90_ms": stats.percentile(ttft, 90),
               "itl_p95_ms": stats.percentile(gaps, 95)}
        hist = self.source.histogram(self._next)
        self.log(f"window {window:.3f} s: {len(in_win)} requests "
                 f"submitted ({len(in_win) / window:.2f}/s), {tokens} "
                 f"tokens out, {steps} engine steps "
                 f"({window / max(steps, 1) * 1e3:.2f} ms/step wall), "
                 f"{len(recs)} requests in all, {len(self.failed)} "
                 f"failed; lengths (min, median, max) {hist}")
        self.log(f"ttft ms p50 {stats.percentile(ttft, 50):.1f} p90 "
                 f"{e2e['ttft_p90_ms']:.1f} max {max(ttft):.1f} "
                 f"(n={len(ttft)}); itl ms p50 "
                 f"{stats.percentile(gaps, 50):.2f} p95 "
                 f"{e2e['itl_p95_ms']:.2f} max {max(gaps):.1f} "
                 f"(n={len(gaps)}); generator lateness ms p50 "
                 f"{stats.percentile(late, 50):.3f} max {max(late):.3f}")
        occ, pool = self.occupancy, self.engine.kv.num_blocks
        self.log(f"kv pool: {pool} blocks of {self.engine.block_size} "
                 f"tokens; in use over the window mean "
                 f"{sum(occ) / len(occ):.0f} ({sum(occ) / len(occ) / pool:.1%}"
                 f"), peak {max(occ)} ({max(occ) / pool:.1%}), least "
                 f"{min(occ)} (n={len(occ)} readings); {self.preempted} "
                 f"preemptions in the window")
        self.log(f"sentinel in company matches its answer alone in "
                 f"{self.match if self.match is None else round(self.match, 4)}"
                 " of its tokens")
        out = {"window_start": w0, "end_to_end": e2e,
               "attempted": len(recs), "failed": len(self.failed),
               "compiles_in_window": compiles}
        if trace:
            out["ctx"] = self._context(w0, w1, steps)
        return out

    def _context(self, w0, w1, steps):
        """What the per-layer readers see: the reduced device trace,
        the request spans and flight records of the window, counters."""
        from paddle_tpu.serving import tracing
        env = self.env
        device, host = trace_reduce.read_profile(
            trace_reduce.find_xplane(env.trace_dir), env.rehearse)
        trace = trace_reduce.reduce_events(
            device, host, labels=(CLIENT_IDLE,))
        if not env.keep_trace:
            shutil.rmtree(env.trace_dir, ignore_errors=True)
        traces = tracing.TRACER.traces()
        spans = []
        for t in traces:
            enq = t.first("enqueued")
            if enq is not None and w0 <= enq.ts < w1:
                spans.append(t.derive())
        flight = [r for r in self.engine.flight.records
                  if w0 <= r.get("ts", 0.0) < w1]
        self.dropped = {
            "tracer.dropped_traces": tracing.TRACER.dropped_traces,
            "trace.dropped_events": sum(t.dropped_events
                                        for t in traces),
            "flight.dropped": self.engine.flight.dropped}
        self.log(f"traced: {len(spans)} request spans and {len(flight)} "
                 f"flight records in the window; dropped {self.dropped}; "
                 f"profiled slice {trace.window_s:.3f} s, device busy "
                 f"{trace.busy_s:.3f} s, "
                 f"{trace.calls_of('', 'modules'):.0f} programs run")
        return types.SimpleNamespace(
            trace=trace, spans=spans, flight=flight, steps=None,
            counters={"engine_steps": steps}, config=env.config,
            traffic=env.traffic, peaks=env.peaks, log=self.log)

    # ------------------------------------------------------------ checks
    def check(self):
        rec = self.loop.run_until_complete(self._serve(self.sentinel))
        blocks = self.engine.kv.blocks_in_use
        self.loop.run_until_complete(self.frontend.stop())
        self.loop.close()
        bad = self.failed[:3]
        checks = {
            "compiles in the window": self.compiles_in_window
            and f"{self.compiles_in_window} executables were built or "
                "loaded inside the measured window",
            "mixed step compiles": self.engine.step_compile_count() != 1
            and f"{self.engine.step_compile_count()}, wanted 1",
            "kernels": self.kernels_missing
            and f"{self.kernels_missing} are not in the mixed step: an "
                "XLA fallback ran in their place",
            "requests": bad and "; ".join(
                f"request {r.req.index} ({len(r.req.prompt)} -> "
                f"{r.req.max_new_tokens}): {r.error or 'got'} "
                f"{len(r.tokens)} tokens" for r in bad),
            "sentinel": (rec.error or rec.tokens != self.alone)
            and f"alone after the drain {rec.tokens} ({rec.error}), "
                f"alone in warm-up {self.alone}",
            "sentinel in company": self.match != 1.0
            and f"mid-window, among the other requests, the sentinel "
                f"matched its answer alone in {self.match} of its tokens",
            "reference": self.ref_margin > self.env.config[
                "reference"]["margin_sigmas"]
            and f"a greedy token of the sentinel lies {self.ref_margin} "
                "sigma under the float32 reference's largest logit",
            "kv blocks": blocks and f"{blocks} blocks in use after the "
                                    "drain",
        }
        dropped = getattr(self, "dropped", {})
        checks["tracing dropped"] = any(dropped.values()) and str(dropped)
        return {k: v or None for k, v in checks.items()}
