"""Fleet-wide request tracing + SLO plane tests (ISSUE 16).

Tracer unit semantics (bounds, monotone clamp, idempotent terminals,
failover reopen, eviction), the sliding-window quantile estimator vs
numpy.percentile, the SLO monitor's gauges + edge-triggered breach
callbacks, solo-engine end-to-end traces whose span-derived latencies
match the registry histograms EXACTLY, the overhead contract (tracing
ON adds no compiles and bounded wall-clock), the stitching edge cases
(failover restart, preempted migrant re-prefill, abandonment
mid-stream after a handoff), the profiler chrome/summary merge, and
the tools/trace_smoke.py CI contract.
"""
import asyncio
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForGeneration
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.serving import metrics as sm
from paddle_tpu.serving import slo, tracing
from paddle_tpu.serving.distributed import (InProcessTransport,
                                            ReplicaRouter)
from paddle_tpu.serving.engine import ServingEngine
from paddle_tpu.serving.frontend import ServingFrontend
from paddle_tpu.serving.slo import (SLOConfig, SLOMonitor,
                                    SlidingWindowQuantile)
from paddle_tpu.serving.tracing import TRACER, RequestTracer


@pytest.fixture(autouse=True)
def _trace_state():
    """Every test starts from a clean, DISABLED tracer and leaves it
    that way — tracing is opt-in for the rest of the suite."""
    tracing.disable()
    TRACER.reset()
    yield
    tracing.disable()
    TRACER.reset()


@pytest.fixture
def _pm_restore():
    """Restore profiler-metrics state for tests that enable it at a
    specific point (AFTER their warm compiles)."""
    was = pm._enabled
    yield
    pm.REGISTRY.reset()
    if not was:
        pm.disable()


def _model():
    paddle.seed(1234)
    m = GPTForGeneration(vocab_size=193, hidden_size=32, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128,
                         compute_dtype="float32")
    m.eval()
    return m


def _engine(m, role="mixed", **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", "float32")
    kw.setdefault("seed", 0)
    return ServingEngine(m, role=role, **kw)


def _prompt(n=9, seed=0):
    return np.random.RandomState(seed).randint(1, 193, n).tolist()


# --------------------------------------------------- tracer unit level


class TestRequestTracer:
    def test_lifecycle_and_derive(self):
        tracing.enable()
        clk = iter(float(i) for i in range(100))
        tr = RequestTracer(capacity=8, max_events=16,
                           clock=lambda: next(clk))
        tid = tr.mint("tenantA")
        tr.event(tid, "enqueued", replica="e0", ts=1.0)
        tr.event(tid, "admitted", replica="e0", ts=1.5)
        tr.event(tid, "first_token", replica="e0", ts=2.0)
        tr.event(tid, "decode_step", replica="e0", ts=2.25, gap=0.25)
        tr.finish(tid, "finished", replica="e0", ts=3.0)
        t = tr.get(tid)
        assert t.done and t.outcome == "finished"
        assert t.monotone()
        assert t.replicas == ["e0"]
        d = t.derive()
        assert d["ttft"] == pytest.approx(1.0)
        assert d["queue_wait"] == pytest.approx(0.5)
        assert d["inter_token"] == [0.25]
        assert tr.active() == []

    def test_unknown_id_gets_shell_trace(self):
        tracing.enable()
        tr = RequestTracer(capacity=8, max_events=16)
        tr.event("tr-ghost", "decode_step", replica="e1", ts=1.0,
                 tenant="t9")
        t = tr.get("tr-ghost")
        assert t is not None and t.tenant == "t9"
        assert len(tr.active()) == 1

    def test_monotone_clamp(self):
        tracing.enable()
        tr = RequestTracer(capacity=8, max_events=16)
        tid = tr.mint()
        tr.event(tid, "enqueued", ts=5.0)
        tr.event(tid, "admitted", ts=4.0)       # clock skew: clamped
        assert [e.ts for e in tr.get(tid).events] == [5.0, 5.0]
        assert tr.get(tid).monotone()

    def test_event_cap_drops_but_terminal_lands(self):
        tracing.enable()
        tr = RequestTracer(capacity=8, max_events=8)
        tid = tr.mint()
        for i in range(12):
            tr.event(tid, "decode_step", ts=float(i))
        t = tr.get(tid)
        assert len(t.events) == 8
        assert t.dropped_events == 4
        tr.finish(tid, "finished", ts=99.0)     # always lands
        assert t.events[-1].name == "finished"
        assert t.outcome == "finished"

    def test_finish_idempotent_first_wins(self):
        tracing.enable()
        tr = RequestTracer(capacity=8, max_events=16)
        tid = tr.mint()
        tr.finish(tid, "cancelled", ts=1.0)
        tr.finish(tid, "finished", ts=2.0)      # ignored
        assert tr.get(tid).outcome == "cancelled"
        assert len(tr.get(tid).events) == 1

    def test_reopen_on_redispatch(self):
        """Failover: the dying replica's cancel closes the trace; the
        router's re-dispatch REOPENS it so the survivor's outcome
        wins."""
        tracing.enable()
        tr = RequestTracer(capacity=8, max_events=16)
        tid = tr.mint()
        tr.event(tid, "enqueued", replica="e0", ts=1.0)
        tr.finish(tid, "cancelled", replica="e0", ts=2.0)
        tr.event(tid, "dispatched", replica="e1", ts=3.0)
        assert not tr.get(tid).done
        assert len(tr.active()) == 1
        tr.finish(tid, "finished", replica="e1", ts=4.0)
        t = tr.get(tid)
        assert t.outcome == "finished"
        assert t.replicas == ["e0", "e1"]

    def test_capacity_evicts_finished_first(self):
        tracing.enable()
        tr = RequestTracer(capacity=2, max_events=16)
        a, b = tr.mint(), tr.mint()
        tr.finish(a, "finished")
        c = tr.mint()                            # evicts a (finished)
        assert tr.get(a) is None
        assert tr.get(b) is not None and tr.get(c) is not None
        assert tr.dropped_traces == 1
        # all-open table: oldest open dropped, active count stays right
        d = tr.mint()
        assert tr.get(b) is None
        assert len(tr.active()) == len([x for x in (c, d)
                                        if tr.get(x)]) == 2

    def test_disabled_is_noop(self):
        tr = RequestTracer(capacity=8, max_events=16)
        tr.event("tr-x", "enqueued", ts=1.0)
        tr.finish("tr-x", "finished")
        assert tr.get("tr-x") is None
        assert tr.traces() == []

    def test_reset_clears(self):
        tracing.enable()
        tr = RequestTracer(capacity=8, max_events=16)
        tr.mint()
        tr.reset()
        assert tr.traces() == [] and tr.active() == []


# ------------------------------------------------------- SLO plane


class TestSlidingWindowQuantile:
    def test_matches_numpy_percentile(self):
        rng = np.random.RandomState(3)
        vals = rng.rand(64).tolist()
        w = SlidingWindowQuantile(window_s=100.0, max_samples=128)
        for i, v in enumerate(vals):
            w.observe(v, ts=float(i) * 0.1)
        now = 6.4
        for q in (0.5, 0.95, 0.99):
            assert w.quantile(q, now) == pytest.approx(
                np.percentile(vals, q * 100))

    def test_window_prunes_old_samples(self):
        w = SlidingWindowQuantile(window_s=10.0, max_samples=128)
        w.observe(100.0, ts=0.0)
        w.observe(1.0, ts=50.0)
        assert w.quantile(0.99, now=55.0) == pytest.approx(1.0)
        assert w.count(55.0) == 1
        assert w.quantile(0.5, now=1000.0) is None

    def test_cap_drops_oldest(self):
        w = SlidingWindowQuantile(window_s=1e9, max_samples=4)
        for i in range(10):
            w.observe(float(i), ts=float(i))
        assert w.dropped == 6 and w.total == 10
        assert w.quantile(0.0, now=10.0) == pytest.approx(6.0)


class TestSLOMonitor:
    def test_config_validation_and_merge(self):
        cfg = SLOConfig.from_dict(
            {"default": {"ttft_p95": 1.0},
             "tenants": {"vip": {"ttft_p95": 0.2}}})
        assert cfg.targets_for("vip")["ttft_p95"] == 0.2
        assert cfg.targets_for("other")["ttft_p95"] == 1.0
        with pytest.raises(ValueError, match="unknown SLOConfig"):
            SLOConfig.from_dict({"objectives": {}})

    def test_edge_triggered_breach_and_recovery(self):
        clk = [100.0]
        mon = SLOMonitor({"default": {"ttft_p95": 0.1},
                          "window_s": 20.0}, clock=lambda: clk[0])
        fired = []
        mon.on_breach(lambda *a: fired.append(a))
        mon.on_ttft("t", 0.05, 95.0)
        rep = mon.evaluate()
        assert rep["t"]["ttft_p95"]["ok"]
        assert fired == []
        mon.on_ttft("t", 5.0, 99.0)
        rep = mon.evaluate()
        assert not rep["t"]["ttft_p95"]["ok"]
        assert rep["t"]["ttft_p95"]["burn_rate"] > 1.0
        assert len(fired) == 1 and fired[0][0] == "t"
        mon.evaluate()                       # still burning: no re-fire
        assert len(fired) == 1
        clk[0] = 130.0                       # window slides past the spike
        mon.on_ttft("t", 0.05, 129.0)
        assert mon.evaluate()["t"]["ttft_p95"]["ok"]
        mon.on_ttft("t", 5.0, 129.5)         # re-armed: fires again
        mon.evaluate()
        assert len(fired) == 2

    def test_deadline_miss_rate(self):
        clk = [10.0]
        mon = SLOMonitor({"default": {"deadline_miss_rate": 0.25},
                          "window_s": 100.0}, clock=lambda: clk[0])
        for i in range(8):
            mon.on_outcome("t", "finished", i == 0, float(i))
        rep = mon.evaluate()
        r = rep["t"]["deadline_miss_rate"]
        assert r["value"] == pytest.approx(1 / 8) and r["ok"]
        for i in range(4):
            mon.on_outcome("t", "expired", True, 9.0)
        assert not mon.evaluate()["t"]["deadline_miss_rate"]["ok"]

    def test_gauges_and_breach_counter(self, _pm_restore):
        pm.REGISTRY.reset()
        pm.enable()
        mon = SLOMonitor({"default": {"ttft_p95": 0.1},
                          "window_s": 1e9}, clock=lambda: 10.0)
        mon.on_ttft("vip", 0.7, 5.0)
        mon.evaluate()
        g = dict(sm.SERVING_SLO_TTFT_P95.samples())
        assert g[("vip",)].value == pytest.approx(0.7)
        b = dict(sm.SERVING_SLO_BURN_RATE.samples())
        assert b[("vip", "ttft_p95")].value == pytest.approx(7.0)
        br = dict(sm.SERVING_SLO_BREACHES.samples())
        assert br[("vip", "ttft_p95")].value == 1

    def test_attach_enables_tracing_and_observes(self):
        mon = SLOMonitor({"default": {"ttft_p95": 10.0}})
        assert not tracing.enabled()
        with mon:
            assert tracing.enabled()
            TRACER._notify("on_ttft", "t", 0.5, 1.0)
        assert mon._ttft["t"].total == 1
        TRACER._notify("on_ttft", "t", 0.5, 2.0)   # detached: ignored
        assert mon._ttft["t"].total == 1


# --------------------------------------------------- engine end to end


class TestEngineTracing:
    def test_solo_engine_trace_matches_histograms(self, _pm_restore):
        m = _model()
        eng = _engine(m, name="solo_t")
        eng.generate_batch([[7, 7]], max_new_tokens=1)   # warm compile
        steps0 = eng.steps_run
        pm.REGISTRY.reset()
        pm.enable()
        tracing.enable()
        req = eng.submit(_prompt(), max_new_tokens=6)
        eng.run()
        assert req.state == "finished"

        traces = TRACER.traces()
        assert len(traces) == 1
        t = traces[0]
        assert t.trace_id == req.trace_id
        assert t.outcome == "finished" and t.monotone()
        names = [e.name for e in t.events]
        for needed in ("enqueued", "admitted", "prefill_chunk",
                       "first_token", "decode_step", "finished"):
            assert needed in names, names
        assert TRACER.active() == []
        assert t.replicas == ["solo_t"]

        # span-derived latencies == registry histograms, EXACTLY: the
        # hooks reuse the emit-time numbers the histograms observe
        d = t.derive()
        assert sm.SERVING_TTFT_SECONDS.count == 1
        assert sm.SERVING_TTFT_SECONDS.sum == pytest.approx(
            d["ttft"], abs=1e-9)
        assert sm.SERVING_INTER_TOKEN_SECONDS.count == len(
            d["inter_token"])
        assert sm.SERVING_INTER_TOKEN_SECONDS.sum == pytest.approx(
            sum(d["inter_token"]), abs=1e-9)
        assert sm.SERVING_TRACE_QUEUE_WAIT.count == 1
        assert sm.SERVING_TRACE_QUEUE_WAIT.sum == pytest.approx(
            d["queue_wait"], abs=1e-9)

        # flight recorder saw every traced step, with real token counts
        assert eng.flight.steps == eng.steps_run - steps0
        assert sum(r.get("prefill_tokens", 0)
                   for r in eng.flight.records) >= len(req.prompt)
        assert sum(r.get("decode_tokens", 0)
                   for r in eng.flight.records) > 0
        assert all(r.get("compile_cache_size") == 1
                   for r in eng.flight.records)

    def test_tracing_off_records_nothing(self):
        m = _model()
        eng = _engine(m)
        eng.submit(_prompt(), max_new_tokens=4)
        eng.run()
        assert TRACER.traces() == []
        assert eng.flight.steps == 0

    def test_overhead_contract(self):
        """Tracing ON must add zero compiles (autouse watchdog + cache
        probe) and bounded wall-clock on the CPU harness."""
        m = _model()
        eng = _engine(m)
        prompts = [_prompt(n, seed=n) for n in (5, 8, 11)]
        eng.generate_batch(prompts, max_new_tokens=8)     # warm

        def run_once():
            t0 = time.perf_counter()
            eng.generate_batch(prompts, max_new_tokens=8)
            return time.perf_counter() - t0

        off = min(run_once() for _ in range(2))
        compiles0 = eng.step_compile_count()
        tracing.enable()
        on = min(run_once() for _ in range(2))
        assert eng.step_compile_count() == compiles0
        assert TRACER.traces()                       # it did record
        # host-side dict appends vs multi-ms jitted steps: generous
        # bound absorbs CI noise while catching a hot-path regression
        assert on <= off * 2.0 + 0.05, (on, off)


# ------------------------------------------ host phases and work counters

ENGINE_FIELDS = ("ph_plan", "ph_pack", "ph_dispatch", "ph_wait",
                 "ph_emit", "ph_note")
FRONTEND_FIELDS = ("ph_hop_out", "ph_publish", "ph_admit", "ph_hop_in")


def _serve(eng, prompts, max_new_tokens=6):
    """The prompts through a `ServingFrontend`, all at once."""
    async def run():
        async with ServingFrontend(eng, max_pending=16) as fe:
            return await asyncio.gather(*[
                fe.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts])
    return asyncio.run(run())


def _brute_work(plan):
    """(kv_tokens_read, attn_pairs) counted key by key."""
    read = pairs = 0
    groups = [(pos, np.atleast_1d(tok).size)
              for _, tok, pos in plan.decode]
    groups += [(start, len(chunk)) for _, chunk, start, _ in plan.prefills]
    for start, n in groups:
        read += len({k for q in range(start, start + n)
                     for k in range(q + 1)})
        pairs += sum(1 for q in range(start, start + n)
                     for k in range(q + 1))
    return read, pairs


class TestHostPhases:
    @pytest.mark.parametrize("ticks", (1, 4))
    @pytest.mark.parametrize("behind", ("frontend", "solo"))
    def test_phases_tile_step_and_gap(self, ticks, behind):
        eng = _engine(_model(), ticks_per_dispatch=ticks)
        prompts = [_prompt(n, seed=n) for n in (5, 9, 13)]
        eng.generate_batch([[7, 7]], max_new_tokens=1)    # warm compile
        tracing.enable()
        if behind == "frontend":
            _serve(eng, prompts, max_new_tokens=8)
        else:
            eng.generate_batch(prompts, max_new_tokens=8)
        eng.flush_observability()
        recs = list(eng.flight.records)
        assert len(recs) == eng.flight.steps >= 4
        for r in recs:
            # one shape of record on both paths: the engine's phases
            # tile the step
            have = [f for f in ENGINE_FIELDS if f in r]
            assert set(ENGINE_FIELDS[:5]) <= set(have), r
            assert sum(r[f] for f in have) == pytest.approx(
                r["dur"], abs=2e-4)
            assert all(r[f] >= 0 for f in have)
        assert "gap_before" not in recs[0]
        for a, b in zip(recs, recs[1:]):
            assert b["gap_before"] == pytest.approx(
                b["ts"] - (a["ts"] + a["dur"]), abs=1e-9)
            assert b["gap_before"] >= 0
            if behind == "frontend":
                # the four frontend phases of the gap before the step
                # ride on its record, and fit inside that gap
                assert set(FRONTEND_FIELDS) <= set(b), b
                assert sum(b[f] for f in FRONTEND_FIELDS) <= \
                    b["gap_before"] + 2e-4
            else:
                assert not set(FRONTEND_FIELDS) & set(b)
        if ticks > 1:
            assert any(r["ticks"] > 1 for r in recs)
        # nothing is left open or untaken once the engine is idle
        assert eng.phases.name is None or behind == "solo"

    @pytest.mark.parametrize("ticks", (1, 4))
    def test_work_counters_match_brute_force(self, ticks):
        """Chunked prefill riding with decode: a short request decodes
        while a long prompt is fed in chunks of the token budget."""
        eng = _engine(_model(), token_budget=8, max_slots=3,
                      ticks_per_dispatch=ticks)
        eng.generate_batch([[7, 7]], max_new_tokens=1)
        plans = []
        real_plan = eng.scheduler.plan

        def plan(*args):    # (the engine's drain, with a step unread)
            p = real_plan(*args)
            if not p.empty:
                plans.append(p)
            return p
        eng.scheduler.plan = plan
        tracing.enable()
        eng.submit(_prompt(5, seed=1), max_new_tokens=12)
        eng.step()
        eng.submit(_prompt(27, seed=2), max_new_tokens=3)
        eng.run()
        eng.flush_observability()
        recs = list(eng.flight.records)
        assert len(recs) == len(plans) >= 5
        assert any(p.decode and p.prefills for p in plans)
        assert any(start > 0 for p in plans
                   for _, _, start, _ in p.prefills)
        for r, p in zip(recs, plans):
            read, pairs = _brute_work(p)
            if r.get("ticks", 1) == 1:
                assert (r["kv_tokens_read"], r["attn_pairs"]) == \
                    (read, pairs), (r, p)
            else:
                # later ticks of the device loop: one query a token
                assert not p.prefills
                extra = r["decode_tokens"] - len(p.decode)
                assert extra > 0
                assert r["kv_tokens_read"] - read == \
                    r["attn_pairs"] - pairs >= extra
        assert all(r["kv_tokens_read"] > 0 for r in recs)
        # the same contexts in blocks, each walked once by the kernel
        bs = eng.block_size
        heads = eng.kv.num_heads * eng.kv.num_layers
        for r, p in zip(recs, plans):
            assert r["kv_blocks_walked"] == r["kv_blocks_needed"] > 0
            # what the paged kernel computes for the same runs, by its
            # own tiles: never less than the pairs x heads it keeps
            assert r["attn_logits_useful"] == r["attn_pairs"] * heads
            assert r["attn_logits_issued"] >= r["attn_logits_useful"]
            if r.get("ticks", 1) == 1:
                ends = [pos + np.atleast_1d(tok).size
                        for _, tok, pos in p.decode]
                ends += [start + len(c) for _, c, start, _ in p.prefills]
                assert r["kv_blocks_needed"] == sum(
                    -(-e // bs) for e in ends)
        if ticks > 1:
            assert any(r["ticks"] > 1 for r in recs)
            # a slot that decodes c tokens from pos reads pos+1..pos+c
            r, p = next((r, p) for r, p in zip(recs, plans)
                        if r["ticks"] > 1 and len(p.decode) == 1)
            pos, c = p.decode[0][2], r["decode_tokens"]
            assert r["attn_pairs"] == sum(pos + j + 1 for j in range(c))

    def test_pool_counters_read_at_note_time(self):
        eng = _engine(_model(), token_budget=8)
        eng.generate_batch([[7, 7]], max_new_tokens=1)
        # the record is MADE later (after the next dispatch has
        # launched), of what `_step_record` read inside `engine.note`
        seen = []
        step_record = eng._step_record

        def spy(*args):
            seen.append((eng.kv.blocks_in_use,
                         eng.scheduler.preemption_count))
            return step_record(*args)
        eng._step_record = spy
        tracing.enable()
        eng.generate_batch([_prompt(n, seed=n) for n in (6, 11, 19)],
                           max_new_tokens=7)
        recs = list(eng.flight.records)
        assert len(recs) == len(seen) > 3
        assert [(r["kv_blocks_in_use"], r["preemptions"])
                for r in recs] == seen
        assert {r["kv_blocks_total"] for r in recs} == {eng.kv.num_blocks}
        assert max(r["kv_blocks_in_use"] for r in recs) > 0
        assert len({r["kv_blocks_in_use"] for r in recs}) > 1

    def test_off_builds_no_annotation_and_notes_nothing(self, monkeypatch):
        import jax
        built = []
        real = jax.profiler.TraceAnnotation

        class Counting(real):
            def __init__(self, *a, **kw):
                built.append(a[0])
                super().__init__(*a, **kw)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        eng = _engine(_model())
        prompts = [_prompt(n, seed=n) for n in (5, 8)]
        want = _serve(eng, prompts)
        compiles = eng.step_compile_count()
        assert built == [] and eng.flight.steps == 0
        assert eng.phases.take() == {} and eng.phases.name is None
        assert TRACER.traces() == []
        # on: the same tokens, the same one executable, and every
        # phase of the cycle gets its annotation
        tracing.enable()
        assert _serve(eng, prompts) == want
        assert eng.step_compile_count() == compiles == 1
        assert set(built) == set(tracing.HOST_PHASES)
        assert eng.flight.steps > 0 and eng.phases.name is None

    def test_annotations_land_on_the_profilers_clock(self, tmp_path):
        """Under a `jax.profiler` session every phase is an event of
        `/host:CPU`, the plane device ops share a clock with, and
        carries the cycle's step index."""
        import glob

        import jax
        from jax.profiler import ProfileData
        eng = _engine(_model())
        eng.generate_batch([[7, 7]], max_new_tokens=1)
        tracing.enable()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            first = eng.steps_run
            _serve(eng, [_prompt(n, seed=n) for n in (5, 9)])
        finally:
            jax.profiler.stop_trace()
        data = ProfileData.from_file(glob.glob(
            str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0])
        steps = {}
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in tracing.HOST_PHASES:
                        stats = dict(ev.stats)
                        assert "step" in stats, ev.name
                        steps.setdefault(ev.name, []).append(
                            int(stats["step"]))
        assert set(steps) == set(tracing.HOST_PHASES)
        ran = set(range(first, eng.steps_run))
        for name in tracing.HOST_PHASES[2:]:
            # every step's own phases carry its index
            assert ran <= set(steps[name]), (name, steps[name])
        assert set(steps["frontend.hop_in"]) >= ran

    def test_chrome_events_nest_phases_under_their_step(self):
        eng = _engine(_model(), name="nest_t")
        eng.generate_batch([[7, 7]], max_new_tokens=1)
        tracing.enable()
        _serve(eng, [_prompt(7, seed=3)])
        evs = [e for e in eng.flight.chrome_events()
               if e["tid"] == "engine:nest_t"]
        steps = [e for e in evs if e["name"].startswith("step[")]
        inner = [e for e in evs if e["name"].startswith("engine.")]
        outer = [e for e in evs if e["name"].startswith("frontend.")]
        assert len(steps) == eng.flight.steps
        assert len(inner) == 6 * len(steps)
        assert {e["name"] for e in inner + outer} == \
            set(tracing.HOST_PHASES)
        for e in inner:
            assert any(s["ts"] - 1 <= e["ts"] and e["ts"] + e["dur"]
                       <= s["ts"] + s["dur"] + 200 for s in steps), e
        for e in outer:
            # between steps, never inside one
            assert not any(s["ts"] + 1 < e["ts"] + e["dur"] / 2
                           < s["ts"] + s["dur"] - 1 for s in steps), e

    def test_stop_or_disable_mid_cycle_leaves_no_phase_open(self):
        eng = _engine(_model())
        eng.generate_batch([[7, 7]], max_new_tokens=1)
        tracing.enable()

        async def run():
            fe = ServingFrontend(eng, max_pending=4)
            await fe.start()
            task = asyncio.ensure_future(
                fe.submit(_prompt(9), max_new_tokens=40))
            while eng.flight.steps < 3:
                await asyncio.sleep(0.001)
            assert eng.phases.name is not None      # mid-cycle
            await fe.stop()                         # while busy
            with pytest.raises(Exception):
                await task
            assert eng.phases.name is None
            # tracing switched off between two cycles: the phase the
            # frontend had open is dropped, not charged to a later step
            fe = ServingFrontend(eng, max_pending=4)
            await fe.start()
            task = asyncio.ensure_future(
                fe.submit(_prompt(9), max_new_tokens=30))
            while eng.flight.steps < 6:
                await asyncio.sleep(0.001)
            tracing.disable()
            await task
            await fe.stop()
        asyncio.run(run())
        assert eng.phases.name is None and eng.phases.take() == {}

    def test_host_gaps_tool_names_idle_time_by_phase(self, tmp_path,
                                                     capsys):
        """tools/host_gaps.py on a trace made here (CPU: host events
        with an `hlo_op` stat stand in for device ops), and its split of
        a gap among the phases on a hand-made list."""
        import importlib.util
        import os

        import jax
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "host_gaps.py")
        spec = importlib.util.spec_from_file_location("host_gaps", path)
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        ms = 1_000_000
        host = [("engine.wait", 0, 10 * ms), ("engine.emit", 10 * ms, ms),
                ("frontend.hop_out", 11 * ms, 3 * ms),
                ("client_idle", 0, 100 * ms),
                ("engine.pack", 16 * ms, 2 * ms)]
        # a gap of 9..17 ms: 1 wait, 1 emit, 3 hop_out, 2 nobody's,
        # 1 pack; and one of 30..31 ms that no phase covers
        got = tool.split_by_phase(
            [(0.009, 0.008, "x"), (0.030, 0.001, "x")], host)
        assert got == pytest.approx({
            "engine.wait": 0.001, "engine.emit": 0.001,
            "frontend.hop_out": 0.003, "engine.pack": 0.001,
            "host_between_dispatches": 0.003})
        # the device planes run ahead of the host's by at least the
        # most a step program leads the dispatch that launches it
        device = {"/device:TPU:0": {
            "ops": [("fusion.1", 4 * ms, ms)],
            "modules": [("jit_serving_mixed_step(1)", 4 * ms, 60 * ms),
                        ("jit__threefry_split(2)", 1 * ms, 1000),
                        ("jit_serving_mixed_step(1)", 71 * ms, 60 * ms)]}}
        host = [("engine.dispatch", 6 * ms, ms),
                ("engine.dispatch", 72 * ms, ms)]
        assert tool.device_lead(device, host) == 2 * ms
        assert tool.device_lead(device, host[1:]) == 1 * ms
        assert tool.device_lead(device, [("engine.dispatch", ms, ms)]) == 0
        assert tool.later(device, 2 * ms)["/device:TPU:0"]["ops"] == [
            ("fusion.1", 6 * ms, ms)]

        eng = _engine(_model())
        eng.generate_batch([[7, 7]], max_new_tokens=1)
        tracing.enable()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _serve(eng, [_prompt(n, seed=n) for n in (5, 9)])
        finally:
            jax.profiler.stop_trace()
        assert tool.main([str(tmp_path), "--rehearse"]) == 0
        out = capsys.readouterr().out
        assert "CPU REHEARSAL" in out
        for name in tracing.HOST_PHASES:
            assert name in out
        assert "under a phase name" in out
        assert tool.main([]) == 2


class TestXplaneTables:
    def test_device_op_table_counts_calls_without_tensorflow(
            self, tmp_path, monkeypatch):
        import sys
        import types

        from paddle_tpu.profiler import xplane
        monkeypatch.setitem(sys.modules, "tensorflow", None)
        ev = lambda name, ns: types.SimpleNamespace(  # noqa: E731
            name=name, duration_ns=ns)
        data = types.SimpleNamespace(planes=[
            types.SimpleNamespace(name="/device:TPU:0", lines=[
                types.SimpleNamespace(name="XLA Ops", events=[
                    ev("%fusion.1 = f32[] fusion()", 3000),
                    ev("paged_ragged.3", 2000), ev("paged_ragged.3", 2500)]),
                types.SimpleNamespace(name="XLA Modules", events=[
                    ev("jit_step(1)", 9000)])]),
            types.SimpleNamespace(name="/host:CPU", lines=[
                types.SimpleNamespace(name="XLA Ops", events=[
                    ev("host", 7)])])])
        assert xplane.device_op_stats(data) == {
            "%fusion.1 = f32[] fusion()": [3000, 1],
            "paged_ragged.3": [4500, 2]}
        assert xplane.device_op_times(data).most_common(1) == [
            ("paged_ragged.3", 4500)]
        monkeypatch.setattr(xplane, "load_xplane", lambda d: data)
        assert xplane.device_op_table("x", n_steps=2) == [
            ("paged_ragged.3", 0.00225, 2),
            ("%fusion.1 = f32[] fusion()", 0.0015, 1)]

    def test_reads_a_real_trace_with_jax_alone(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from paddle_tpu import profiler
        from paddle_tpu.profiler import xplane
        with pytest.raises(FileNotFoundError):
            xplane.load_xplane(str(tmp_path))
        jax.profiler.start_trace(str(tmp_path))
        try:
            jnp.ones((8, 8)).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        data = xplane.load_xplane(str(tmp_path))
        assert any(p.name == "/host:CPU" for p in data.planes)
        # a CPU trace has no TPU plane: an empty table, not an error
        assert xplane.device_op_table(str(tmp_path)) == []
        assert "Device (TPU) Op Summary" in profiler.summary(
            trace_dir=str(tmp_path))


# ------------------------------------------------- stitching edge cases


class TestStitchingEdgeCases:
    def test_failover_keeps_one_trace(self, _pm_restore):
        """Kill a mixed replica mid-request: delivered-token
        suppression re-runs the request elsewhere, and the trace table
        must hold ONE trace with the failover event and both replicas
        — never a second trace for the re-dispatch."""
        m = _model()
        p = _prompt(9, seed=1)
        engines = [_engine(m, max_slots=3, prefix_caching=True,
                           name=f"fo{i}") for i in range(2)]
        for e in engines:
            e.generate_batch([[7, 7]], max_new_tokens=1)
        oracle = _engine(m).generate_batch([p], max_new_tokens=16)
        pm.REGISTRY.reset()
        pm.enable()
        tracing.enable()
        fes = [ServingFrontend(e, max_pending=16) for e in engines]

        async def run():
            router = ReplicaRouter(fes, probe_interval=0.02)
            async with router:
                got = []
                # kill the serving replica after the second delivered
                # token — deterministically mid-stream, engines warm
                async for tok in router.stream(p, max_new_tokens=16):
                    got.append(tok)
                    if len(got) == 2:
                        victim = max(range(2),
                                     key=router.queue_depth)

                        def boom():
                            raise RuntimeError("injected crash")
                        fes[victim].engine.step = boom
            return got, router

        out, router = asyncio.run(run())
        assert router.failovers >= 1
        assert [out] == oracle

        traces = TRACER.traces()
        assert len(traces) == 1, [t.as_dict() for t in traces]
        t = traces[0]
        assert t.outcome == "finished"
        assert t.monotone()
        names = [e.name for e in t.events]
        assert "failover" in names
        assert names.count("finished") == 1
        assert len(t.replicas) == 2          # both engines contributed
        assert TRACER.active() == []
        # the registry saw exactly one terminal for this request
        outcomes = dict(sm.SERVING_TRACES.samples())
        assert outcomes[("finished",)].value == 1

    def test_preempted_migrant_re_prefill_same_trace(self):
        """A migrated-in request that later gets preempted re-prefills
        from its transported history — decode_admission, import
        admission, preempted and re_prefill admission must all land on
        the ONE trace the source minted."""
        m = _model()
        tracing.enable()
        pre = _engine(m, role="prefill", name="pp0")
        dec = _engine(m, role="decode", name="pd0")
        req = pre.submit(_prompt(10, seed=2), max_new_tokens=8)
        for _ in range(100):
            if req.state in ("handoff", "finished"):
                break
            pre.step()
        assert req.state == "handoff"
        ticket = pre.extract_request(req)
        assert ticket.trace_id == req.trace_id
        t = InProcessTransport()
        t.send_ticket(0, 1, "k0", ticket)
        dreq = dec.submit_migrated(t.collect(1, "k0"))
        assert dreq.trace_id == req.trace_id
        dec.step()                           # admit (import) + decode
        assert dreq.slot >= 0
        victim = dec.scheduler._preempt_victim(set())
        assert victim is dreq
        dec.run()
        assert dreq.state == "finished"

        traces = TRACER.traces()
        assert len(traces) == 1
        tr = traces[0]
        assert tr.trace_id == req.trace_id
        assert tr.outcome == "finished" and tr.monotone()
        names = [e.name for e in tr.events]
        for needed in ("handoff", "handoff_export",
                       "migration_transport", "decode_admission",
                       "preempted"):
            assert needed in names, names
        kinds = [e.attrs.get("kind") for e in tr.events
                 if e.name == "admitted"]
        assert kinds == ["prefill", "import", "re_prefill"]
        assert tr.replicas == ["0->1", "pd0", "pp0"]
        assert TRACER.active() == []

    def test_abandoned_stream_closes_trace_after_handoff(self):
        """Abandoning the router stream after the handoff (the caller
        walks away mid-decode) must close the trace "cancelled", leave
        no orphan spans, drop the transport inbox and reclaim every
        slot/block on both replicas."""
        m = _model()
        engines = [_engine(m, role="prefill", max_slots=3, name="cp0"),
                   _engine(m, role="decode", max_slots=3, name="cd0")]
        for e in engines:
            e.generate_batch([[7, 7]], max_new_tokens=1)
        tracing.enable()
        fes = [ServingFrontend(e, max_pending=16) for e in engines]

        async def run():
            router = ReplicaRouter(fes, roles=["prefill", "decode"],
                                   probe_interval=0.02)
            async with router:
                got = []
                async for tok in router.stream(_prompt(8, seed=3),
                                               max_new_tokens=30):
                    got.append(tok)
                    if len(got) == 2:        # post-handoff: walk away
                        break
                await asyncio.sleep(0.15)    # cancellation lands
            return got, router

        got, router = asyncio.run(run())
        assert len(got) == 2
        traces = TRACER.traces()
        assert len(traces) == 1
        tr = traces[0]
        assert tr.outcome == "cancelled"
        names = [e.name for e in tr.events]
        assert "handoff_export" in names
        assert "migration_transport" in names
        assert TRACER.active() == []
        assert router.transport._inbox == {}
        for e in engines:
            assert e.scheduler.num_active == 0
            assert e.kv.blocks_in_use == 0


# ------------------------------------------- profiler merge + smoke


class TestProfilerMerge:
    def test_chrome_source_and_summary_sections(self):
        tracing.enable()
        tid = TRACER.mint("t0")
        TRACER.event(tid, "enqueued", replica="e0", ts=1.0)
        TRACER.event(tid, "admitted", replica="e0", ts=1.5)
        TRACER.event(tid, "first_token", replica="e0", ts=2.0)
        TRACER.finish(tid, "finished", replica="e0", ts=3.0)
        rec = tracing.StepFlightRecorder("e0", "mixed", maxlen=16)
        tracing.register_flight_recorder(rec)
        rec.note(ts=1.0, dur=0.01, prefill_tokens=4, decode_tokens=2)

        from paddle_tpu import profiler
        evs = profiler._extra_chrome_events()
        tids = {e["tid"] for e in evs}
        assert f"trace:{tid}" in tids and "engine:e0" in tids
        slices = [e for e in evs if e.get("ph") == "X"
                  and e["tid"] == f"trace:{tid}"]
        assert {e["name"].split("[")[0] for e in slices} == {
            "queued", "prefill", "decode"}

        text = profiler.summary()
        assert "request traces" in text
        assert "flight recorders" in text
        assert "finished" in text

    def test_chrome_export_file_merges_traces(self, tmp_path):
        import json

        tracing.enable()
        tid = TRACER.mint()
        TRACER.event(tid, "enqueued", ts=1.0)
        TRACER.finish(tid, "finished", ts=2.0)
        from paddle_tpu import profiler
        prof = profiler.Profiler(
            timer_only=True,
            on_trace_ready=profiler.export_chrome_tracing(
                str(tmp_path)))
        prof.start()
        prof.stop()
        files = list(tmp_path.glob("*.json"))
        assert len(files) == 1
        data = json.loads(files[0].read_text())
        assert any(e.get("tid") == f"trace:{tid}"
                   for e in data["traceEvents"])


def test_trace_smoke_tool(capsys):
    """tools/trace_smoke.py is the observability CI contract: one
    stitched trace per request across a forced-migration fleet, span/
    histogram agreement, zero orphans after drain, an engineered SLO
    breach, and the full serving metric contract under sanitize()."""
    import importlib.util
    import os

    pm.REGISTRY.reset()
    was = pm._enabled
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_smoke.py")
    spec = importlib.util.spec_from_file_location("trace_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        rc = mod.main()
        out = capsys.readouterr().out
        assert rc == 0
        from paddle_tpu.serving.metrics import CONTRACT_METRICS
        for name in CONTRACT_METRICS:
            assert name in out
        assert "trace smoke OK" in out
    finally:
        pm.REGISTRY.reset()
        if not was:
            pm.disable()
        tracing.disable()
        TRACER.reset()
