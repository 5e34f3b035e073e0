"""Disaggregated prefill/decode serving tests (ISSUE 13).

Engine-level handoff + live-migration parity against a monolithic
engine (the bit-equal greedy contract, incl. int8 KV, prefix caching
and speculation on the decode role), the scheduler's ticket admission,
the shadow-radix `on_migrate` regression (satellite 2), the
router-orchestrated pipeline (handoff, shed, failover, auto-balance),
the Config round-trip, and the tools/disagg_smoke.py CI contract.
"""
import asyncio

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForGeneration
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.serving.distributed import (InProcessTransport,
                                            ReplicaRouter,
                                            ShadowRadixIndex)
from paddle_tpu.serving.engine import ServingEngine
from paddle_tpu.serving.frontend import RequestMigrated, ServingFrontend


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


def _prompts(n=3, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 193, int(k)).tolist()
            for k in rng.randint(5, 20, n)]


def _handoff_all(pre, reqs, max_steps=100):
    for _ in range(max_steps):
        if all(r.state in ("handoff", "finished") for r in reqs):
            return
        pre.step()
    raise AssertionError([r.state for r in reqs])


def _drain_check(*engines):
    for eng in engines:
        assert eng.scheduler.num_active == 0
        if eng.prefix_cache is not None:
            eng.prefix_cache.evict_all()
        assert eng.kv.blocks_in_use == 0
        assert eng.kv.allocator.invariant_ok


# ------------------------------------------------------- engine level


class TestEngineHandoff:
    @pytest.mark.parametrize("kv_dtype,draft_k,prefix", [
        (None, 0, False),
        ("int8", 2, True),       # the acceptance matrix: quantized KV,
    ])                           # prefix sharing, spec on the decode role
    def test_handoff_parity_vs_monolithic(self, kv_dtype, draft_k,
                                          prefix):
        m = _model()
        prompts = _prompts()
        mono = _engine(m, kv_dtype=kv_dtype, draft_k=draft_k,
                       prefix_caching=prefix)
        oracle = mono.generate_batch(prompts, max_new_tokens=10)

        pre = _engine(m, role="prefill", kv_dtype=kv_dtype,
                      prefix_caching=prefix)
        dec = _engine(m, role="decode", kv_dtype=kv_dtype,
                      draft_k=draft_k, prefix_caching=prefix)
        reqs = [pre.submit(p, max_new_tokens=10) for p in prompts]
        _handoff_all(pre, reqs)
        t = InProcessTransport()
        dreqs = []
        for i, r in enumerate(reqs):
            first = list(r.output)
            ticket = pre.extract_request(r)
            assert r.state == "migrated"
            assert ticket.output == first       # first token rides along
            assert ticket.slot_len == len(r.prompt)
            t.send_ticket(0, 1, f"k{i}", ticket)
            dreqs.append(dec.submit_migrated(t.collect(1, f"k{i}")))
        assert pre.scheduler.num_active == 0    # slots freed at extract
        dec.run()
        assert [list(r.output) for r in dreqs] == oracle
        _drain_check(pre, dec)

    def test_prefill_role_decode_budget_defaults(self):
        m = _model()
        pre = _engine(m, role="prefill")
        dec = _engine(m, role="decode")
        mixed = _engine(m)
        assert pre.token_budget == mixed.token_budget
        assert dec.token_budget < mixed.token_budget
        # still room to re-prefill a preempted migrant every step
        assert dec.token_budget > dec.kv.max_slots

    def test_request_finishing_at_first_token_never_migrates(self):
        m = _model()
        pre = _engine(m, role="prefill")
        req = pre.submit(_prompts()[0], max_new_tokens=1)
        while not req.done:
            pre.step()
        assert req.state == "finished"
        _drain_check(pre)

    def test_shed_mid_stream_parity(self):
        m = _model()
        p = _prompts()[0]
        mono = _engine(m)
        oracle = mono.generate_batch([p], max_new_tokens=20)[0]
        pre = _engine(m, role="prefill")
        a = _engine(m, role="decode")
        b = _engine(m, role="decode")
        t = InProcessTransport()
        r = pre.submit(p, max_new_tokens=20)
        _handoff_all(pre, [r])
        t.send_ticket(0, "a", "h", pre.extract_request(r))
        ra = a.submit_migrated(t.collect("a", "h"))
        while len(ra.output) < 5 and not ra.done:
            a.step()
        assert not ra.done
        tk = a.extract_request(ra)          # live shed, mid-decode
        assert tk.slot_len == len(p) + len(ra.output) - 1
        t.send_ticket("a", "b", "s", tk)
        rb = b.submit_migrated(t.collect("b", "s"))
        b.run()
        assert list(rb.output) == oracle
        _drain_check(pre, a, b)

    def test_ticket_waits_for_blocks_then_admits(self):
        """A migrated ticket that can't get blocks yet stays queued at
        the head and admits once the pool frees — never a partial
        import, never a corrupted ledger."""
        m = _model()
        pre = _engine(m, role="prefill")
        # decode pool with barely enough blocks for ONE request
        dec = _engine(m, role="decode", max_slots=2, num_blocks=8)
        p = [5] * 17                        # 5 blocks once decoding
        mono = _engine(m)
        oracle = mono.generate_batch([p, p[:9]], max_new_tokens=6)
        t = InProcessTransport()
        r1 = pre.submit(p, max_new_tokens=6)
        r2 = pre.submit(p[:9], max_new_tokens=6)
        _handoff_all(pre, [r1, r2])
        t.send_ticket(0, 1, "a", pre.extract_request(r1))
        t.send_ticket(0, 1, "b", pre.extract_request(r2))
        d1 = dec.submit_migrated(t.collect(1, "a"))
        d2 = dec.submit_migrated(t.collect(1, "b"))
        dec.step()
        # d2 jumped the queue (appendleft) and fits; d1 (5 blocks)
        # must wait for the pool
        assert d2.state == "decode"
        assert d1.state == "queued"
        assert dec.kv.allocator.invariant_ok
        dec.run()
        assert [list(d1.output), list(d2.output)] == oracle
        _drain_check(pre, dec)

    def test_migrated_request_survives_preemption(self):
        """A migrated-in request that later gets preempted re-prefills
        from prompt+output like any victim — outputs unchanged."""
        m = _model()
        p = _prompts()[0]
        mono = _engine(m)
        oracle = mono.generate_batch([p], max_new_tokens=12)[0]
        pre = _engine(m, role="prefill")
        dec = _engine(m, role="decode")
        t = InProcessTransport()
        r = pre.submit(p, max_new_tokens=12)
        _handoff_all(pre, [r])
        t.send_ticket(0, 1, "k", pre.extract_request(r))
        dr = dec.submit_migrated(t.collect(1, "k"))
        for _ in range(3):
            dec.step()
        assert dr.state == "decode" and dr.ticket is None
        # force a preemption of the migrant (as the plan does it: the
        # step in flight is read back first, a victim carries every
        # token it was given)
        dec.drain()
        dec.scheduler._preempt_victim(set())
        assert dr.state == "queued" and dr.slot == -1
        dec.run()
        assert list(dr.output) == oracle
        _drain_check(pre, dec)


# ------------------------------------------------ shadow index movement


class TestShadowOnMigrate:
    def test_entries_move_with_the_request(self):
        """Satellite 2 regression: post-migration affinity must steer
        at the KV's new home, not the stale source copy."""
        idx = ShadowRadixIndex(block_size=4)
        seq = list(range(12))
        idx.insert("a", seq)
        assert idx.match("a", seq) == 12
        idx.on_migrate("a", "b", seq)
        assert idx.match("a", seq) == 0
        assert idx.match("b", seq) == 12
        assert idx.size("a") == 0
        assert idx.size("b") == 3

    def test_shared_family_head_survives_removal(self):
        """Removing a migrated request's path keeps prefixes other
        requests still extend — only the unique tail goes."""
        idx = ShadowRadixIndex(block_size=4)
        head = list(range(8))
        a_tail = head + [101, 102, 103, 104]
        b_tail = head + [201, 202, 203, 204]
        idx.insert("r", a_tail)
        idx.insert("r", b_tail)
        removed = idx.remove("r", a_tail)
        assert removed == 1                   # just a's unique leaf
        assert idx.match("r", a_tail) == 8    # head still matches
        assert idx.match("r", b_tail) == 12   # sibling untouched

    def test_remove_unknown_replica_or_path_is_noop(self):
        idx = ShadowRadixIndex(block_size=4)
        assert idx.remove("ghost", [1, 2, 3, 4]) == 0
        idx.insert("r", [1, 2, 3, 4])
        assert idx.remove("r", [9, 9, 9, 9]) == 0
        assert idx.match("r", [1, 2, 3, 4]) == 4

    def test_eviction_heap_consistent_after_removal(self):
        idx = ShadowRadixIndex(block_size=1, capacity_blocks=4)
        for i in range(4):
            idx.insert("r", [10 + i])
        idx.remove("r", [10])
        idx.insert("r", [50])                 # within cap again
        assert idx.size("r") == 4
        for i in range(1, 4):
            assert idx.match("r", [10 + i]) == 1
        assert idx.match("r", [50]) == 1


# --------------------------------------------------------- router E2E


def _fleet(m, n_decode=2, migration=None, **dec_kw):
    pre = _engine(m, role="prefill", max_slots=3, prefix_caching=True)
    decs = [_engine(m, role="decode", max_slots=3, **dec_kw)
            for _ in range(n_decode)]
    fes = [ServingFrontend(e, max_pending=16) for e in [pre] + decs]
    return ReplicaRouter(
        fes, roles=["prefill"] + ["decode"] * n_decode,
        probe_interval=0.02, migration=migration), fes


class TestRouterDisagg:
    def test_disagg_outputs_match_monolithic(self):
        m = _model()
        prompts = _prompts(6, seed=1)
        mono = _engine(m)
        oracle = mono.generate_batch(prompts, max_new_tokens=10)
        router, fes = _fleet(m)

        async def run():
            async with router:
                return await asyncio.gather(*[
                    router.submit(p, max_new_tokens=10)
                    for p in prompts])

        outs = asyncio.run(run())
        assert outs == oracle
        st = router.stats()
        assert st["migrations"]["handoff"] == len(prompts)
        assert st["role_dispatches"]["prefill"] == len(prompts)
        assert st["role_dispatches"]["decode"] >= len(prompts)
        assert st["transport"]["bytes_sent"] > 0
        _drain_check(*[fe.engine for fe in fes])

    def test_blocks_stream_ahead_of_the_ticket(self):
        """A long prompt prefills over several steps; completed blocks
        must ship BEFORE the handoff ticket (the overlap the tentpole
        names) — i.e. the ticket's own chunks start past block 0."""
        m = _model()
        long_prompt = list(np.random.RandomState(9).randint(
            1, 193, 40))                     # > one 16-token budget step
        mono = _engine(m)
        oracle = mono.generate_batch([long_prompt], max_new_tokens=6)
        router, fes = _fleet(m, n_decode=1)
        seen = []
        orig = router.transport.send_ticket

        def spy(src, dst, key, ticket):
            seen.append([c.start for c in ticket.chunks])
            return orig(src, dst, key, ticket)

        router.transport.send_ticket = spy

        async def run():
            async with router:
                return await router.submit(long_prompt,
                                           max_new_tokens=6)

        out = asyncio.run(run())
        assert [out] == oracle
        assert seen and seen[0] and seen[0][0] > 0
        assert router.transport.blocks_sent \
            >= len(long_prompt) // fes[0].engine.block_size

    def test_shed_and_failover_stay_lossless(self):
        m = _model()
        prompts = _prompts(4, seed=2)
        mono = _engine(m)
        oracle = mono.generate_batch(prompts, max_new_tokens=20)
        router, fes = _fleet(m)

        async def run():
            async with router:
                tasks = [asyncio.ensure_future(
                    router.submit(p, max_new_tokens=20))
                    for p in prompts]
                # shed from the busiest decode replica...
                for _ in range(300):
                    await asyncio.sleep(0.01)
                    busiest = max((1, 2), key=router.queue_depth)
                    if router.shed(busiest, 1):
                        break
                # ...then kill the OTHER decode replica outright
                victim = min((1, 2), key=router.queue_depth)

                def boom():
                    raise RuntimeError("injected decode crash")
                fes[victim].engine.step = boom
                return await asyncio.gather(*tasks)

        outs = asyncio.run(run())
        assert outs == oracle
        st = router.stats()
        assert st["migrations"]["shed"] >= 1

    def test_auto_balance_policy_sheds(self):
        m = _model()
        prompts = _prompts(6, seed=3)
        mono = _engine(m)
        oracle = mono.generate_batch(prompts, max_new_tokens=20)
        router, fes = _fleet(m, migration={"imbalance": 2,
                                           "interval": 0.02})

        async def run():
            async with router:
                return await asyncio.gather(*[
                    router.submit(p, max_new_tokens=20)
                    for p in prompts])

        outs = asyncio.run(run())
        assert outs == oracle
        assert router.stats()["migrations"]["shed"] >= 1
        _drain_check(*[fe.engine for fe in fes])

    def test_rebalance_noop_below_threshold(self):
        m = _model()
        router, _fes = _fleet(m, migration={"imbalance": 1000})
        assert router.rebalance() == 0

    def test_migration_requires_disagg_roles(self):
        """Auto-shed on a monolithic fleet would end healthy streams
        with an unhandled RequestMigrated — refused at construction."""
        m = _model()
        fes = [ServingFrontend(_engine(m, max_slots=3)),
               ServingFrontend(_engine(m, max_slots=3))]
        with pytest.raises(ValueError, match="disaggregated fleet"):
            ReplicaRouter(fes, migration=True)

    def test_mixed_dispatch_replica_skips_stream_ahead_and_can_shed(self):
        """roles=["mixed", "decode"]: requests served end-to-end on the
        mixed replica must move ZERO KV (no stream-ahead paid for a
        handoff that never happens); a shed mid-decode then migrates
        with full parity and counts as a shed, not a handoff."""
        m = _model()
        prompts = _prompts(3, seed=5)
        mono = _engine(m)
        oracle = mono.generate_batch(prompts, max_new_tokens=16)
        mixed = _engine(m, max_slots=3, prefix_caching=True)
        dec = _engine(m, role="decode", max_slots=3)
        fes = [ServingFrontend(e, max_pending=16) for e in (mixed, dec)]
        router = ReplicaRouter(fes, roles=["mixed", "decode"],
                               probe_interval=0.02)

        async def run():
            async with router:
                outs = await asyncio.gather(*[
                    router.submit(p, max_new_tokens=16)
                    for p in prompts])
            return outs

        outs = asyncio.run(run())
        assert outs == oracle
        st = router.stats()
        assert st["migrations"] == {"handoff": 0, "shed": 0}
        assert st["transport"]["blocks_sent"] == 0

        # fresh fleet (routers/frontends are one-shot): shed the mixed
        # replica's live decode mid-stream
        mixed2 = _engine(m, max_slots=3, prefix_caching=True)
        dec2 = _engine(m, role="decode", max_slots=3)
        router2 = ReplicaRouter(
            [ServingFrontend(e, max_pending=16) for e in (mixed2, dec2)],
            roles=["mixed", "decode"], probe_interval=0.02)

        async def run_shed():
            async with router2:
                tasks = [asyncio.ensure_future(
                    router2.submit(p, max_new_tokens=24))
                    for p in prompts]
                for _ in range(300):
                    await asyncio.sleep(0.01)
                    if router2.shed(0, 1):
                        break
                return await asyncio.gather(*tasks)

        outs2 = asyncio.run(run_shed())
        assert outs2 == mono.generate_batch(prompts, max_new_tokens=24)
        st = router2.stats()
        assert st["migrations"]["shed"] >= 1
        assert st["migrations"]["handoff"] == 0
        _drain_check(mixed2, dec2)

    def test_role_validation(self):
        m = _model()
        pre = _engine(m, role="prefill")
        dec = _engine(m, role="decode")
        fes = [ServingFrontend(pre), ServingFrontend(dec)]
        with pytest.raises(ValueError, match="engine role"):
            ReplicaRouter(fes, roles=["decode", "prefill"])
        with pytest.raises(ValueError, match="decode-capable"):
            ReplicaRouter([fes[0]], roles=["prefill"])
        with pytest.raises(ValueError, match="mixed/prefill/decode"):
            ReplicaRouter(fes, roles=["prefill", "weird"])
        # mismatched KV geometry across a disagg fleet is refused
        dec8 = _engine(m, role="decode", kv_dtype="int8")
        with pytest.raises(ValueError, match="identical KV geometry"):
            ReplicaRouter([ServingFrontend(pre), ServingFrontend(dec8)],
                          roles=["prefill", "decode"])

    def test_direct_prefill_submit_surfaces_migration(self):
        """fe.submit on a prefill-role replica (no router) raises
        RequestMigrated — a loud signal, never a silent hang."""
        m = _model()
        fe = ServingFrontend(_engine(m, role="prefill"))

        async def run():
            async with fe:
                await fe.submit(_prompts()[0], max_new_tokens=8)

        with pytest.raises(RequestMigrated) as ei:
            asyncio.run(run())
        assert len(ei.value.ticket.output) == 1


# -------------------------------------------------------- config knobs


class TestConfigRoundTrip:
    def test_disagg_knobs_reach_router_and_engines(self):
        from paddle_tpu import inference
        m = _model()
        cfg = inference.Config()
        cfg.enable_continuous_batching(
            max_slots=3, block_size=4, max_seq_len=64,
            cache_dtype="float32", draft_k=2, prefix_caching=True,
            prefill_replicas=1, decode_replicas=2,
            migration={"imbalance": 3})
        router = inference.create_serving_router(cfg, m)
        assert router.roles == ["prefill", "decode", "decode"]
        assert router.migration["imbalance"] == 3
        assert router.migration["interval"] \
            == ReplicaRouter.MIGRATION_DEFAULTS["interval"]
        assert router.transport is not None
        pre = router.frontends[0].engine
        assert pre.role == "prefill" and pre.draft_k == 0
        for fe in router.frontends[1:]:
            assert fe.engine.role == "decode"
            assert fe.engine.draft_k == 2
            # decode-sized default budget: verify region + headroom
            # (the pow2 floor can make tiny geometries coincide with
            # the prefill budget, never exceed it)
            assert fe.engine.token_budget <= pre.token_budget

    def test_disagg_knob_validation(self):
        from paddle_tpu import inference
        cfg = inference.Config()
        cfg.enable_continuous_batching(max_slots=5, num_replicas=2)
        with pytest.raises(ValueError, match="pair"):
            cfg.enable_continuous_batching(prefill_replicas=1)
        # a raising call must leave the config exactly as it was
        assert cfg.serving_config()["max_slots"] == 5
        assert cfg._num_replicas == 2
        assert cfg._prefill_replicas is None
        with pytest.raises(ValueError, match="not both"):
            cfg.enable_continuous_batching(
                num_replicas=2, prefill_replicas=1, decode_replicas=1)
        cfg2 = inference.Config()
        cfg2.enable_continuous_batching(
            prefill_replicas=0, decode_replicas=1)
        with pytest.raises(ValueError, match=">= 1"):
            inference.create_serving_router(cfg2, _model())


# ------------------------------------------------------- smoke wiring


def test_disagg_smoke_tool(capsys):
    """tools/disagg_smoke.py is the disaggregated-serving CI contract:
    fleet outputs identical to a solo monolithic engine, >= 1 completed
    live migration, zero leaked blocks/scale rows after drain, and the
    full serving metric contract."""
    import importlib.util
    import os

    pm.REGISTRY.reset()
    was = pm._enabled
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "disagg_smoke.py")
    spec = importlib.util.spec_from_file_location("disagg_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        rc = mod.main()
        out = capsys.readouterr().out
        assert rc == 0
        from paddle_tpu.serving.metrics import CONTRACT_METRICS
        for name in CONTRACT_METRICS:
            assert name in out
    finally:
        pm.REGISTRY.reset()
        if not was:
            pm.disable()
