"""Fleet control plane tests (ISSUE 17).

Bundle export/AOT-boot roundtrips (zero mixed-step compiles under
the watchdog, token identity, warm prefix re-adoption), the live
weight swap (bit-identity vs a fresh engine, the single budget-1
swap compile, prefix invalidation, the guard rails), prefix-cache
spill/restore semantics, the router's quiesce/drain/add_replica
plane, rolling-upgrade protocol rules, autoscaler hysteresis as pure
policy arithmetic, the controller lifecycle, the sparse-budget tuner
contract, and the tools/fleet_smoke.py CI gate.
"""
import asyncio
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import guards
from paddle_tpu.models.gpt import GPTForGeneration
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.serving.distributed import ReplicaRouter
from paddle_tpu.serving.distributed.router import NoReplicaAvailable
from paddle_tpu.serving.engine import ServingEngine
from paddle_tpu.serving.fleet import (AutoscalerPolicy, FleetBundle,
                                      FleetController, SLOAutoscaler,
                                      boot_engine_from_bundle,
                                      export_bundle, weights_from_model)
from paddle_tpu.serving.fleet.upgrade import rolling_upgrade
from paddle_tpu.serving.frontend import ServingFrontend
from paddle_tpu.serving.slo import SLOMonitor

ENG_KW = dict(max_slots=4, block_size=4, num_blocks=64, max_seq_len=64,
              token_budget=64, cache_dtype="float32", seed=0,
              prefix_caching=True)
PROMPTS = [[2, 3, 5, 7, 11], [13, 17, 19], [23, 29, 31, 37]]


def _model(seed=1234):
    paddle.seed(seed)
    m = GPTForGeneration(vocab_size=193, hidden_size=32, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128,
                         compute_dtype="float32")
    m.eval()
    return m


def _gen(engine, prompts=PROMPTS, n=8):
    return engine.generate_batch([list(p) for p in prompts],
                                 max_new_tokens=n)


# ----------------------------------------------------------- bundles
class TestBundle:
    def test_aot_boot_zero_compiles_token_identical(self, tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        ref = _gen(eng)
        bundle = FleetBundle(export_bundle(eng, str(tmp_path),
                                           version="v1"))
        assert bundle.version == "v1"
        assert bundle.has_executable("mixed", 1)
        with guards.sanitize(budgets={"serving_mixed_step": 0}) as wd:
            boot = boot_engine_from_bundle(bundle)
            out = _gen(boot)
        assert not wd.violations
        assert out == ref
        assert boot.weights_version == "v1"

    def test_bundle_weights_are_canonical_and_validated(self, tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        bundle = FleetBundle(export_bundle(eng, str(tmp_path)))
        tensors = list(eng.model._gen_tensors())
        weights = bundle.weights()
        assert len(weights) == len(tensors)
        for t, w in zip(tensors, weights):
            np.testing.assert_array_equal(np.asarray(t._data), w)
        man = bundle.manifest
        assert man["engine"]["block_size"] == 4
        assert man["kv_meta"] == eng.kv.kv_meta()
        # weight-manifest drift is refused, not silently mis-zipped
        bundle.manifest["model"]["num_layers"] = 3
        with pytest.raises(ValueError, match="tensor"):
            bundle.build_model()

    def test_boot_without_executable_falls_back_to_jit(self, tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        ref = _gen(eng)
        bundle = FleetBundle(export_bundle(
            eng, str(tmp_path), include_executable=False))
        assert not bundle.has_executable()
        boot = boot_engine_from_bundle(bundle)   # ordinary jit path
        assert _gen(boot) == ref

    @pytest.mark.parametrize("drift", ["before_the_field", "other_order"])
    def test_executable_of_another_signature_is_not_loaded(self, tmp_path,
                                                          drift):
        """The manifest pins the constructor's knobs, not the step's
        argument list: a bundle whose executable was compiled for
        another list (before the plan travelled as one buffer the step
        took five arrays in its place; such a manifest has no
        `step_signatures`) boots through a fresh compile and serves the
        same tokens."""
        import json
        eng = ServingEngine(_model(), **ENG_KW)
        ref = _gen(eng)
        bdir = export_bundle(eng, str(tmp_path))
        mpath = os.path.join(bdir, "manifest.json")
        with open(mpath) as f:
            man = json.load(f)
        sig = man["step_signatures"]["mixed-tp1"]
        assert [f[0] for f in sig["plan"]] == [
            "token_ids", "slot_ids", "positions", "sample_index",
            "block_tables"]
        # the plan, the tokens of the step before (PR 36: the engine
        # dispatches ahead), the key
        assert sig["args"][-3:] == [
            f"int32[{eng.plan_layout.size}]",
            f"int32[{eng.kv.max_slots}]", "uint32[2]"]
        if drift == "before_the_field":
            del man["step_signatures"]
        else:
            sig["args"] = sig["args"][::-1]
        with open(mpath, "w") as f:
            json.dump(man, f)
        bundle = FleetBundle(bdir)
        assert bundle.has_executable("mixed", 1)
        boot = boot_engine_from_bundle(bundle)
        assert not boot._aot_step
        assert _gen(boot) == ref
        assert boot.step_compile_count() == 1

    def test_warm_boot_restores_prefix_spill(self, tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        ref = _gen(eng)
        bundle = FleetBundle(export_bundle(eng, str(tmp_path)))
        spill = str(tmp_path / "prefix.pkl")
        spilled = eng.close(spill_prefix=spill)
        assert spilled > 0
        with guards.sanitize(budgets={"serving_mixed_step": 0}) as wd:
            warm = boot_engine_from_bundle(bundle, warm_prefix=spill)
        assert not wd.violations
        assert warm.prefix_cache.cached_blocks == spilled
        assert _gen(warm) == ref

    def test_engine_overrides_apply_on_boot(self, tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        bundle = FleetBundle(export_bundle(eng, str(tmp_path)))
        boot = boot_engine_from_bundle(bundle, name="ovr",
                                       prefix_caching=False)
        assert boot.name == "ovr"
        assert boot.prefix_cache is None


# -------------------------------------------------------- weight swap
class TestWeightSwap:
    def test_swap_token_identical_one_budget1_compile(self):
        m2 = _model(777)
        w2 = weights_from_model(m2)
        ref2 = _gen(ServingEngine(m2, **ENG_KW))
        eng = ServingEngine(_model(), **ENG_KW)
        _gen(eng)                                  # live v1 traffic
        with guards.sanitize(budgets={"serving_mixed_step": 0,
                                      "serving_weight_swap": 1}) as wd:
            eng.swap_weights(w2, "v2")
            eng.swap_weights(weights_from_model(_model()), "v3")
            eng.swap_weights(w2, "v2")             # reuses the jit
            out = _gen(eng)
        assert not wd.violations   # no step recompile, ONE swap compile
        assert out == ref2
        assert eng.weights_version == "v2"

    def test_swap_invalidates_prefix_cache(self):
        eng = ServingEngine(_model(), **ENG_KW)
        _gen(eng)
        assert eng.prefix_cache.cached_blocks > 0
        eng.swap_weights(weights_from_model(_model(777)), "v2")
        assert eng.prefix_cache.cached_blocks == 0

    def test_swap_guard_rails(self):
        eng = ServingEngine(_model(), **ENG_KW)
        w = weights_from_model(_model(777))
        with pytest.raises(ValueError, match="tensors"):
            eng.swap_weights(w[:-1], "v2")
        bad = [np.zeros((3, 3), np.float32) for _ in w]
        with pytest.raises(ValueError, match="shape"):
            eng.swap_weights(bad, "v2")
        assert eng.weights_version == "v0"         # unchanged on error


# ------------------------------------------------ prefix spill/restore
class TestPrefixSpill:
    def test_roundtrip_counts_and_reuse(self, tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        _gen(eng)
        cached = eng.prefix_cache.cached_blocks
        assert cached > 0
        path = str(tmp_path / "p.pkl")
        assert eng.prefix_cache.spill(path) == cached
        free0 = eng.kv.allocator.num_free
        eng.prefix_cache.evict_all()
        other = ServingEngine(_model(), **ENG_KW)
        assert other.prefix_cache.restore(path) == cached
        assert other.prefix_cache.cached_blocks == cached
        # restored KV is served, not recomputed: hit counters move
        h0 = other.prefix_cache.hit_tokens
        _gen(other)
        assert other.prefix_cache.hit_tokens > h0
        assert eng.kv.allocator.num_free >= free0   # donor unharmed

    def test_restore_refuses_mismatched_pool_or_dirty_tree(self,
                                                           tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        _gen(eng)
        path = str(tmp_path / "p.pkl")
        eng.prefix_cache.spill(path)
        kw = dict(ENG_KW)
        kw["block_size"] = 8                       # different geometry
        odd = ServingEngine(_model(), **kw)
        with pytest.raises(ValueError, match="kv_meta"):
            odd.prefix_cache.restore(path)
        dirty = ServingEngine(_model(), **ENG_KW)
        _gen(dirty)
        with pytest.raises(ValueError, match="empty"):
            dirty.prefix_cache.restore(path)

    def test_restore_is_all_or_nothing(self, tmp_path):
        eng = ServingEngine(_model(), **ENG_KW)
        _gen(eng)
        path = str(tmp_path / "p.pkl")
        eng.prefix_cache.spill(path)
        kw = dict(ENG_KW)
        kw["num_blocks"] = 4                       # too small for spill
        tiny = ServingEngine(_model(), **kw)
        assert tiny.prefix_cache.restore(path) == 0
        assert tiny.prefix_cache.cached_blocks == 0


# ------------------------------------------------- router fleet plane
class TestRouterFleetPlane:
    def _fes(self, n=2):
        return [ServingFrontend(
            ServingEngine(_model(), name=f"r{i}", **ENG_KW),
            max_pending=16) for i in range(n)]

    def test_quiesce_excludes_from_dispatch(self):
        fes = self._fes()
        router = ReplicaRouter(fes)

        async def run():
            async with router:
                router.quiesce(0)
                for _ in range(4):
                    await router.submit([2, 3, 5], max_new_tokens=2)
                router.unquiesce(0)
        asyncio.run(run())
        # every request landed on replica 1: only ITS prefix cache saw
        # traffic, and the quiesced set is empty again
        assert fes[0].engine.prefix_cache.cached_blocks == 0
        assert fes[1].engine.prefix_cache.cached_blocks > 0
        assert router.stats()["quiesced"] == []

    def test_quiesce_all_refuses_dispatch(self):
        router = ReplicaRouter(self._fes())

        async def run():
            async with router:
                router.quiesce(0)
                router.quiesce(1)
                with pytest.raises(NoReplicaAvailable, match="quiesced"):
                    await router.submit([2, 3], max_new_tokens=1)
        asyncio.run(run())

    def test_add_replica_validates_and_appends(self):
        router = ReplicaRouter(self._fes())
        kw = dict(ENG_KW)
        kw["block_size"] = 8
        bad = ServingFrontend(ServingEngine(_model(), **kw))
        good = ServingFrontend(ServingEngine(_model(), name="r2",
                                             **ENG_KW))

        async def run():
            async with router:
                with pytest.raises(ValueError, match="block_size"):
                    await router.add_replica(bad)
                with pytest.raises(ValueError, match="role"):
                    await router.add_replica(good, role="oracle")
                idx = await router.add_replica(good)
                assert idx == 2
                assert len(router.health) == 3
                ref = await router.submit([2, 3, 5], max_new_tokens=4)
                router.quiesce(0)
                router.quiesce(1)      # only the new replica serves
                out = await router.submit([2, 3, 5], max_new_tokens=4)
                assert out == ref
        asyncio.run(run())

    def test_is_drained_tracks_live_work(self):
        router = ReplicaRouter(self._fes(1))

        async def run():
            async with router:
                assert router.is_drained(0)
                task = asyncio.ensure_future(
                    router.submit([2, 3, 5, 7], max_new_tokens=24))
                await asyncio.sleep(0.01)
                assert not router.is_drained(0)
                await task
                for _ in range(200):
                    if router.is_drained(0):
                        break
                    await asyncio.sleep(0.005)
                assert router.is_drained(0)
        asyncio.run(run())


# ---------------------------------------------------- rolling upgrade
class TestRollingUpgrade:
    def test_refuses_single_replica_fleet(self):
        fe = ServingFrontend(ServingEngine(_model(), **ENG_KW))
        router = ReplicaRouter([fe])
        w2 = weights_from_model(_model(777))

        async def run():
            async with router:
                with pytest.raises(ValueError, match=">= 2"):
                    await rolling_upgrade(router, w2, "v2")
        asyncio.run(run())

    def test_upgrade_is_lossless_and_versions_flip(self):
        m2 = _model(777)
        w2 = weights_from_model(m2)
        ref2 = _gen(ServingEngine(m2, **ENG_KW), n=6)
        fes = [ServingFrontend(ServingEngine(_model(), name=f"r{i}",
                                             **ENG_KW), max_pending=16)
               for i in range(2)]
        for fe in fes:
            fe.engine.generate_batch([[7, 7]], max_new_tokens=1)
        router = ReplicaRouter(fes, probe_interval=0.02)

        async def run():
            async with router:
                tasks = [asyncio.ensure_future(
                    router.submit(list(p), max_new_tokens=6))
                    for p in PROMPTS]
                await asyncio.sleep(0.005)
                flipped = await rolling_upgrade(router, w2, "v2")
                outs = await asyncio.gather(*tasks)
                post = await asyncio.gather(
                    *[router.submit(list(p), max_new_tokens=6)
                      for p in PROMPTS])
                return flipped, outs, post
        flipped, outs, post = asyncio.run(run())
        assert sorted(flipped) == [0, 1]
        assert post == ref2
        assert router.stats()["versions"] == ["v2", "v2"]
        assert router.stats()["quiesced"] == []
        ref1 = _gen(ServingEngine(_model(), **ENG_KW), n=6)
        for o, r1, r2 in zip(outs, ref1, ref2):
            assert o == r1 or o == r2   # never a mid-request mix


# -------------------------------------------------------- autoscaler
class _FakeFE:
    class engine:
        flight = None


class _FakeRouter:
    class _FES:
        def __getitem__(self, i):
            return _FakeFE()
    frontends = _FES()

    def __init__(self):
        self.depths = {}

    def queue_depth(self, i):
        return self.depths.get(i, 0)


class _FakeController:
    def __init__(self, clock):
        self.router = _FakeRouter()
        self.clock = clock
        self.n = 1

    def active_replicas(self):
        return list(range(self.n))

    async def scale_up(self, reason):
        self.n += 1
        return self.n - 1

    async def scale_down(self, reason):
        self.n -= 1
        return self.n


class TestAutoscaler:
    def _scaler(self, **pol):
        clk = [100.0]
        mon = SLOMonitor({"default": {"ttft_p95": 0.1},
                          "window_s": 1e9}, clock=lambda: clk[0])
        ctl = _FakeController(lambda: clk[0])
        pol = dict(dict(min_replicas=1, max_replicas=2, sustain_s=1.0,
                        recovery_s=2.0, cooldown_s=3.0), **pol)
        scaler = SLOAutoscaler(ctl, mon, clock=lambda: clk[0],
                               policy=AutoscalerPolicy(**pol))
        return clk, mon, ctl, scaler

    def test_sustained_burn_then_recovery_hysteresis(self):
        clk, mon, ctl, scaler = self._scaler()

        async def run():
            mon.on_ttft("t", 5.0, clk[0])
            assert await scaler.step() is None      # not sustained
            clk[0] += 1.1
            d = await scaler.step()
            assert d["direction"] == "up" and d["reason"] == "ttft_p95"
            assert ctl.n == 2
            mon.on_ttft("t", 5.0, clk[0])
            clk[0] += 1.5                           # inside cooldown
            assert await scaler.step() is None
            mon._ttft.clear()                       # burn ends
            mon.on_ttft("t", 0.01, clk[0])
            assert await scaler.step() is None      # not recovered yet
            clk[0] += 2.5
            d = await scaler.step()
            assert d["direction"] == "down"
            assert ctl.n == 1
            clk[0] += 10.0                          # min_replicas floor
            assert await scaler.step() is None
        asyncio.run(run())
        assert [d["direction"] for d in scaler.decisions] == \
            ["up", "down"]

    def test_max_replicas_caps_scale_up(self):
        clk, mon, ctl, scaler = self._scaler(max_replicas=1)

        async def run():
            mon.on_ttft("t", 5.0, clk[0])
            clk[0] += 1.1
            assert await scaler.step() is None
        asyncio.run(run())

    def test_cost_model_gates_scale_down(self):
        # recovered, but the predicted post-removal TTFT exceeds the
        # strictest target -> the autoscaler must keep the replica
        clk, mon, ctl, scaler = self._scaler(min_replicas=1)
        ctl.n = 2
        ctl.router.depths = {0: 40, 1: 40}
        scaler.mean_step_seconds = lambda: 0.05   # 80/1 * 0.05 >> 0.1

        async def run():
            mon.on_ttft("t", 0.01, clk[0])
            assert await scaler.step() is None    # starts recovery clock
            clk[0] += 2.5                         # recovery IS sustained
            assert scaler.predict_ttft(-1) > 0.1
            assert await scaler.step() is None    # cost model blocks
            ctl.router.depths = {}                # queues drain
            clk[0] += 1.0
            d = await scaler.step()
            assert d and d["direction"] == "down"
        asyncio.run(run())

    def test_predictions_use_host_state_only(self):
        clk, mon, ctl, scaler = self._scaler()
        ctl.n = 2
        ctl.router.depths = {0: 6, 1: 2}
        scaler.mean_step_seconds = lambda: 0.01
        assert scaler.queued_requests() == 8
        assert scaler.predict_ttft() == pytest.approx(8 / 2 * 0.01)
        assert scaler.predict_ttft(+1) == pytest.approx(8 / 3 * 0.01)
        assert scaler.predict_inter_token() == pytest.approx(0.01)


# -------------------------------------------------- fleet controller
class TestFleetController:
    def test_boot_upgrade_retire_lifecycle(self, tmp_path, _pm_off):
        m2 = _model(777)
        w2 = weights_from_model(m2)
        ref2 = _gen(ServingEngine(m2, **ENG_KW), n=6)
        eng0 = ServingEngine(_model(), name="r0", **ENG_KW)
        bundle = FleetBundle(export_bundle(eng0, str(tmp_path),
                                           version="v1"))
        fes = [ServingFrontend(eng0, max_pending=16),
               ServingFrontend(ServingEngine(_model(), name="r1",
                                             **ENG_KW), max_pending=16)]
        router = ReplicaRouter(fes, probe_interval=0.02)
        ctl = FleetController(router, bundle,
                              spill_dir=str(tmp_path / "spill"))
        pm.REGISTRY.reset()
        pm.enable()

        async def run():
            async with router:
                idx = await ctl.boot_replica()
                assert idx == 2
                assert ctl.active_replicas() == [0, 1, 2]
                await ctl.rolling_upgrade(w2, "v2")
                outs = await asyncio.gather(
                    *[router.submit(list(p), max_new_tokens=6)
                      for p in PROMPTS])
                assert outs == ref2
                eng = router.frontends[idx].engine
                await ctl.retire(idx)
                assert ctl.active_replicas() == [0, 1]
                assert idx in ctl.retired
                assert eng.kv.blocks_in_use == 0
                # retired slot never reused; fleet keeps serving
                outs = await asyncio.gather(
                    *[router.submit(list(p), max_new_tokens=6)
                      for p in PROMPTS])
                assert outs == ref2
        asyncio.run(run())
        from paddle_tpu.serving import metrics as sm
        boots = dict(sm.FLEET_BOOTS.samples())
        assert boots[("cold",)].value == 1
        assert sm.FLEET_UPGRADES.value == 3
        reps = {lv: g.value for lv, g in sm.FLEET_REPLICAS.samples()}
        assert reps[("mixed", "v2")] == 2
        assert sm.FLEET_COLD_START.count == 1

    def test_scale_down_retires_last_booted(self, tmp_path):
        eng0 = ServingEngine(_model(), name="r0", **ENG_KW)
        bundle = FleetBundle(export_bundle(eng0, str(tmp_path)))
        fes = [ServingFrontend(eng0, max_pending=16)]
        router = ReplicaRouter(fes, probe_interval=0.02)
        ctl = FleetController(router, bundle)

        async def run():
            async with router:
                a = await ctl.scale_up("ttft_p95")
                b = await ctl.scale_up("ttft_p95")
                assert (a, b) == (1, 2)
                down = await ctl.scale_down("recovered")
                assert down == 2                  # LIFO
                assert ctl.active_replicas() == [0, 1]
        asyncio.run(run())


# ------------------------------------------------ sparse budget tuner
class TestSparseBudget:
    @pytest.mark.slow
    def test_tuner_records_smallest_passing_budget(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_CACHE",
                           str(tmp_path / "kt.json"))
        from paddle_tpu.ops.pallas import autotune as kt
        from paddle_tpu.serving import sparse_budget as sb
        kt.reset_for_tests()
        res = sb.tune_sparse_budget(candidates=(4, 8))
        assert res["best"] is not None
        assert res["agreement"] >= 0.99
        swept = [r["sparse_blocks"] for r in res["sweep"]]
        assert swept == [4, 8]
        # smallest passing budget wins; the auto engine resolves it
        passing = [r["sparse_blocks"] for r in res["sweep"]
                   if r["agreement"] >= 0.99]
        assert res["best"]["sparse_blocks"] == passing[0]
        eng = ServingEngine(sb.needle_model(), max_slots=4,
                            block_size=4, max_seq_len=224,
                            cache_dtype="float32", seed=0,
                            sparse_blocks="auto")
        assert eng.sparse_blocks == res["best"]["sparse_blocks"]

    def test_auto_engine_cold_cache_default(self, tmp_path,
                                            monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_CACHE",
                           str(tmp_path / "kt.json"))
        from paddle_tpu.ops.pallas import autotune as kt
        kt.reset_for_tests()
        eng = ServingEngine(_model(), sparse_blocks="auto",
                            sparse_recent=3, **ENG_KW)
        assert eng.sparse_blocks == 8              # docs/SERVING.md pick
        assert eng._sparse_recent >= 3


# --------------------------------------------------------- CI gate
@pytest.fixture
def _pm_off():
    was = pm._enabled
    yield
    pm.REGISTRY.reset()
    if not was:
        pm.disable()


def test_fleet_smoke_tool(capsys, _pm_off):
    """tools/fleet_smoke.py is the fleet CI contract: zero-compile AOT
    boot, lossless rolling upgrade under live traffic, exactly-one
    scale-up + converged recovery, zero leaked blocks, and the fleet
    metric contract under sanitize()."""
    import importlib.util

    pm.REGISTRY.reset()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "fleet_smoke.py")
    spec = importlib.util.spec_from_file_location("fleet_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main()
    out = capsys.readouterr().out
    assert rc == 0
    for name in ("paddle_tpu_serving_fleet_replicas",
                 "paddle_tpu_serving_fleet_boots_total",
                 "paddle_tpu_serving_fleet_upgrades_total",
                 "paddle_tpu_serving_fleet_scale_events_total",
                 "paddle_tpu_serving_fleet_cold_start_seconds"):
        assert name in out
    assert "fleet smoke OK" in out
