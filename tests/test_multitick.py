"""Device-resident multi-tick decode tests (ISSUE 18 tentpole).

Contracts: an engine with `ticks_per_dispatch=N` runs up to N decode
ticks per host dispatch inside ONE on-device `lax.while_loop` and is
token-identical to the N=1 engine across the whole feature matrix —
greedy, seeded sampling, preemption under block pressure, block-sparse
+ fp8 KV, LoRA adapters, TP=2 — while still compiling the mixed step
exactly ONCE (n_ticks is a traced scalar, so 1-tick and N-tick
dispatches share the executable; the suite-wide compile watchdog
backstops every test here). Speculation and history-dependent sampling
ride INSIDE the loop since ISSUE 19: a per-slot device ring buffer
feeds `ngram_propose_device` and a `[max_slots, penalty_vocab_bins]`
count tensor feeds the penalty processors, so `draft_k > 0` and
repetition/presence penalties compose with `ticks_per_dispatch=N`
(token-identical to the N=1 host-drafter engine for greedy, same
sampling distribution otherwise). The `inference.Config` knob
validates before mutating and the disaggregated router pins prefill
replicas to 1 tick.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForGeneration
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.serving.batcher import SamplingConfig
from paddle_tpu.serving.engine import STEP_FN_NAME, ServingEngine


def _model(vocab=193):
    paddle.seed(1234)
    m = GPTForGeneration(vocab_size=vocab, hidden_size=32, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128,
                         compute_dtype="float32")
    m.eval()
    return m


def _prompts(vocab=193, lens=(5, 9, 3, 12)):
    rng = np.random.RandomState(0)
    return [rng.randint(1, vocab, n).tolist() for n in lens]


def _engine(m, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", "float32")
    kw.setdefault("seed", 0)
    return ServingEngine(m, **kw)


@pytest.fixture(scope="module")
def model():
    return _model()


def _run_pair(mk, prompts, n, max_new_tokens=8):
    """Build the N=1 reference and the N=n engine from the same
    factory; return (ref_outputs, outputs, engine, mixed-step
    compiles of the N=n engine)."""
    ref = mk(1).generate_batch(prompts, max_new_tokens=max_new_tokens)
    pm.enable()
    pm.REGISTRY.reset()
    try:
        eng = mk(n)
        c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
        out = eng.generate_batch(prompts, max_new_tokens=max_new_tokens)
        compiles = pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0
    finally:
        pm.REGISTRY.reset()
        pm.disable()
    return ref, out, eng, compiles


# ------------------------------------------------- identity matrix


class TestMultitickIdentity:
    @pytest.mark.parametrize("n", [4, 8])
    def test_greedy_token_identical(self, model, n):
        ref, out, eng, compiles = _run_pair(
            lambda k: _engine(model, ticks_per_dispatch=k),
            _prompts(), n)
        assert out == ref
        assert compiles == 1
        assert eng.kv.blocks_in_use == 0
        # the loop really multi-ticked: more device ticks than host
        # dispatches, and the early-exit taxonomy recorded events
        assert eng.device_ticks_run > eng.dispatches_run
        ee = eng.early_exit_counts
        assert ee["finish"] + ee["overflow"] > 0

    @pytest.mark.parametrize("n", [4, 8])
    def test_seeded_sampling_token_identical(self, model, n):
        """The carry threads the PRNG chain through the loop: per-tick
        `random.split` on device must reproduce the host-loop chain
        bit-exactly."""
        sc = SamplingConfig(strategy="sampling", temperature=1.2,
                            top_k=40, top_p=0.9)
        ref, out, eng, compiles = _run_pair(
            lambda k: _engine(model, sampling=sc, seed=7,
                              ticks_per_dispatch=k),
            _prompts(), n)
        assert out == ref
        assert compiles == 1
        assert eng.kv.blocks_in_use == 0

    @pytest.mark.parametrize("n", [4, 8])
    def test_preemption_token_identical(self, model, n):
        """Block pressure (num_blocks=14) forces preempt/resume cycles;
        the per-slot cap lane must stop a preempted slot's ticks at its
        preallocated frontier, never past it."""
        ref, out, eng, compiles = _run_pair(
            lambda k: _engine(model, num_blocks=14, ticks_per_dispatch=k),
            _prompts(), n)
        assert out == ref
        assert compiles == 1
        assert eng.kv.blocks_in_use == 0

    @pytest.mark.parametrize("n", [4, 8])
    def test_sparse_fp8_token_identical(self, model, n):
        """Block-sparse decode attention + fp8 pools: the in-loop block
        count must grow per tick exactly as the host loop's width-1
        formula does."""
        ref, out, eng, compiles = _run_pair(
            lambda k: _engine(model, kv_dtype="fp8_e4m3",
                              sparse_blocks=12, ticks_per_dispatch=k),
            _prompts(), n)
        assert out == ref
        assert compiles == 1
        assert eng.kv.blocks_in_use == 0

    def test_auto_mode_token_identical(self, model):
        """`ticks_per_dispatch="auto"` paces N from the host-gap/tick
        EMAs; whatever N it picks, tokens cannot move."""
        ref, out, eng, compiles = _run_pair(
            lambda k: _engine(
                model,
                ticks_per_dispatch="auto" if k != 1 else 1),
            _prompts(), 8)
        assert out == ref
        assert compiles == 1
        assert eng._ticks_auto and eng.ticks_per_dispatch == 8


class TestMultitickAdapters:
    @pytest.mark.parametrize("n", [4, 8])
    def test_lora_slots_token_identical(self, model, n):
        """Per-slot adapter ids ride the control tail: rebuilt ticks
        must keep each slot on its own adapter."""
        from tests.test_adapters import make_random_adapter
        ad = make_random_adapter(model.decoder, 4, seed=1, scale=0.3)
        prompts = _prompts()

        def run(k):
            pm.enable()
            pm.REGISTRY.reset()
            try:
                eng = _engine(model, max_adapters=3, lora_rank=4,
                              ticks_per_dispatch=k)
                eng.register_adapter("t1", ad)
                c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
                reqs = [eng.submit(p, 8,
                                   adapter_id="t1" if i % 2 else None)
                        for i, p in enumerate(prompts)]
                eng.run()
                c = pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0
                return [list(r.output) for r in reqs], eng, c
            finally:
                pm.REGISTRY.reset()
                pm.disable()

        ref, _, _ = run(1)
        out, eng, compiles = run(n)
        assert out == ref
        assert compiles == 1
        assert eng.kv.blocks_in_use == 0


class TestMultitickTP:
    @pytest.mark.parametrize("n", [4, 8])
    def test_tp2_token_identical_one_compile(self, model, n):
        """The while_loop wraps the shard_map'ed step body, so the loop
        sits OUTSIDE the mesh partitioning and the control tail stays
        replicated — including the PRNG chain, which the host must
        round-trip as a host array or the second dispatch sees a
        sharded key and recompiles."""
        import jax

        from paddle_tpu.serving.distributed import TPServingEngine
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        prompts = _prompts()
        ref = _engine(model).generate_batch(prompts, max_new_tokens=8)
        pm.enable()
        pm.REGISTRY.reset()
        try:
            eng = TPServingEngine(model, tensor_parallel=2,
                                  max_slots=4, block_size=4,
                                  max_seq_len=64,
                                  cache_dtype="float32", seed=0,
                                  ticks_per_dispatch=n)
            c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
            out = eng.generate_batch(prompts, max_new_tokens=8)
            compiles = pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0
        finally:
            pm.REGISTRY.reset()
            pm.disable()
        assert out == ref
        assert compiles == 1
        assert eng.kv.blocks_in_use == 0
        assert eng.device_ticks_run > eng.dispatches_run


# ------------------------------------------------- on-device speculation


def _spec_sampling(name):
    return {
        "greedy": None,
        "top-p": SamplingConfig(strategy="sampling", temperature=0.8,
                                top_p=0.9),
        "rep-pen": SamplingConfig(strategy="sampling", temperature=0.9,
                                  repetition_penalty=1.3),
        "rep-pen-greedy": SamplingConfig(repetition_penalty=1.3,
                                         presence_penalty=0.2),
    }[name]


class TestSpeculativeMultitick:
    """ISSUE 19 identity matrix: the N-tick engine with the TRACED
    drafter/verify/ring/count math must reproduce the N=1 engine —
    host n-gram drafter, host accept loop, host-rebuilt penalty counts
    — bit-exactly, in one compile, for every sampling family and for
    draft_k=0 (penalties-in-the-loop is new here too)."""

    @pytest.mark.parametrize("n", [4, "auto"])
    @pytest.mark.parametrize("draft_k", [0, 3])
    @pytest.mark.parametrize("name", ["greedy", "top-p", "rep-pen",
                                      "rep-pen-greedy"])
    def test_token_identical_one_compile(self, model, n, draft_k,
                                         name):
        sc = _spec_sampling(name)
        kw = dict(draft_k=draft_k)
        if sc is not None:
            kw["sampling"] = sc
        ref, out, eng, compiles = _run_pair(
            lambda k: _engine(model,
                              ticks_per_dispatch=n if k != 1 else 1,
                              **kw),
            _prompts(), n, max_new_tokens=8)
        assert out == ref
        assert compiles == 1
        assert eng.kv.blocks_in_use == 0
        want = "device" if draft_k else "off"
        assert eng.speculation_mode == want

    def test_repetitive_prompts_accept_on_device(self, model):
        """A prompt the n-gram drafter can actually predict: the
        in-loop accept roll must land multi-token groups and the host
        mirrors of the device counters must agree with the metrics."""
        prompts = [[7, 8, 9] * 6, [3, 4] * 8]
        ref = _engine(model, draft_k=3).generate_batch(
            prompts, max_new_tokens=12)
        eng = _engine(model, draft_k=3, ticks_per_dispatch=4)
        out = eng.generate_batch(prompts, max_new_tokens=12)
        assert out == ref
        assert eng.spec_accepted_total > 0
        assert eng.spec_proposed_total >= eng.spec_accepted_total

    def test_tp2_spec_token_identical_one_compile(self, model):
        """TP=2 shares the identical traced drafter: the loop (and its
        ring/drafter/accept math) sits OUTSIDE shard_map on replicated
        control arrays, so a TP=2 speculative engine matches the
        1-chip N=1 host-drafter reference in one compile."""
        import jax

        from paddle_tpu.serving.distributed import TPServingEngine
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        prompts = _prompts()
        ref = _engine(model, draft_k=3).generate_batch(
            prompts, max_new_tokens=8)
        pm.enable()
        pm.REGISTRY.reset()
        try:
            eng = TPServingEngine(model, tensor_parallel=2,
                                  max_slots=4, block_size=4,
                                  max_seq_len=64,
                                  cache_dtype="float32", seed=0,
                                  draft_k=3, ticks_per_dispatch=4)
            c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
            out = eng.generate_batch(prompts, max_new_tokens=8)
            compiles = pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0
        finally:
            pm.REGISTRY.reset()
            pm.disable()
        assert out == ref
        assert compiles == 1
        assert eng.speculation_mode == "device"
        assert eng.kv.blocks_in_use == 0


# ------------------------------------------------- fallback + plumbing


class TestMultitickFallbacks:
    def test_speculation_rides_multitick(self, model):
        """draft_k > 0 no longer falls back to single-tick dispatches
        (ISSUE 19): the n-gram drafter runs inside the while_loop on a
        device token-history ring, and the N-tick engine is
        token-identical to the N=1 engine running the HOST drafter."""
        prompts = _prompts()
        ref = _engine(model, draft_k=3).generate_batch(
            prompts, max_new_tokens=8)
        eng = _engine(model, draft_k=3, ticks_per_dispatch=4)
        assert eng._multitick and eng.speculation_mode == "device"
        assert eng.generate_batch(prompts, max_new_tokens=8) == ref
        # the drafter really proposed on device and the readback
        # mirrored the totals
        assert eng.spec_proposed_total > 0
        assert 0 <= eng.spec_accepted_total <= eng.spec_proposed_total

    def test_bad_spec_configs_raise_loudly(self, model):
        """Impossible speculation combos are a loud ValueError at
        construction, never a silent draft_k zeroing (ISSUE 19
        satellite)."""
        for kw in (dict(draft_k=-1),
                   dict(draft_k=2, draft_ngram=0),
                   dict(draft_k=2, draft_ring=1)):
            with pytest.raises(ValueError):
                _engine(model, **kw)
        with pytest.raises(ValueError):
            _engine(model, penalty_vocab_bins=0,
                    sampling=SamplingConfig(repetition_penalty=1.3))

    def test_bad_ticks_rejected(self, model):
        for bad in (0, -1, "fast"):
            with pytest.raises((ValueError, TypeError)):
                _engine(model, ticks_per_dispatch=bad)

    def test_flight_recorder_dispatch_fields(self, model):
        """Multi-tick dispatches land ticks/early-exit/host-stall
        fields in the per-engine flight recorder summary."""
        from paddle_tpu.serving import tracing
        eng = _engine(model, ticks_per_dispatch=4)
        tracing.enable()
        try:
            eng.generate_batch(_prompts(), max_new_tokens=8)
        finally:
            tracing.disable()
        agg = eng.flight.summary()
        assert agg["dispatches"] > 0
        assert agg["ticks_total"] == eng.device_ticks_run
        assert agg["ticks_per_dispatch_mean"] > 1.0
        assert agg["host_stall_s"] >= 0.0


class TestConfigPlumbing:
    def test_knob_validates_before_mutating(self):
        from paddle_tpu.inference import Config
        c = Config()
        for bad in (0, -2, 1.5, True, "fast"):
            with pytest.raises(ValueError):
                c.enable_continuous_batching(ticks_per_dispatch=bad)
            assert c.serving_config() is None
        c.enable_continuous_batching(max_slots=2, ticks_per_dispatch=8)
        assert c.serving_config()["ticks_per_dispatch"] == 8
        c2 = Config()
        c2.enable_continuous_batching(ticks_per_dispatch="auto")
        assert c2.serving_config()["ticks_per_dispatch"] == "auto"

    def test_create_engine_passthrough(self, model):
        from paddle_tpu.inference import Config, create_serving_engine
        c = Config()
        c.enable_continuous_batching(
            max_slots=4, block_size=4, max_seq_len=64,
            cache_dtype="float32", ticks_per_dispatch=4)
        eng = create_serving_engine(c, model)
        assert eng.ticks_per_dispatch == 4 and eng._multitick

    def test_disagg_roles_pin_prefill_default_decode(self, model):
        """Prefill replicas are pinned to 1 tick; decode replicas
        default onto the device-resident loop when the config leaves
        the knob unset."""
        from paddle_tpu.inference import Config, create_serving_router
        c = Config()
        c.enable_continuous_batching(
            max_slots=4, block_size=4, max_seq_len=64,
            cache_dtype="float32", prefill_replicas=1,
            decode_replicas=1)
        router = create_serving_router(c, model)
        engines = [f.engine for f in router.frontends]
        assert engines[0].role == "prefill"
        assert engines[0].ticks_per_dispatch == 1
        assert engines[1].role == "decode"
        assert engines[1].ticks_per_dispatch == 4
        # an explicit config value overrides the decode default
        c2 = Config()
        c2.enable_continuous_batching(
            max_slots=4, block_size=4, max_seq_len=64,
            cache_dtype="float32", prefill_replicas=1,
            decode_replicas=1, ticks_per_dispatch=2)
        router2 = create_serving_router(c2, model)
        engines2 = [f.engine for f in router2.frontends]
        assert engines2[0].ticks_per_dispatch == 1
        assert engines2[1].ticks_per_dispatch == 2


# ------------------------------------------------- one packer, one observe


def _afmoe_engine():
    from paddle_tpu.models import afmoe
    from tests.test_afmoe_serving import small
    return ServingEngine(afmoe.AfmoeForGeneration(small(), seed=3),
                         max_slots=3, block_size=4, num_blocks=80,
                         max_seq_len=128, token_budget=16,
                         cache_dtype="float32")


PACKER_CASES = {
    "gpt": dict(),
    "draft": dict(draft_k=2),
    "adapters": dict(max_adapters=2, lora_rank=4),
    "penalized": dict(sampling=SamplingConfig(
        strategy="sampling", repetition_penalty=1.3)),
    "sparse": dict(sparse_blocks=4),
    "ticks4": dict(ticks_per_dispatch=4),
    "ticks4_draft": dict(ticks_per_dispatch=4, draft_k=2),
    "afmoe_block": None,
}


def _arg_specs(args):
    """Tree structure, and per leaf (host or device, shape, dtype)."""
    import jax
    leaves, tree = jax.tree.flatten(list(args))
    return tree, [("host" if isinstance(a, np.ndarray) else "device",
                   tuple(a.shape), str(a.dtype)) for a in leaves]


class TestOnePacker:
    @pytest.mark.parametrize("case", list(PACKER_CASES))
    def test_example_args_are_a_live_steps(self, model, case):
        """`example_step_args()` is what the kernel check traces and
        what the fleet bundle compiles: argument by argument it must be
        what a live step hands the compiled step."""
        kw = PACKER_CASES[case]
        eng = _afmoe_engine() if kw is None else _engine(model, **kw)
        example = _arg_specs(eng.example_step_args())
        live, step_fn = [], eng._step_fn

        def spy(*args):
            live.append(_arg_specs(args))
            return step_fn(*args)
        eng._step_fn = spy
        eng.generate_batch(_prompts(vocab=90), max_new_tokens=6)
        # a prefill step, a decode step, and (multi-tick) a dispatch of
        # several ticks: every one the same signature
        assert len(live) >= 3
        for tree, leaves in live:
            assert tree == example[0]
            assert leaves == example[1]
        assert _arg_specs(eng.example_step_args()) == example

    def test_one_tick_and_two_ticks_publish_the_same_counters(self, model):
        """One `observe`: the same traffic leaves the same token,
        request, preemption and prefix counters whichever device
        program ran it (block pressure and the prefix cache apart: a
        tick burst's preallocation may evict a cached block sooner)."""
        from paddle_tpu.serving import metrics as sm
        rng = np.random.RandomState(3)
        head = rng.randint(1, 193, 8).tolist()
        prompts = [head + rng.randint(1, 193, n).tolist()
                   for n in (3, 7, 5, 9, 4, 6)]

        def counters(ticks, **kw):
            pm.enable()
            pm.REGISTRY.reset()
            try:
                eng = _engine(model, ticks_per_dispatch=ticks, **kw)
                out = eng.generate_batch(prompts, max_new_tokens=8)
                return out, dict(
                    prefill=sm.SERVING_TOKENS.labels("prefill").value,
                    decode=sm.SERVING_TOKENS.labels("decode").value,
                    finished=sm.SERVING_REQUESTS.labels(
                        "finished").value,
                    preemptions=sm.SERVING_PREEMPTIONS.value,
                    prefix_hit=sm.SERVING_PREFIX_HIT_TOKENS.value,
                    prefix_miss=sm.SERVING_PREFIX_MISS_TOKENS.value,
                    prefix_evicted=sm.SERVING_PREFIX_EVICTIONS.value)
            finally:
                pm.REGISTRY.reset()
                pm.disable()

        for kw, moved in ((dict(num_blocks=14), "preemptions"),
                          (dict(prefix_caching=True), "prefix_hit")):
            out1, c1 = counters(1, **kw)
            out2, c2 = counters(2, **kw)
            assert out2 == out1
            assert c2 == c1, kw
            assert c1["finished"] == len(prompts)
            assert c1["prefill"] > 0 and c1["decode"] > 0
            assert c1[moved] > 0, c1


# ------------------------------------------------- smoke-tool wiring


def test_multitick_smoke_tool(capsys):
    """tools/multitick_smoke.py is the multi-tick CI contract: one
    Poisson stream through N=1/4/8 engines, token-identical, one
    compile each, early exits recorded, every serving metric name
    present."""
    import importlib.util
    import os

    pm.REGISTRY.reset()
    was = pm._enabled
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "multitick_smoke.py")
    spec = importlib.util.spec_from_file_location("multitick_smoke",
                                                  path)
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
