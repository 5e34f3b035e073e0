"""Long-context serving: block-sparse paged decode attention + fp8 KV
pools (ISSUE 15).

The contract matrix: sparse selection at full coverage is
token-identical to the dense engine (TP=1 AND the TP=2 CPU mesh, one
mixed-step compile each); real sparsity holds the >= 99% agreement /
>= 50% skip contract end-to-end via tools/longctx_smoke.py (the
needle workload); fp8 pools ride the int8 scale plumbing (parity,
sizing, transport, CoW); summary rows ride block coordinates through
CoW/export/import by construction; the Pallas interpret-mode path
serves the SAME tokens as the XLA oracle through the shortened
tables.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForGeneration
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.serving import batcher
from paddle_tpu.serving.distributed import TPServingEngine
from paddle_tpu.serving.engine import STEP_FN_NAME, ServingEngine
from paddle_tpu.serving.kv_cache import KV_DTYPES, PagedKVCache


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForGeneration(vocab_size=211, hidden_size=32, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128,
                         compute_dtype="float32")
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(7)
    return [rng.randint(1, 211, n).tolist()
            for n in (3, 9, 17, 5, 12, 7, 21, 4)]


def _engine(cls, m, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("cache_dtype", "float32")
    kw.setdefault("seed", 0)
    return cls(m, **kw)


@pytest.fixture
def _metrics():
    pm.enable()
    pm.REGISTRY.reset()
    yield
    pm.REGISTRY.reset()
    pm.disable()


# ------------------------------------------------ region packing units


def test_pack_step_decode_region():
    """reserve_region=True at verify_width 1: decode token of slot s
    sits at flat index s, sample_index points there, prefill packs
    after the region."""
    sp = batcher.pack_step(16, 4, [(2, 7, 5), (0, 9, 3)],
                           [(1, np.arange(4, dtype=np.int32), 0,
                             False)],
                           verify_width=1, reserve_region=True)
    assert sp.token_ids[2] == 7 and sp.slot_ids[2] == 2
    assert sp.token_ids[0] == 9 and sp.slot_ids[0] == 0
    assert sp.sample_index[2] == 2 and sp.sample_index[0] == 0
    assert sp.slot_ids[1] == -1 and sp.slot_ids[3] == -1
    # prefill starts AFTER the reserved region
    assert list(sp.slot_ids[4:8]) == [1, 1, 1, 1]
    # dense layout unchanged without the flag
    sp2 = batcher.pack_step(16, 4, [(2, 7, 5)], [], verify_width=1)
    assert sp2.slot_ids[0] == 2 and sp2.sample_index[2] == 0


def test_choose_token_budget_reserve_region():
    assert batcher.choose_token_budget(4, 4, reserve_region=True) \
        == batcher.choose_token_budget(4, 4, verify_width=1) * 1
    # the region floor applies to explicit budgets
    assert batcher.choose_token_budget(
        8, 4, requested=4, reserve_region=True) >= 9


# -------------------------------------------------- kv_cache: fp8 + summaries


def test_kv_dtype_validation():
    with pytest.raises(ValueError, match="fp8_e4m3"):
        PagedKVCache(1, 1, 8, num_blocks=4, block_size=4, max_slots=1,
                     max_blocks_per_slot=2, kv_dtype="fp5")
    from paddle_tpu.inference import Config
    with pytest.raises(ValueError, match="not supported"):
        Config().enable_continuous_batching(kv_dtype="fp5")
    assert "fp8_e4m3" in KV_DTYPES and "int8" in KV_DTYPES


def test_kv_bytes_per_token_fp8_and_summaries():
    def kv(**kw):
        return PagedKVCache(2, 4, 8, num_blocks=8, block_size=4,
                            max_slots=2, max_blocks_per_slot=4, **kw)
    fp32 = kv()
    f8 = kv(kv_dtype="fp8_e4m3")
    assert fp32.kv_bytes_per_token == 2 * 2 * 4 * 8 * 4      # 512
    # fp8: 1 B payload + 4 B fp32 scale per head entry
    assert f8.kv_bytes_per_token == 2 * 2 * (4 * 8 * 1 + 4 * 4)
    assert f8.kv_bytes_per_token * 1.9 <= fp32.kv_bytes_per_token
    # summaries add the per-block min+max rows amortized per token
    s = kv(summaries=True)
    assert s.kv_bytes_per_token == fp32.kv_bytes_per_token \
        + 2 * (2 * 4 * 8 * 4) // 4
    assert str(f8.k_pool.dtype) == "float8_e4m3fn"
    assert f8.quantized and f8.k_scale is not None


def test_cow_and_transport_carry_summaries_and_fp8():
    import jax.numpy as jnp
    kv = PagedKVCache(2, 2, 8, num_blocks=10, block_size=4,
                      max_slots=2, max_blocks_per_slot=4,
                      kv_dtype="fp8_e4m3", summaries=True)
    assert kv.ensure_capacity(0, 8)
    blocks = kv.slot_blocks(0)
    rng = np.random.RandomState(3)
    kv.k_pool = jnp.asarray(np.clip(
        rng.randn(*kv.k_pool.shape) * 50, -440, 440).astype(
        np.float32)).astype(kv.k_pool.dtype)
    kv.k_sum_min = jnp.asarray(
        rng.randn(*kv.k_sum_min.shape).astype(np.float32))
    kv.k_sum_max = kv.k_sum_min + 1.0
    # CoW copies the summary rows with the payload
    src = blocks[0]
    assert kv.cow_block(0, 0)
    dst = kv.slot_blocks(0)[0]
    np.testing.assert_array_equal(np.asarray(kv.k_sum_min[:, dst]),
                                  np.asarray(kv.k_sum_min[:, src]))
    np.testing.assert_array_equal(
        np.asarray(kv.k_pool[:, dst], np.float32),
        np.asarray(kv.k_pool[:, src], np.float32))
    # export -> import round-trips payload + scales + summaries
    # bit-exactly into a second pool
    ids = kv.slot_blocks(0)
    arrays = kv.export_blocks(ids)
    assert len(arrays) == 6          # k, v, k_scale, v_scale, min, max
    kv2 = PagedKVCache(2, 2, 8, num_blocks=10, block_size=4,
                       max_slots=2, max_blocks_per_slot=4,
                       kv_dtype="fp8_e4m3", summaries=True)
    got = kv2.allocator.alloc(len(ids))
    kv2.import_blocks(got, arrays)
    np.testing.assert_array_equal(
        np.asarray(kv2.k_pool[:, got], np.float32),
        np.asarray(kv.k_pool[:, ids], np.float32))
    np.testing.assert_array_equal(np.asarray(kv2.k_sum_min[:, got]),
                                  np.asarray(kv.k_sum_min[:, ids]))
    assert kv.kv_meta()["summaries"] and kv.kv_meta()["kv_dtype"] \
        == "fp8_e4m3"
    # geometry guard: a summary-less fleet refuses the extra arrays
    kv3 = PagedKVCache(2, 2, 8, num_blocks=10, block_size=4,
                       max_slots=2, max_blocks_per_slot=4,
                       kv_dtype="fp8_e4m3")
    got3 = kv3.allocator.alloc(len(ids))
    with pytest.raises(ValueError, match="payload"):
        kv3.import_blocks(got3, arrays)


# ------------------------------------------------------ engine contracts


class TestSparseEngine:
    def test_full_coverage_token_identical_one_compile(
            self, model, prompts, _metrics):
        dense = _engine(ServingEngine, model)
        ref = dense.generate_batch(prompts, max_new_tokens=6)
        c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
        # max_seq_len 48 / block 4 = 12 blocks; B=12 covers every slot
        sp = _engine(ServingEngine, model, sparse_blocks=12)
        assert sp.generate_batch(prompts, max_new_tokens=6) == ref
        assert pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0 == 1
        assert sp.sparse_skip_ratio() == 0.0
        assert sp.kv.blocks_in_use == 0

    def test_full_coverage_with_speculation(self, model, prompts):
        dense = _engine(ServingEngine, model, draft_k=2)
        ref = dense.generate_batch(prompts, max_new_tokens=6)
        sp = _engine(ServingEngine, model, draft_k=2, sparse_blocks=12)
        assert sp.generate_batch(prompts, max_new_tokens=6) == ref

    def test_tp2_sparse_matches_tp1(self, model, prompts, _metrics):
        for B in (12, 2):
            ref = _engine(ServingEngine, model,
                          sparse_blocks=B).generate_batch(
                prompts, max_new_tokens=6)
            c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
            tp = _engine(TPServingEngine, model, tensor_parallel=2,
                         sparse_blocks=B)
            assert tp.generate_batch(prompts, max_new_tokens=6) == ref
            assert pm.JIT_COMPILES.labels(
                STEP_FN_NAME).value - c0 == 1

    def test_tp2_sparse_speculative_matches_tp1(self, model, prompts):
        """The cell the score-psum ordering bug hid in: TP=2 +
        speculation (K > 1) + REAL sparsity (B < allocated). The psum
        over mp must happen before the max over the group's K queries
        — max_k(a_k + b_k) != max_k(a_k) + max_k(b_k) when different
        queries achieve each shard's maximum, so the reversed order
        makes TP=2 select (and emit) different tokens than TP=1."""
        ref = _engine(ServingEngine, model, draft_k=2, sparse_blocks=2,
                      sparse_recent=2).generate_batch(
            prompts, max_new_tokens=8)
        tp = _engine(TPServingEngine, model, tensor_parallel=2,
                     draft_k=2, sparse_blocks=2, sparse_recent=2)
        assert tp.generate_batch(prompts, max_new_tokens=8) == ref

    def test_sparse_preemption_parity(self, model, prompts):
        """A sparse engine under block pressure (preemptions forced)
        still matches its unconstrained twin: summaries reset on the
        offset-0 rewrite, so reused blocks never leak a previous
        owner's statistics into the scorer."""
        roomy = _engine(ServingEngine, model, sparse_blocks=12)
        ref = roomy.generate_batch(prompts, max_new_tokens=6)
        tight = _engine(ServingEngine, model, sparse_blocks=12,
                        num_blocks=10)
        assert tight.generate_batch(prompts, max_new_tokens=6) == ref
        assert tight.scheduler.preemption_count > 0

    def test_sparse_pallas_interpret_matches_oracle(
            self, model, prompts, monkeypatch):
        """The shortened tables + compacted positions through the REAL
        scalar-prefetch Pallas kernels (interpret mode) serve the same
        tokens as the XLA gather oracle."""
        monkeypatch.setenv("PADDLE_TPU_PAGED_PALLAS", "0")
        ref = _engine(ServingEngine, model, sparse_blocks=3,
                      sparse_recent=1).generate_batch(
            prompts, max_new_tokens=6)
        monkeypatch.delenv("PADDLE_TPU_PAGED_PALLAS")
        monkeypatch.setattr(pa, "_INTERPRET", True)
        out = _engine(ServingEngine, model, sparse_blocks=3,
                      sparse_recent=1).generate_batch(
            prompts, max_new_tokens=6)
        assert out == ref

    def test_sparse_skip_accounting(self, model):
        rng = np.random.RandomState(11)
        long_prompts = [rng.randint(1, 211, 36).tolist()
                        for _ in range(2)]
        sp = _engine(ServingEngine, model, sparse_blocks=1,
                     sparse_recent=1)
        sp.generate_batch(long_prompts, max_new_tokens=6)
        assert sp.sparse_table_width == 3
        assert sp.sparse_candidate_blocks > sp.sparse_selected_blocks
        assert 0.0 < sp.sparse_skip_ratio() < 1.0

    def test_sparse_knob_validation(self, model):
        with pytest.raises(ValueError, match="sparse_blocks"):
            _engine(ServingEngine, model, sparse_blocks=0)


class TestFp8Engine:
    def test_fp8_agreement_and_sizing(self, model, prompts,
                                      _metrics):
        ref = _engine(ServingEngine, model).generate_batch(
            prompts, max_new_tokens=6)
        c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
        f8 = _engine(ServingEngine, model, kv_dtype="fp8_e4m3")
        out = f8.generate_batch(prompts, max_new_tokens=6)
        assert pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0 == 1
        total = sum(len(o) for o in ref)
        agree = sum(a == b for x, y in zip(ref, out)
                    for a, b in zip(x, y))
        # e4m3 noise on this tiny random model: most tokens agree
        # (the hard >= 99% bound lives on the smoke's needle workload)
        assert agree / total >= 0.9
        assert f8.kv.kv_bytes_per_token * 1.9 \
            <= _engine(ServingEngine, model).kv.kv_bytes_per_token
        assert f8.kv.blocks_in_use == 0

    def test_fp8_deterministic_under_preemption(self, model, prompts):
        roomy = _engine(ServingEngine, model, kv_dtype="fp8_e4m3")
        ref = roomy.generate_batch(prompts, max_new_tokens=6)
        tight = _engine(ServingEngine, model, kv_dtype="fp8_e4m3",
                        num_blocks=10)
        assert tight.generate_batch(prompts, max_new_tokens=6) == ref
        assert tight.scheduler.preemption_count > 0

    def test_fp8_pallas_interpret_matches_oracle(self, model, prompts,
                                                 monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PAGED_PALLAS", "0")
        ref = _engine(ServingEngine, model,
                      kv_dtype="fp8_e4m3").generate_batch(
            prompts, max_new_tokens=6)
        monkeypatch.delenv("PADDLE_TPU_PAGED_PALLAS")
        monkeypatch.setattr(pa, "_INTERPRET", True)
        out = _engine(ServingEngine, model,
                      kv_dtype="fp8_e4m3").generate_batch(
            prompts, max_new_tokens=6)
        assert out == ref

    def test_fp8_speculation_identity(self, model, prompts):
        ref = _engine(ServingEngine, model,
                      kv_dtype="fp8_e4m3").generate_batch(
            prompts, max_new_tokens=6)
        spec = _engine(ServingEngine, model, kv_dtype="fp8_e4m3",
                       draft_k=2)
        assert spec.generate_batch(prompts, max_new_tokens=6) == ref


# ------------------------------------------------------- tuner coverage


def test_sparse_and_fp8_buckets_registered(model):
    sp = _engine(ServingEngine, model, sparse_blocks=2)
    kernels = [k for k, _, _ in sp._kernel_buckets]
    assert "paged_sparse" in kernels and "paged_ragged" in kernels
    (_, bucket, dt) = [k for k in sp._kernel_buckets
                       if k[0] == "paged_sparse"][0]
    assert bucket[-1] >= sp.sparse_table_width    # pow2 of the width
    f8 = _engine(ServingEngine, model, kv_dtype="fp8_e4m3")
    assert all(d == "float8_e4m3fn" for _, _, d in f8._kernel_buckets)


def test_tune_paged_sparse_search():
    res = pa.tune_paged_sparse(4, 1, 2, 16, 4, 3, persist=False,
                               budget_s=5)
    assert res.config["kv_blocks"] in (4, 8, 16)
    assert res.tried >= 1


# --------------------------------------------------------- smoke wiring


def _load_tool(name):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_longctx_smoke_tool(capsys):
    """tools/longctx_smoke.py is the tier-1 CI contract: full-coverage
    identity, >= 99% agreement at >= 50% measured skip on the needle
    workload, fp8 >= 1.9x equal-HBM residency, zero leaks after
    evict_all, one compile under the watchdog, and the new metric
    names in the dump."""
    pm.REGISTRY.reset()
    was = pm._enabled
    mod = _load_tool("longctx_smoke")
    try:
        rc = mod.main()
        out = capsys.readouterr().out
        assert rc == 0
        assert "paddle_tpu_serving_kv_blocks_skipped_total" in out
        assert "paddle_tpu_serving_sparse_attention_ratio" in out
    finally:
        pm.REGISTRY.reset()
        if not was:
            pm.disable()


def test_tpu_tile_validate_refuses_cpu(capsys):
    """Off-TPU, without --rehearse, the tile validator FAILS: it
    validated nothing, and a zero exit would read as a pass."""
    mod = _load_tool("tpu_tile_validate")
    assert mod.main([]) == 1
    assert "nothing validated" in capsys.readouterr().err


def test_tpu_tile_validate_matrix_interpret(capsys):
    """The validator's kernel matrix itself stays runnable (API drift
    guard): in interpret mode every cell must pass its oracle, so the
    real-TPU run can only fail for DEVICE reasons — and the output
    says it was a rehearsal."""
    mod = _load_tool("tpu_tile_validate")
    assert mod.main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    assert "CPU REHEARSAL" in out
    assert "FAIL" not in out and "paged_sparse int8" in out
