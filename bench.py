"""Multi-config training benchmark (BASELINE.md configs 1-5).

Headline metric (driver contract, ONE JSON line): GPT-350M-class causal-LM
training tokens/sec/chip, vs_baseline = tokens_per_sec / 10_000 (published
Megatron-era V100 number for a 345M GPT-2: ~9-10k tokens/sec fp16 — 1.0
means V100 parity). The `extras` field carries the other BASELINE configs
(ResNet-50 imgs/sec, BERT-base+LAMB seqs/sec, LeNet fit steps/sec,
Wide&Deep PS examples/sec) each with an approximate MFU against the
v5e chip's 197 TFLOP/s bf16 peak, so the headline can't flatter
(VERDICT r1 weak #9).

Timing method: inputs are device-resident (one transfer), N steps are
chained through donated params, and ONE jax.device_get of the final loss
is the barrier: it waits for the whole chain, and a host fetch per step
would stall async dispatch.

A soft time budget drops remaining configs (headline always runs first)
so the driver's harness timeout can't truncate the JSON output.
"""
import json
import time

import numpy as np

PEAK_FLOPS = 197e12  # v5e bf16 peak per chip
BUDGET_S = 555.0     # soft wall-clock budget for the whole suite

_t_start = time.time()


def _budget_left():
    return BUDGET_S - (time.time() - _t_start)


# ----------------------------------------------------------------- gpt


def bench_gpt(on_tpu):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.hybrid_gpt import GPTConfig, HybridGPT

    dev = jax.devices()[0]
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, seq_len=1024, d_model=1024,
                        n_heads=16, n_layers=24, dp=1, pp=1, mp=1,
                        micro_batches=1, remat=True, zero_stage=0,
                        # r5 levers (docs/gpt_perf_analysis.md): keep the
                        # splash kernel's (out, lse) residuals across the
                        # block remat, fused bf16 CE (chunked x4 for the
                        # freed logits memory), bf16 grads w/ f32 master
                        remat_policy="save_splash_residuals",
                        fused_ce=True, ce_seq_chunks=4, bf16_grads=True,
                        compute_dtype=jnp.bfloat16)
        batch, iters = 32, 12
    else:
        cfg = GPTConfig(vocab_size=1024, seq_len=128, d_model=128,
                        n_heads=4, n_layers=2, dp=1, pp=1, mp=1,
                        micro_batches=1, remat=False, zero_stage=0,
                        compute_dtype=jnp.float32)
        batch, iters = 4, 3

    trainer = HybridGPT(cfg, devices=[dev])
    params, opt = trainer.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len)),
                      jnp.int32)
    lab = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len)),
                      jnp.int32)
    # compile + 2 warm steps, so the first post-compile dispatches stay
    # out of the timed window.
    # (A K-step grouped timed window via trainer.train_many measured
    # SLOWER — 39.0k vs 39.4k tok/s: the scan-carried param/opt state
    # costs more than the 12 saved dispatches. Per-step stays.)
    for w in range(3):
        params, opt, loss = trainer.train_step(params, opt, tok, lab,
                                               step_num=w + 1)
        float(jax.device_get(loss))

    # min-of-k timed windows (r6 BASELINE.md host-variance hardening,
    # extended to this lane per ISSUE 7): a host-load spike inside a
    # single window is indistinguishable from a code regression
    step_num = 4
    best = float("inf")
    final_loss = None
    for k in range(3 if on_tpu else 2):
        t0 = time.perf_counter()
        for i in range(iters):
            params, opt, loss = trainer.train_step(params, opt, tok, lab,
                                                   step_num=step_num)
            step_num += 1
        final_loss = float(jax.device_get(loss))
        best = min(best, time.perf_counter() - t0)
        if k and _budget_left() < 300:
            break
    assert np.isfinite(final_loss)

    toks = batch * cfg.seq_len * iters
    tps = toks / best
    from paddle_tpu.profiler import metrics as _metrics
    if _metrics._enabled:
        _metrics.TOKENS_PER_SEC.set(tps)
    # approx train FLOPs/token: 6*N (fwd+bwd weight flops) + causal
    # attention 6*L*S*d
    d, L, S, V = cfg.d_model, cfg.n_layers, cfg.seq_len, cfg.vocab_size
    n_params = 12 * L * d * d + V * d + S * d
    flops_tok = 6 * n_params + 6 * L * S * d
    mfu = tps * flops_tok / PEAK_FLOPS
    step_seconds = best / iters
    return tps, mfu, _tuner_plan_extra(mfu if on_tpu else None,
                                       step_seconds if on_tpu else None)


def _tuner_plan_extra(measured_mfu, measured_step_seconds):
    """auto_tuner placement-search extra (ISSUE 7 acceptance: record the
    tuner's predicted MFU NEXT TO the measured one). The search prices
    the GPT-350M bench config on the 8-chip v5e-ish ClusterSpec;
    calibration uses THIS run's measured single-chip step on TPU, or
    the recorded BENCH_r05 measurement (MFU 0.456) on CPU where the
    tiny smoke config says nothing about the 350M model."""
    try:
        from paddle_tpu.parallel.auto_tuner import (ClusterSpec,
                                                    CostModel, ModelSpec,
                                                    Strategy, tune)
        mspec = ModelSpec(n_layers=24, d_model=1024, seq_len=1024,
                          vocab_size=50304, global_batch=32, n_heads=16)
        single = Strategy()
        meas = {"strategy": single}
        if measured_step_seconds:
            meas["step_seconds"] = measured_step_seconds
            calib_src = "this_run"
        else:
            meas["mfu"] = 0.456          # BENCH_r05 measured single-chip
            calib_src = "bench_r05"
        plan = tune(mspec, cluster=ClusterSpec(), measurements=meas)
        cm = CostModel(plan.cluster)
        pred_single = cm.predicted_mfu(mspec, single)
        return {
            "metric": "auto_tuner_plan",
            "chosen_config": plan.strategy.as_hybrid_configs(),
            "predicted_mfu_8chip": round(plan.predicted_mfu, 4),
            "predicted_step_seconds_8chip": round(plan.step_time, 5),
            "predicted_single_chip_mfu": round(pred_single, 4),
            "measured_single_chip_mfu": (round(measured_mfu, 4)
                                         if measured_mfu else None),
            "calibration_source": calib_src,
            "calibrated_mxu_efficiency": round(
                plan.cluster.mxu_efficiency, 4),
        }
    except Exception as e:  # noqa: BLE001
        return {"metric": "auto_tuner_plan",
                "error": f"{type(e).__name__}: {e}"}


# -------------------------------------------------------------- resnet


def bench_resnet50():
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.vision.models import resnet50

    net = resnet50(num_classes=1000)
    amp.decorate(net, level="O2")
    model = paddle.Model(net)
    opt = paddle.optimizer.Momentum(
        0.1, parameters=model.parameters(), weight_decay=1e-4)
    model.prepare(opt, paddle.nn.CrossEntropyLoss())

    B, H = 128, 224
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(jnp.asarray(rng.rand(B, 3, H, H), jnp.float32))
    y = paddle.to_tensor(jnp.asarray(rng.randint(0, 1000, (B, 1)),
                                     jnp.int32))
    float(x._data.sum())  # input transfer done

    losses, _ = model._train_batch_inner([x], [y])  # compile
    float(jax.device_get(losses[0]._data))
    assert model._jit_ok, "ResNet-50 compiled path fell back to eager"

    # min-of-2 timed windows (BASELINE.md host-variance hardening,
    # extended to this lane per ISSUE 7)
    iters = 20
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        last = None
        for _i in range(iters):
            losses, _ = model._train_batch_inner([x], [y])  # lazy loss
            last = losses[0]
        float(jax.device_get(last._data))  # single honest barrier
        best = min(best, time.perf_counter() - t0)
        if _budget_left() < 120:
            break
    ips = B * iters / best
    # ResNet-50@224 fwd = 4.1 GMACs = 8.2 GFLOPs (2*MAC, same convention
    # as the GPT/BERT 6N formulas); train ~3x fwd. The r1/r2 benches used
    # 4.1e9 here — counting MACs as FLOPs — and so understated MFU 2x.
    flops_img = 3 * 8.2e9
    return ips, ips * flops_img / PEAK_FLOPS


# ---------------------------------------------------------------- bert


def bench_bert():
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.models import (bert_base, BertForPretraining,
                                   BertPretrainingCriterion)

    bert = bert_base()
    net = BertForPretraining(bert)
    # AMP O2 like the ResNet config (and the reference's fp16 BERT
    # pretrain recipe); r2-r4 ran this config in full f32 — that plus
    # threefry dropout RNG (now rbg on TPU, core/random.py _use_rbg)
    # was the 27.6%-MFU plateau. At S=128 the XLA attention path beats
    # the splash kernel (854 vs 754 seqs/s measured), so the masked
    # splash routing matters for long-S/eval, not this config.
    amp.decorate(net, level="O2")
    crit = BertPretrainingCriterion(bert.vocab_size)
    model = paddle.Model(net)
    opt = paddle.optimizer.Lamb(learning_rate=1e-3,
                                lamb_weight_decay=0.01,
                                parameters=net.parameters())
    model.prepare(opt, crit)

    B, S = 64, 128
    rng = np.random.RandomState(0)
    tok = rng.randint(1, bert.vocab_size, (B, S))
    mlm = rng.randint(0, bert.vocab_size, (B, S))
    mlm[rng.rand(B, S) > 0.15] = -1
    nsp = rng.randint(0, 2, (B,))
    tok_t = paddle.to_tensor(jnp.asarray(tok, jnp.int32))
    mlm_t = paddle.to_tensor(jnp.asarray(mlm, jnp.int32))
    nsp_t = paddle.to_tensor(jnp.asarray(nsp, jnp.int32))
    float(tok_t._data.sum())

    losses, _ = model._train_batch_inner([tok_t], [mlm_t, nsp_t])
    float(jax.device_get(losses[0]._data))
    assert model._jit_ok, "BERT compiled path fell back to eager"

    iters = 20
    t0 = time.perf_counter()
    last = None
    for _ in range(iters):
        losses, _ = model._train_batch_inner([tok_t], [mlm_t, nsp_t])
        last = losses[0]
    float(jax.device_get(last._data))
    dt = time.perf_counter() - t0
    sps = B * iters / dt
    d, L = bert.hidden_size, bert.num_layers
    n_params = 12 * L * d * d + bert.vocab_size * d
    flops_seq = (6 * n_params + 12 * L * S * d) * S
    return sps, sps * flops_seq / PEAK_FLOPS


# --------------------------------------------------------------- lenet


def bench_lenet():
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import LeNet
    from paddle_tpu.vision.datasets import MNIST

    model = paddle.Model(LeNet())
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    # 32-step dispatch groups: per-dispatch host latency (not measured
    # on the direct backend) would otherwise dominate a sub-ms model
    model._fit_group_max = 32
    ds = MNIST(mode="train", synthetic_size=4096)
    # device-cached input pipeline: MNIST fits in HBM, so epochs past
    # the first stream with zero host->device transfers (the TPU-first
    # input pattern)
    from paddle_tpu.io import DataLoader, DeviceCacheLoader
    loader = DeviceCacheLoader(DataLoader(ds, batch_size=64,
                                          shuffle=True))
    fit_kw = dict(epochs=1, batch_size=64, verbose=0, log_freq=32)
    model.fit(loader, **fit_kw)  # warm/compile + fill the device cache
    # min-of-3 epochs: this config is fit-loop/host bound and the
    # BASELINE.md r4->r5 A/B showed host-load spikes swing it 3x+ while
    # real deltas were <1% — a single timed epoch is host-noise
    # roulette (same hardening the int8 B=1 ratio got in r5)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        model.fit(loader, **fit_kw)
        best = min(best, time.perf_counter() - t0)
        if _budget_left() < 90:
            break
    steps = 4096 // 64
    return steps / best, None  # steps/sec (fit-loop bound, no MFU)


# ----------------------------------------------------------- wide&deep


def _load_wd_example():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "wd_example",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "examples", "5_wide_deep_ps.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_wide_deep():
    """Config 5: embedding pull -> dense train -> push through the native
    PS engine (C++ sharded tables), examples/sec + training AUC."""
    mod = _load_wd_example()
    if not hasattr(mod, "run_bench"):
        return None, None
    # min-of-2 full runs (host-variance hardening per BASELINE.md:
    # the r5 "-11%" was host load, not code): keep the faster
    # run's examples/sec and its AUC, budget permitting
    eps, auc = mod.run_bench()
    if _budget_left() > 120:
        eps2, auc2 = mod.run_bench()
        if eps2 > eps:
            eps, auc = eps2, auc2
    return eps, None, {"metric": "wide_deep_train_auc",
                       "value": round(auc, 4), "unit": "auc"}


def bench_graph_sage():
    """GraphSAGE over the sharded graph engine (ps/graph: hash-
    partitioned adjacency co-located with the embedding shards,
    per-hop frontier dedup, deterministic fixed-shape sampling, bundle
    prefetch, stream-mode feature engine) with RPC-backed features, vs
    the plain sequential order of operations (raw-frontier sampling —
    every duplicate node re-sampled each hop — plus full raw-bundle
    RemoteSparseTable pull/push per batch: no dedup, no cache, no
    prefetch) against the same localhost parameter servers. The two
    lanes produce bit-identical training (the sampler is pure per
    (node, seed)). CPU-capable; the driver contract is engine >= 1.2x
    sequential.

    On the 1-core CPU box thread overlap conserves CPU, so the honest
    speedup source is WORK REDUCTION — above all the frontier dedup:
    the bundle is ~71% duplicate keys (power-law hubs + mask padding)
    and shard-side sampling cost scales with the edges gathered for
    queried rows, so the naive lane pays ~5x the sampling work; the
    raw-bundle wire path adds more (docs/GRAPH.md has the
    decomposition and the expected multi-core/TPU overlap effect)."""
    import numpy as np

    from paddle_tpu.ps import (GraphEngine, HeterEmbeddingEngine,
                               ShardedGraphTable)
    from paddle_tpu.ps.graph import (SageTrainer, contrastive_batches,
                                     make_power_law_graph)
    from paddle_tpu.ps.service import (PSClient, PSServer,
                                       RemoteSparseTable)

    dim, bsz, steps, nodes = 64, 128, 16, 20000
    src, dst = make_power_law_graph(num_nodes=nodes, avg_degree=8,
                                    seed=3)
    ids = np.arange(1, nodes + 1, dtype=np.uint64)
    batches = contrastive_batches(src, dst, ids, batch_size=bsz,
                                  steps=steps, seed=5)

    from paddle_tpu.ps.graph.engine import GraphEngine as _GE

    class NaiveGraphEngine(_GE):
        """Plain order of operations: sample the RAW frontier each hop
        (no per-hop np.unique — duplicate nodes are sampled again, as
        a straightforward per-node loop would). Output is BIT-IDENTICAL
        to the deduped engine (the sampler is pure per (node, seed));
        the dedup is pure work-reduction, which is what this lane
        measures the absence of."""

        def _sample_hops(self, seeds, batch_seed):
            neighbors, masks = [], []
            uniqs = [np.unique(seeds)]
            frontier = seeds
            raw = 0
            for h, f in enumerate(self.fanouts):
                raw += frontier.size
                nb, mk = self.graph.sample_neighbors(
                    frontier, f,
                    seed=(batch_seed + h) & 0xFFFFFFFFFFFFFFFF)
                neighbors.append(nb)
                masks.append(mk)
                frontier = nb.reshape(-1)
                uniqs.append(np.unique(frontier))
            node_union = np.unique(np.concatenate(uniqs))
            return (tuple(neighbors), tuple(masks), node_union, raw,
                    raw)

    class DirectFeatures:
        """Plain order of operations: sync full-raw-bundle RPC pull
        and push, duplicates and all."""

        def __init__(self, table):
            self.table = table
            self.dim = table.dim

        def pull(self, keys, train=False, use_prefetch=False):
            return self.table.pull(np.asarray(keys).reshape(-1))

        def push(self, keys, grads):
            return self.table.push(np.asarray(keys).reshape(-1),
                                   grads)

        def flush(self):
            return self

        def state(self):
            return {"direct": True}

    def make_lane(pipelined):
        servers = [PSServer() for _ in range(2)]
        for s in servers:
            s.register_sparse_table(0, dim=dim, sgd_rule="sgd",
                                    learning_rate=0.5)
            s.run(background=True)
        client = PSClient([f"127.0.0.1:{s.port}" for s in servers])
        table = RemoteSparseTable(client, 0, dim=dim)
        # stream-mode features (bounded-staleness async-SGD, the
        # wide_deep_heter bench lane's mode): resident rows accumulate
        # merged deltas in the cache and write back on eviction/
        # staleness/flush instead of strict's synchronous push +
        # re-read round trip per batch. The parity gates
        # (tools/graph_smoke.py, tests) run strict.
        feats = (HeterEmbeddingEngine(table, cache_capacity=16384,
                                      mode="stream", staleness_bound=8,
                                      prefetch=True)
                 if pipelined else DirectFeatures(table))
        graph = ShardedGraphTable(num_shards=2)
        graph.add_edges(src, dst)
        cls = GraphEngine if pipelined else NaiveGraphEngine
        eng = cls(graph, features=feats, fanouts=(10, 5),
                  mode="strict", base_seed=7,
                  prefetch=pipelined)
        tr = SageTrainer(eng, hidden_dims=(32, 16), lr=0.5,
                         param_seed=0)

        def one_pass():
            t0 = time.perf_counter()
            for i, (c, p, n) in enumerate(batches):
                tr.train_step(c, p, n)
                if pipelined and i + 1 < steps:
                    tr.prefetch(*batches[i + 1])
            eng.flush()
            return time.perf_counter() - t0

        def close():
            st = eng.state()
            eng.close()
            client.close()
            for s in servers:
                s.stop()
            return st
        return one_pass, close

    # Both lanes stay live and alternate timed passes so host drift
    # over the lane's window hits them equally (the serving lanes'
    # best-of-3 interleaved discipline). A flushed lane is quiescent
    # between passes, so the idle one doesn't steal the timed one's
    # core.
    direct_pass, direct_close = make_lane(False)
    engine_pass, engine_close = make_lane(True)
    direct_pass()                           # warmup/compile
    engine_pass()
    dts_e, dts_d = [], []
    for _ in range(3):
        dts_e.append(engine_pass())
        dts_d.append(direct_pass())
    direct_close()
    st = engine_close()
    direct_eps = bsz * steps / min(dts_d)
    engine_eps = bsz * steps / min(dts_e)
    return {"metric": "graph_sage_examples_per_sec",
            "value": round(engine_eps, 1), "unit": "examples/sec",
            "direct_examples_per_sec": round(direct_eps, 1),
            "speedup_vs_direct": round(engine_eps / direct_eps, 3),
            "dedup_ratio": st["dedup_ratio"],
            "prefetch": st["prefetch"],
            "fanouts": st["fanouts"],
            "graph_nodes": st["graph_nodes"],
            "graph_edges": st["graph_edges"]}


def bench_wide_deep_heter():
    """HeterPS-style embedding engine (ps/heter: hot-ID cache +
    prefetch pipeline + dedup-merged background push) vs the direct
    RemoteSparseTable lane, both against real parameter servers over
    localhost RPC on a zipf key stream. CPU-capable; the driver
    contract is engine >= 1.3x direct."""
    engine_eps, direct_eps, stats = _load_wd_example().run_bench_heter()
    return {"metric": "wide_deep_heter_examples_per_sec",
            "value": round(engine_eps, 1), "unit": "examples/sec",
            "direct_examples_per_sec": round(direct_eps, 1),
            "speedup_vs_direct": round(engine_eps / direct_eps, 3),
            "cache_hit_ratio": stats["cache_hit_ratio"],
            "dedup_ratio": stats["dedup_ratio"],
            "prefetch": stats["prefetch"]}


# -------------------------------------------------------------- decode


def bench_decode():
    """LLM serving decode: GPT2-350M-class FusedMultiTransformer stack,
    weight-only int8, fixed-shape KV cache, compiled scan decode
    (reference capability: `fused_multi_transformer_op.cu` + cache_kvs).
    tokens/sec = generated tokens (prefill amortized in)."""
    import jax
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTForGeneration

    def _decode_tps(m, B, T=128, reps=1):
        P = 128
        rng = np.random.RandomState(0)
        ids = Tensor(rng.randint(0, 50304, (B, P)).astype(np.int32))
        out, _ = m.generate(ids, max_new_tokens=T)  # compile + warm
        np.asarray(out.numpy())
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out, _ = m.generate(ids, max_new_tokens=T)
            np.asarray(out.numpy())
            best = min(best, time.perf_counter() - t0)
        return B * T / best

    def run(weight_only, B, T=128, reps=1):
        m = GPTForGeneration(vocab_size=50304, hidden_size=1024,
                             num_layers=24, num_attention_heads=16,
                             max_position_embeddings=2048,
                             compute_dtype="bfloat16",
                             weight_only=weight_only)
        m.eval()
        return m, _decode_tps(m, B, T, reps)

    m64, tps = run(True, 64)
    # the weight-only-int8 REGIME win: B=1 serving is
    # weight-bandwidth-bound (int8 halves HBM reads); at B>=8 the
    # KV cache + per-step kernel latency dominate and int8 ~ bf16
    # (docs/decode_int8_analysis.md). This extra must land in the
    # driver run (VERDICT r4 #4) — only a FAILURE (not the budget)
    # may drop it, and failure must not lose the headline. Full
    # T=128 horizon: a shorter decode dilutes the ratio with the
    # (identical) prefill cost — measured 1.10x at T=64 vs 1.26x+
    # at T=128.
    try:
        # min-of-5 per side: the B=1 ratio is dispatch-latency-bound
        # and a single host-load spike measured it at 1.03x (vs the
        # quiet-machine 1.24-1.34x)
        i8 = _decode_tps(m64, 1, reps=5)  # same weights, new batch
        del m64
        import gc
        gc.collect()
        _, b16 = run(False, 1, reps=5)
        extra = {"metric": "gpt2_350m_decode_int8_speedup_b1",
                 "value": round(i8 / b16, 3), "unit": "x vs bf16"}
    except Exception as e:  # noqa: BLE001
        extra = {"metric": "gpt2_350m_decode_int8_speedup_b1",
                 "error": f"{type(e).__name__}: {e}"}
    return tps, None, extra  # bandwidth-bound; MFU not meaningful


def bench_decode_speculative():
    """ISSUE 3 extra: latency-bound decode with the scanned fused step
    and n-gram speculative verification, B=1 and B=8, on repetitive/
    greedy text (cyclic prompt pattern -> the prompt-lookup draft can
    actually land; acceptance is reported so the number can't hide a
    draft that never hits). tokens/sec counts GENERATED tokens over the
    full generate() wall time, same convention as bench_decode. The r5
    B=1 bf16 baseline for this config was 465 tok/s with the unrolled
    decode step."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTForGeneration

    m = GPTForGeneration(vocab_size=50304, hidden_size=1024,
                         num_layers=24, num_attention_heads=16,
                         max_position_embeddings=2048,
                         compute_dtype="bfloat16")
    m.eval()
    P, T = 128, 128
    pattern = np.arange(7, 23, dtype=np.int32)     # 16-token cycle

    def run(B, draft_k, reps=3):
        ids = Tensor(np.tile(pattern, (B, P // len(pattern))))
        kw = dict(max_new_tokens=T, draft_k=draft_k)
        out, _ = m.generate(ids, **kw)             # compile + warm
        np.asarray(out.numpy())
        best = float("inf")
        accept = None
        for _ in range(reps):
            t0 = time.perf_counter()
            out, _ = m.generate(ids, **kw)
            np.asarray(out.numpy())
            best = min(best, time.perf_counter() - t0)
            if draft_k:
                steps = m.last_accept_counts
                tot = sum(sum(s) for s in steps)
                accept = tot / max(1, sum(len(s) for s in steps))
        return B * T / best, accept

    b1_scan, _ = run(1, 0)             # scanned fused step, no drafts
    b1_spec, b1_acc = run(1, 7)
    extra = {
        "metric": "gpt2_350m_decode_speculative_detail",
        "b1_scan_tokens_per_sec": round(b1_scan, 1),
        "b1_spec_tokens_per_sec": round(b1_spec, 1),
        "b1_mean_accept": round(b1_acc, 2) if b1_acc else None,
        "b1_vs_r5_unrolled_465": round(max(b1_scan, b1_spec) / 465.0, 3),
    }
    if _budget_left() > 120:           # B=8 pair is two more compiles
        b8_scan, _ = run(8, 0)
        b8_spec, b8_acc = run(8, 7)
        extra.update(
            b8_scan_tokens_per_sec=round(b8_scan, 1),
            b8_spec_tokens_per_sec=round(b8_spec, 1),
            b8_mean_accept=round(b8_acc, 2) if b8_acc else None)
    else:
        extra["b8_skipped"] = "time budget"
    # headline = the SPECULATIVE number (the metric's name): a draft
    # path slower than plain scan must show up as a regression, not be
    # papered over by max(); the scan baseline and the best-of ratio
    # ride in the detail extra
    return b1_spec, None, extra


def bench_serving():
    """Continuous batching (paddle_tpu.serving) vs sequential
    one-request-at-a-time generation.py on the SAME synthetic Poisson
    request stream (tiny GPT — runs on CPU too). Driver contract:
    speedup_vs_sequential >= 2.0 sustained, mixed_step_compiles == 1
    across the whole run (admissions/evictions never retrace)."""
    import time as _time

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.profiler import metrics as pm
    from paddle_tpu.serving.batcher import next_pow2
    from paddle_tpu.serving.engine import ServingEngine, STEP_FN_NAME

    rng = np.random.RandomState(0)
    V, T_new, N = 1024, 16, 24
    m = GPTForGeneration(vocab_size=V, hidden_size=128, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32")
    m.eval()
    lens = rng.randint(4, 40, N)
    prompts = [rng.randint(1, V, int(n)).astype(np.int32) for n in lens]
    arrivals = np.cumsum(rng.exponential(0.002, N))  # Poisson stream
    arrivals -= arrivals[0]

    was_enabled = pm._enabled
    pm.enable()
    try:
        eng = ServingEngine(m, max_slots=8, block_size=16,
                            max_seq_len=128, cache_dtype="float32",
                            seed=0)
        # warm: compiles the ONE mixed step; the timed stream reuses it
        eng.generate_batch([prompts[0]], max_new_tokens=2)

        t0 = _time.perf_counter()
        pending = list(zip(prompts, arrivals))
        reqs = []
        while pending or eng.scheduler.has_work:
            now = _time.perf_counter() - t0
            while pending and pending[0][1] <= now:
                p, _ = pending.pop(0)
                reqs.append(eng.submit(p, T_new))
            if not eng.step() and pending:
                _time.sleep(max(0.0, pending[0][1]
                                 - (_time.perf_counter() - t0)))
        serve_wall = _time.perf_counter() - t0
        served_tokens = sum(len(r.output) for r in reqs)
        lat = sorted(r.finish_time - r.submit_time for r in reqs)
        compiles = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
        preempts = eng.scheduler.preemption_count
    finally:
        if not was_enabled:
            pm.disable()

    # sequential baseline: one generate() per request in arrival order,
    # started at max(arrival, previous finish); warm each prompt bucket
    # so neither side pays compiles inside the timed region
    for b in sorted({next_pow2(len(p)) for p in prompts}):
        m.generate(Tensor(np.ones((1, b), np.int32)),
                   max_new_tokens=T_new, cache_dtype="float32")
    t = 0.0
    finish = []
    for p, a in zip(prompts, arrivals):
        s0 = _time.perf_counter()
        out, _ = m.generate(Tensor(np.asarray(p)[None]),
                            max_new_tokens=T_new, cache_dtype="float32")
        np.asarray(out.numpy())
        dt = _time.perf_counter() - s0
        t = max(t, a) + dt
        finish.append(t)
    seq_tokens = N * T_new
    seq_tput = float(seq_tokens / (finish[-1] - arrivals[0]))
    serve_tput = float(served_tokens / serve_wall)
    return {
        "metric": "serving_continuous_batching",
        "value": round(serve_tput, 1), "unit": "tokens/sec",
        "sequential_tokens_per_sec": round(seq_tput, 1),
        "speedup_vs_sequential": round(serve_tput / seq_tput, 3),
        "p50_latency_s": round(lat[len(lat) // 2], 4),
        "p99_latency_s": round(lat[min(len(lat) - 1,
                                       int(len(lat) * 0.99))], 4),
        "requests": N, "mixed_step_compiles": int(compiles),
        "preemptions": int(preempts),
    }


def bench_serving_multitick(n_requests=16, t_new=65):
    """Device-resident multi-tick decode (ISSUE 18): the SAME Poisson
    stream served at ticks_per_dispatch 1, 4 and 8 — decode tokens/sec
    and inter-token p50/p99 vs N — plus a host-stall-share record for
    the async-device_get runtime (sync readback vs overlapped) at N=8.
    Driver contract: decode tok/s strictly improves N=1 -> N=8 (the
    host dispatch wall is the inter-token floor the while_loop
    removes), every engine compiles its mixed step exactly once, and
    outputs stay token-identical across N."""
    import time as _time

    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.profiler import metrics as pm
    from paddle_tpu.serving.engine import ServingEngine, STEP_FN_NAME

    rng = np.random.RandomState(0)
    V = 1024
    m = GPTForGeneration(vocab_size=V, hidden_size=128, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32")
    m.eval()
    lens = rng.randint(4, 40, n_requests)
    prompts = [rng.randint(1, V, int(n)).astype(np.int32)
               for n in lens]
    arrivals = np.cumsum(rng.exponential(0.002, n_requests))
    arrivals -= arrivals[0]

    def stream(eng):
        pending = list(zip(prompts, arrivals))
        reqs, seen, gaps = [], {}, []
        stall0 = eng.host_stall_total
        t0 = _time.perf_counter()
        while pending or eng.scheduler.has_work:
            now = _time.perf_counter() - t0
            while pending and pending[0][1] <= now:
                p, _ = pending.pop(0)
                reqs.append(eng.submit(p, t_new))
            if not eng.step() and pending:
                _time.sleep(max(0.0, pending[0][1]
                                 - (_time.perf_counter() - t0)))
                continue
            now = _time.perf_counter() - t0
            # inter-token gaps, dispatch-granular: a k-token harvest
            # contributes k gaps of (now - last)/k — the stream rate a
            # client consuming the staging buffer actually sees
            for r in reqs:
                i = id(r)
                have = len(r.output)
                last_n, last_t = seen.get(i, (0, None))
                if have > last_n:
                    if last_t is not None:
                        gaps += [(now - last_t) / (have - last_n)] \
                            * (have - last_n)
                    seen[i] = (have, now)
        wall = _time.perf_counter() - t0
        toks = sum(len(r.output) for r in reqs)
        gaps.sort()
        return {
            "outputs": [list(r.output) for r in reqs],
            "tok_s": toks / wall, "wall": wall,
            "itl_p50_ms": gaps[len(gaps) // 2] * 1e3 if gaps else 0.0,
            "itl_p99_ms": gaps[min(len(gaps) - 1,
                                   int(len(gaps) * 0.99))] * 1e3
            if gaps else 0.0,
            "stall_share": (eng.host_stall_total - stall0) / wall,
        }

    def build(n_ticks, multitick_async=True):
        eng = ServingEngine(m, max_slots=8, block_size=16,
                            max_seq_len=128, cache_dtype="float32",
                            seed=0, ticks_per_dispatch=n_ticks,
                            multitick_async=multitick_async)
        c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
        # warm: compiles the ONE mixed step (while_loop included);
        # the timed streams below reuse it
        eng.generate_batch([prompts[0]], max_new_tokens=2)
        return eng, int(pm.JIT_COMPILES.labels(STEP_FN_NAME).value
                        - c0)

    def serve_all(keys, passes=3):
        """Best-of-`passes` per engine, passes INTERLEAVED across the
        engines: the single-core harness drifts by 10-20% over seconds
        (enough to drown the dispatch-wall signal), and round-robin
        spreads any slow window over every N instead of sinking one."""
        engines = {k: build(*k) for k in keys}
        runs = {k: [] for k in keys}
        for _ in range(passes):
            for k in keys:
                runs[k].append(stream(engines[k][0]))
        out = {}
        for k in keys:
            best = max(runs[k], key=lambda r: r["tok_s"])
            if any(r["outputs"] != best["outputs"] for r in runs[k]):
                best["outputs"] = None  # nondeterminism across passes
            best["eng"], best["compiles"] = engines[k]
            out[k] = best
        return out

    was_enabled = pm._enabled
    pm.enable()
    try:
        keys = [(1, True), (4, True), (8, True), (8, False)]
        res = serve_all(keys)
        by_n = {n: res[(n, True)] for n in (1, 4, 8)}
        sync8 = res[(8, False)]
    finally:
        if not was_enabled:
            pm.disable()
    identical = all(by_n[n]["outputs"] == by_n[1]["outputs"]
                    for n in (4, 8))
    e8 = by_n[8]["eng"]
    return {
        "metric": "serving_multitick",
        "value": round(by_n[8]["tok_s"], 1), "unit": "tokens/sec",
        "decode_tok_s_by_n": {
            str(n): round(by_n[n]["tok_s"], 1) for n in (1, 4, 8)},
        "itl_p50_ms_by_n": {
            str(n): round(by_n[n]["itl_p50_ms"], 3)
            for n in (1, 4, 8)},
        "itl_p99_ms_by_n": {
            str(n): round(by_n[n]["itl_p99_ms"], 3)
            for n in (1, 4, 8)},
        "speedup_n8_vs_n1": round(by_n[8]["tok_s"]
                                  / by_n[1]["tok_s"], 3),
        "host_stall_share_sync": round(sync8["stall_share"], 4),
        "host_stall_share_async": round(by_n[8]["stall_share"], 4),
        "ticks_per_dispatch_mean_n8": round(
            e8.device_ticks_run / max(e8.dispatches_run, 1), 2),
        "early_exits_n8": dict(e8.early_exit_counts),
        "outputs_identical_across_n": bool(identical),
        "mixed_step_compiles": max(r["compiles"]
                                   for r in by_n.values()),
        "requests": n_requests,
    }


def bench_serving_spec_multitick(n_requests=8, t_new=64):
    """On-device speculation lane (ISSUE 19): draft_k=3 speculative
    decode INSIDE the ticks_per_dispatch=8 while_loop vs BOTH
    baselines it must beat — the same speculation at N=1 (host
    drafter, dispatch wall back) and no speculation at N=8 (loop
    without drafts). The tiny GPT is first fit for a few epochs on a
    synthetic copy corpus (repeated short motifs): prompt-lookup
    drafting pays off exactly when the model's own continuations copy
    local context (induction), and a random-weight model has none of
    that — its ~10% accept rate measures nothing but verify overhead.
    Prompts are the same short repeating motifs, so the n-gram
    drafter lands accepts; greedy decode keeps all three
    configurations token-identical, which the record asserts.
    Best-of-3 per engine, passes interleaved (same drift discipline
    as bench_serving_multitick). Driver contract: spec-N8 tok/s
    strictly above spec-N1 AND above nospec-N8, one mixed-step
    compile per engine, accept rate recorded."""
    import time as _time

    import paddle_tpu as paddle
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.models.gpt import (GPTForGeneration, GPTModel,
                                       GPTForPretraining,
                                       GPTPretrainingCriterion)
    from paddle_tpu.profiler import metrics as pm
    from paddle_tpu.serving.engine import ServingEngine, STEP_FN_NAME

    rng = np.random.RandomState(0)
    V = 1024
    paddle.seed(0)
    net = GPTForPretraining(GPTModel(vocab_size=V, hidden_size=128,
                                     num_layers=2,
                                     num_attention_heads=4,
                                     max_position_embeddings=512))
    trainer = paddle.Model(net)
    trainer.prepare(paddle.optimizer.AdamW(
        3e-3, parameters=trainer.parameters()),
        GPTPretrainingCriterion())
    crng = np.random.RandomState(1)
    seqs = []
    for _ in range(256):
        motif = crng.randint(1, V, int(crng.randint(2, 5)))
        seqs.append(np.tile(motif, 65 // len(motif) + 1)[:65])
    seqs = np.stack(seqs).astype(np.int32)
    trainer.fit(TensorDataset([seqs[:, :-1], seqs[:, 1:]]), epochs=4,
                batch_size=32, verbose=0)
    m = GPTForGeneration.from_pretraining(net)
    m.eval()
    prompts = []
    for _ in range(n_requests):
        motif = rng.randint(1, V, int(rng.randint(2, 5))).tolist()
        prompts.append((motif * (24 // len(motif) + 1))[:24])

    def build(draft_k, n_ticks):
        eng = ServingEngine(m, max_slots=8, block_size=16,
                            max_seq_len=128, cache_dtype="float32",
                            seed=0, draft_k=draft_k,
                            ticks_per_dispatch=n_ticks)
        c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
        eng.generate_batch([prompts[0]], max_new_tokens=2)  # warm
        return eng, int(pm.JIT_COMPILES.labels(STEP_FN_NAME).value
                        - c0)

    def run(eng):
        p0, a0 = eng.spec_proposed_total, eng.spec_accepted_total
        t0 = _time.perf_counter()
        outs = eng.generate_batch(prompts, max_new_tokens=t_new)
        wall = _time.perf_counter() - t0
        toks = sum(len(o) for o in outs)
        return {"outputs": outs, "tok_s": toks / wall, "wall": wall,
                "proposed": eng.spec_proposed_total - p0,
                "accepted": eng.spec_accepted_total - a0}

    was_enabled = pm._enabled
    pm.enable()
    try:
        keys = {"spec_n8": (3, 8), "spec_n1": (3, 1),
                "nospec_n8": (0, 8)}
        engines = {k: build(*v) for k, v in keys.items()}
        runs = {k: [] for k in keys}
        for _ in range(3):
            for k in keys:
                runs[k].append(run(engines[k][0]))
        best = {k: max(runs[k], key=lambda r: r["tok_s"])
                for k in keys}
    finally:
        if not was_enabled:
            pm.disable()
    identical = all(best[k]["outputs"] == best["spec_n1"]["outputs"]
                    for k in keys)
    # accept rate over ALL passes of the spec-N8 engine (per-pass
    # counts are small enough to be noisy)
    prop = sum(r["proposed"] for r in runs["spec_n8"])
    acc = sum(r["accepted"] for r in runs["spec_n8"])
    e8 = engines["spec_n8"][0]
    return {
        "metric": "serving_spec_multitick",
        "value": round(best["spec_n8"]["tok_s"], 1),
        "unit": "tokens/sec",
        "decode_tok_s": {k: round(best[k]["tok_s"], 1) for k in keys},
        "speedup_vs_spec_n1": round(best["spec_n8"]["tok_s"]
                                    / best["spec_n1"]["tok_s"], 3),
        "speedup_vs_nospec_n8": round(best["spec_n8"]["tok_s"]
                                      / best["nospec_n8"]["tok_s"],
                                      3),
        "accept_rate": round(acc / max(prop, 1), 4),
        "drafts_proposed": int(prop), "drafts_accepted": int(acc),
        "draft_k": 3,
        "speculation_mode_n8": e8.speculation_mode,
        "ticks_per_dispatch_mean_n8": round(
            e8.device_ticks_run / max(e8.dispatches_run, 1), 2),
        "outputs_identical": bool(identical),
        "mixed_step_compiles": max(c for _, c in engines.values()),
        "requests": n_requests,
    }


def bench_serving_disagg():
    """ISSUE 13 extra: disaggregated prefill/decode fleet vs a
    monolithic fleet at EQUAL chip count (2 tiny-GPT engines each,
    every platform) under a mixed long-prompt/short-decode Poisson
    stream — the interference workload. The monolithic replicas need a
    prompt-throughput token budget, so EVERY step (decode-only ones
    included) pays the full [T] compute; the disaggregated decode
    replica runs a decode-sized budget and never shares a step with a
    prefill burst, which is where the inter-token p99 (the
    interference metric) and TTFT move. Outputs are asserted
    token-identical between the fleets (both greedy), so neither side
    can win by dropping work; migrated-block transport volume rides
    the record."""
    import asyncio
    import time as _time

    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.serving.distributed import ReplicaRouter
    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.frontend import ServingFrontend

    rng = np.random.RandomState(0)
    V, T_new, N = 1024, 32, 18
    m = GPTForGeneration(vocab_size=V, hidden_size=128, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32")
    m.eval()
    # 1/3 long prompts (the interference source), 2/3 short
    # decode-dominated requests
    prompts = [rng.randint(1, V, 120 if i % 3 == 0 else
                           int(rng.randint(6, 14))).tolist()
               for i in range(N)]
    # steady-state Poisson: long prompts keep ARRIVING throughout the
    # run (per-prefill interference), rather than one opening burst
    # that a 1-core harness would serialize into a pile-up
    arrivals = np.cumsum(rng.exponential(0.05, N))
    arrivals -= arrivals[0]
    # prompt-throughput token budget: one chunk covers a long prompt
    # (the standard chunked-prefill tuning for TTFT) — which is exactly
    # what makes EVERY monolithic step, decode-only ones included, pay
    # the big [T] compute
    BUDGET = 128

    def _warm_transfers(eng):
        # compile the export/import gather/scatter executables (one
        # per pow2 id-width) outside the timed window, same discipline
        # as the mixed-step warm-up
        ids = eng.kv.allocator.alloc(8)
        for w in (1, 2, 4, 8):
            eng.kv.import_blocks(ids[:w], eng.kv.export_blocks(ids[:w]))
        eng.kv.allocator.free(ids)

    def _mono_fleet():
        fes = []
        for _ in range(2):
            eng = ServingEngine(m, max_slots=6, block_size=16,
                                max_seq_len=256, cache_dtype="float32",
                                seed=0, token_budget=BUDGET)
            eng.generate_batch([prompts[1][:4]], max_new_tokens=2)
            fes.append(ServingFrontend(eng, max_pending=32))
        return ReplicaRouter(fes, probe_interval=0.05), fes

    def _disagg_fleet():
        pre = ServingEngine(m, max_slots=6, block_size=16,
                            max_seq_len=256, cache_dtype="float32",
                            seed=0, role="prefill", token_budget=BUDGET)
        # decode slots are cheap at a decode-sized budget: twice the
        # monolithic slot count still runs a 4x smaller step, and every
        # handed-off request admits without waiting a drain cycle
        dec = ServingEngine(m, max_slots=12, block_size=16,
                            max_seq_len=256, cache_dtype="float32",
                            seed=0, role="decode")
        for eng in (pre, dec):
            # max_new_tokens=1 finishes AT the first token, so the
            # warm-up request never parks in the handoff state
            eng.generate_batch([prompts[1][:4]], max_new_tokens=1)
            _warm_transfers(eng)
        fes = [ServingFrontend(e, max_pending=32) for e in (pre, dec)]
        return ReplicaRouter(fes, roles=["prefill", "decode"],
                             probe_interval=0.05), fes

    def _drive(router):
        ttfts, gaps, outs = [None] * N, [[] for _ in range(N)], \
            [None] * N

        async def fire(i, t0):
            delay = arrivals[i] - (_time.perf_counter() - t0)
            if delay > 0:
                await asyncio.sleep(delay)
            sent = _time.perf_counter()
            toks, last = [], None
            async for tok in router.stream(prompts[i],
                                           max_new_tokens=T_new):
                now = _time.perf_counter()
                if last is None:
                    ttfts[i] = now - sent
                else:
                    gaps[i].append(now - last)
                last = now
                toks.append(tok)
            outs[i] = toks

        async def run():
            async with router:
                t0 = _time.perf_counter()
                await asyncio.gather(*[fire(i, t0) for i in range(N)])
                return _time.perf_counter() - t0

        wall = asyncio.run(run())
        flat = sorted(g for gs in gaps for g in gs)
        served = sum(len(o) for o in outs)

        def pct(q):
            return flat[min(len(flat) - 1, int(len(flat) * q))]

        return {
            "tokens_per_sec": round(served / wall, 1),
            "ttft_p50_s": round(sorted(ttfts)[N // 2], 4),
            "inter_token_p50_s": round(pct(0.50), 4),
            "inter_token_p99_s": round(pct(0.99), 4),
        }, outs

    mono_router, _ = _mono_fleet()
    mono, mono_outs = _drive(mono_router)
    dis_router, _ = _disagg_fleet()
    dis, dis_outs = _drive(dis_router)
    assert dis_outs == mono_outs, \
        "disaggregated outputs diverge from the monolithic fleet"
    st = dis_router.stats()
    return {
        "metric": "serving_disagg",
        # headline: the interference metric the split exists to fix
        "value": round(mono["inter_token_p99_s"]
                       / max(dis["inter_token_p99_s"], 1e-9), 2),
        "unit": "x_p99_inter_token_improvement",
        "monolithic_2x": mono,
        "disagg_1p1d": dis,
        "tokps_ratio_disagg_vs_mono": round(
            dis["tokens_per_sec"] / mono["tokens_per_sec"], 3),
        "requests": N, "max_new_tokens": T_new,
        "outputs_identical": True,
        "migrations": st["migrations"],
        "migrated_blocks": st["transport"]["blocks_sent"],
        "migrated_bytes": st["transport"]["bytes_sent"],
    }


def bench_serving_router():
    """ISSUE 8 extra: 2-replica `ReplicaRouter` under a Poisson
    multi-tenant shared-prefix stream (tiny GPT, every platform) —
    aggregate tokens/sec across replicas, prefix-affinity hit ratio,
    and the failover count after a forced replica crash at ~60% of the
    stream (the surviving replica must finish the in-flight requests
    with greedy-identical outputs, so served tokens stay exact)."""
    import asyncio
    import time as _time

    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.serving.distributed import ReplicaRouter
    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.frontend import ServingFrontend

    rng = np.random.RandomState(0)
    V, T_new, N = 1024, 16, 24
    m = GPTForGeneration(vocab_size=V, hidden_size=128, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32")
    m.eval()
    heads = [rng.randint(1, V, 32).tolist() for _ in range(2)]
    fams = rng.randint(0, 2, N)
    prompts = [heads[f] + rng.randint(1, V, int(n)).tolist()
               for f, n in zip(fams, rng.randint(4, 24, N))]
    arrivals = np.cumsum(rng.exponential(0.004, N))
    arrivals -= arrivals[0]

    fes = []
    for _ in range(2):
        eng = ServingEngine(m, max_slots=6, block_size=16,
                            max_seq_len=128, cache_dtype="float32",
                            seed=0, prefix_caching=True)
        eng.generate_batch([prompts[0][:4]], max_new_tokens=2)  # warm
        fes.append(ServingFrontend(eng, max_pending=32))
    router = ReplicaRouter(fes, probe_interval=0.02)
    kill_at = arrivals[int(N * 0.6)]

    async def drive():
        async def fire(i, t0):
            delay = arrivals[i] - (_time.perf_counter() - t0)
            if delay > 0:
                await asyncio.sleep(delay)
            return await router.submit(prompts[i],
                                       max_new_tokens=T_new,
                                       tenant=f"t{i % 3}")

        async def crash(t0):
            await asyncio.sleep(max(0.0, kill_at
                                    - (_time.perf_counter() - t0)))
            victim = max(range(2), key=router.queue_depth)

            def boom():
                raise RuntimeError("bench-injected replica crash")
            fes[victim].engine.step = boom

        async with router:
            t0 = _time.perf_counter()
            outs, _ = await asyncio.gather(
                asyncio.gather(*[fire(i, t0) for i in range(N)]),
                crash(t0))
            wall = _time.perf_counter() - t0
        return outs, wall

    outs, wall = asyncio.run(drive())
    served = sum(len(o) for o in outs)
    stats = router.stats()
    hit_ratio = stats["affinity_hits"] / max(1, stats["dispatches"])
    return {
        "metric": "serving_router",
        "value": round(served / wall, 1), "unit": "tokens/sec",
        "replicas": 2, "requests": N,
        "served_tokens": int(served),
        "affinity_hit_ratio": round(float(hit_ratio), 3),
        "failovers": int(stats["failovers"]),
        "replicas_up_after": len(stats["health"]["up"]),
    }


def bench_serving_prefix_cache():
    """Radix prefix-cache extra (ISSUE 5 acceptance): N requests with a
    shared system-prompt head, cache-on vs cache-off on the SAME
    engine config (tiny GPT, CPU-safe). Reports the hit-token ratio,
    prefilled tokens both sides, and throughput vs cache-off; outputs
    are asserted token-identical so the speedup can't hide a
    correctness break. One compile per engine, both outside the timed
    window."""
    import time as _time

    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.serving.engine import ServingEngine

    rng = np.random.RandomState(0)
    # prefill-heavy mix (96-token shared head, 8 new tokens): the
    # regime where prefix reuse pays — a decode-bound mix hides it
    V, T_new, N = 1024, 8, 16
    m = GPTForGeneration(vocab_size=V, hidden_size=128, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32")
    m.eval()
    common = rng.randint(1, V, 96).tolist()      # shared system prompt
    prompts = [common + rng.randint(1, V, 8).tolist() for _ in range(N)]
    warm = rng.randint(1, V, 8).tolist()         # disjoint warm prompt

    def run(prefix_caching):
        eng = ServingEngine(m, max_slots=4, block_size=16,
                            max_seq_len=128, cache_dtype="float32",
                            seed=0, prefix_caching=prefix_caching)
        eng.generate_batch([warm], max_new_tokens=2)   # compile
        if eng.prefix_cache is not None:
            eng.prefix_cache.evict_all()
            eng.prefix_cache.hit_tokens = 0
            eng.prefix_cache.miss_tokens = 0
        t0 = _time.perf_counter()
        outs = eng.generate_batch(prompts, max_new_tokens=T_new)
        dt = _time.perf_counter() - t0
        return eng, outs, sum(len(o) for o in outs) / dt

    eng_off, outs_off, tput_off = run(False)
    eng_on, outs_on, tput_on = run(True)
    pc = eng_on.prefix_cache
    extra = {
        "metric": "serving_prefix_cache",
        "value": round(tput_on, 1), "unit": "tokens/sec",
        "cache_off_tokens_per_sec": round(tput_off, 1),
        "speedup_vs_cache_off": round(tput_on / tput_off, 3),
        "hit_token_ratio": round(pc.hit_ratio(), 3),
        "prefilled_tokens_on": int(pc.miss_tokens),
        "prefilled_tokens_off": sum(len(p) for p in prompts),
        "cow_copies": int(pc.cow_copies),
        "outputs_identical": outs_on == outs_off,
        "requests": N,
    }
    if not extra["outputs_identical"]:
        extra["error"] = "prefix-cached outputs diverged from cache-off"
    return extra


def bench_serving_multi_lora():
    """ISSUE 14 extra: K tenants' finetunes through ONE multi-LoRA
    engine vs K per-tenant engines at EQUAL total HBM (the KV block
    budget is split K ways for the solo fleet; adapter slots are the
    multi engine's only extra bytes). Same Poisson-ish interleaved
    stream both sides, outputs asserted token-identical per tenant.
    Reports aggregate tokens/sec both ways, the adapter cache hit
    ratio, and the measured marginal HBM per tenant against the
    analytic 2*r*d*layers-per-projection bound."""
    import time as _time

    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.serving.adapters import make_random_adapter
    from paddle_tpu.serving.engine import ServingEngine

    rng = np.random.RandomState(0)
    V, K = 1024, 4
    tenants = [f"tenant{i}" for i in range(K)]
    m = GPTForGeneration(vocab_size=V, hidden_size=128, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32")
    m.eval()
    adapters = {t: make_random_adapter(m.decoder, 8, seed=i + 1,
                                       scale=0.1)
                for i, t in enumerate(tenants)}
    n_req = 24
    req_tenants = [tenants[i % K] for i in range(n_req)]
    prompts = [rng.randint(1, V, int(n)).tolist()
               for n in rng.randint(8, 48, n_req)]
    total_blocks = 96                    # the shared HBM budget
    warm = rng.randint(1, V, 8).tolist()

    def multi():
        eng = ServingEngine(m, max_slots=8, block_size=16,
                            max_seq_len=128, cache_dtype="float32",
                            num_blocks=total_blocks + 1, seed=0,
                            max_adapters=K + 1, lora_rank=8)
        for t in tenants:
            eng.register_adapter(t, adapters[t])
        eng.generate_batch([warm], max_new_tokens=2)     # compile
        t0 = _time.perf_counter()
        reqs = [eng.submit(p, 16, adapter_id=t)
                for p, t in zip(prompts, req_tenants)]
        eng.run()
        dt = _time.perf_counter() - t0
        outs = [list(r.output) for r in reqs]
        return eng, outs, sum(len(o) for o in outs) / dt

    def solo_fleet():
        engs = {}
        for t in tenants:
            e = ServingEngine(m, max_slots=2, block_size=16,
                              max_seq_len=128, cache_dtype="float32",
                              num_blocks=total_blocks // K + 1, seed=0,
                              max_adapters=2, lora_rank=8)
            e.register_adapter(t, adapters[t])
            e.generate_batch([warm], max_new_tokens=2)   # compile
            engs[t] = e
        t0 = _time.perf_counter()
        reqs = [engs[t].submit(p, 16, adapter_id=t)
                for p, t in zip(prompts, req_tenants)]
        # round-robin the engines the way one process would
        while any(e.scheduler.has_work for e in engs.values()):
            for e in engs.values():
                if e.scheduler.has_work:
                    e.step()
        dt = _time.perf_counter() - t0
        outs = [list(r.output) for r in reqs]
        return sum(len(o) for o in outs) / dt, outs

    eng, outs_multi, tput_multi = multi()
    tput_solo, outs_solo = solo_fleet()
    bound = sum(2 * eng.adapters.rank
                * max(di, do) * eng.adapters.num_layers * 4
                for _, di, do in eng.adapters.hooks)
    extra = {
        "metric": "serving_multi_lora",
        "value": round(tput_multi, 1), "unit": "tokens/sec",
        "solo_fleet_tokens_per_sec": round(tput_solo, 1),
        "speedup_vs_solo_fleet": round(tput_multi / tput_solo, 3),
        "tenants": K, "requests": n_req,
        "adapter_hit_ratio": round(eng.adapters.hit_ratio(), 3),
        "adapter_evictions": int(eng.adapters.evictions),
        "marginal_bytes_per_tenant": int(eng.adapters.bytes_per_slot),
        "marginal_bytes_bound": int(bound),
        "within_analytic_bound":
            eng.adapters.bytes_per_slot <= bound,
        "outputs_identical": outs_multi == outs_solo,
    }
    if not extra["outputs_identical"]:
        extra["error"] = "multi-LoRA outputs diverged from the " \
            "per-tenant solo fleet"
    if not extra["within_analytic_bound"]:
        extra["error"] = "marginal HBM per tenant exceeds the " \
            "analytic bound"
    return extra


def bench_serving_kv_int8():
    """ISSUE 9 extra: fp32 vs int8 KV block pools on the SAME Poisson
    request stream at an EQUAL HBM budget (tiny GPT, every platform).
    Reports tokens/sec both sides, the max concurrent residents each
    pool held before its first preemption, KV bytes/token (the
    `paddle_tpu_serving_kv_bytes_per_token` gauge value) and the
    greedy token-agreement ratio — so the capacity win can't hide a
    divergence break."""
    import time as _time

    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.serving.engine import ServingEngine

    rng = np.random.RandomState(0)
    V, T_new, N = 1024, 12, 24
    m = GPTForGeneration(vocab_size=V, hidden_size=128, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32")
    m.eval()
    prompts = [rng.randint(1, V, int(n)).astype(np.int32)
               for n in rng.randint(8, 56, N)]
    arrivals = np.cumsum(rng.exponential(0.002, N))
    arrivals -= arrivals[0]
    warm = rng.randint(1, V, 8).astype(np.int32)

    # equal HBM budget: what 24 fp32 blocks cost, both pools must fit
    # in — tight enough that the fp32 side preempts under the stream.
    # block_bytes is a pure function of the cache geometry, so size
    # the pools from throwaway minimal PagedKVCaches instead of full
    # engine constructions.
    from paddle_tpu.serving.kv_cache import PagedKVCache

    def _block_bytes(kv_dtype):
        return PagedKVCache(
            2, 4, 32, num_blocks=2, block_size=16, max_slots=1,
            max_blocks_per_slot=1, dtype="float32",
            kv_dtype=kv_dtype).block_bytes

    budget = 24 * _block_bytes(None)

    def run(kv_dtype):
        nb = int(budget // _block_bytes(kv_dtype))
        eng = ServingEngine(m, max_slots=8, block_size=16,
                            num_blocks=nb + 1, max_seq_len=128,
                            cache_dtype="float32", kv_dtype=kv_dtype,
                            seed=0)
        eng.generate_batch([warm], max_new_tokens=2)      # compile
        t0 = _time.perf_counter()
        pending = list(zip(prompts, arrivals))
        reqs = []
        residents_pre = 0
        while pending or eng.scheduler.has_work:
            now = _time.perf_counter() - t0
            while pending and pending[0][1] <= now:
                p, _ = pending.pop(0)
                reqs.append(eng.submit(p, T_new))
            if eng.scheduler.preemption_count == 0:
                residents_pre = max(residents_pre,
                                    eng.scheduler.num_active)
            if not eng.step() and pending:
                _time.sleep(max(0.0, pending[0][1]
                                 - (_time.perf_counter() - t0)))
        wall = _time.perf_counter() - t0
        served = sum(len(r.output) for r in reqs)
        return {
            "blocks": nb,
            "tokens_per_sec": round(served / wall, 1),
            "kv_bytes_per_token": int(eng.kv.kv_bytes_per_token),
            "max_residents_before_preemption": int(residents_pre),
            "preemptions": int(eng.scheduler.preemption_count),
            "outputs": [list(r.output) for r in reqs],
        }

    fp = run(None)
    q8 = run("int8")
    total = sum(len(o) for o in fp["outputs"])
    agree = sum(a == b for x, y in zip(fp["outputs"], q8["outputs"])
                for a, b in zip(x, y))
    # cascade-aware: positionwise agreement punishes every token after
    # a single flip (the context legitimately diverged); the prefix
    # metric counts tokens up to each request's first mismatch
    prefix = 0
    for x, y in zip(fp["outputs"], q8["outputs"]):
        for a, b in zip(x, y):
            if a != b:
                break
            prefix += 1
    for r in (fp, q8):
        del r["outputs"]
    return {
        "metric": "serving_kv_int8",
        "value": q8["tokens_per_sec"], "unit": "tokens/sec",
        "fp32": fp, "int8": q8,
        "hbm_budget_bytes": int(budget),
        "capacity_ratio": round(q8["blocks"] / fp["blocks"], 3),
        "greedy_agreement": round(agree / max(1, total), 4),
        "greedy_prefix_agreement": round(prefix / max(1, total), 4),
        "requests": N,
    }


def bench_serving_long_context():
    """ISSUE 15 extra: long-context decode on 8k-token prompts —
    dense vs block-sparse (`sparse_blocks=`), plus an fp8-pool lane,
    on the PR 13 disaggregated topology: each lane prefills on a
    prefill-role engine (big token budget; `track_summaries` keeps
    sparse lanes' prefill at dense speed) and migrates the request
    onto a decode-role engine (decode-sized budget), because the
    small decode budget is where sparsity's read savings show — a
    mixed budget pays full-table gathers for its prefill lanes either
    way. Decode timing starts AFTER 5 warmup steps (the decode
    engine's compile must not ride the timed window — it swamped the
    measurement by 4x during lane bring-up). Reports decode
    tokens/sec, greedy agreement vs the dense lane, the measured
    block-skip ratio, and the fp8 equal-HBM capacity ratio; the fp8
    sub-lane runs at 2k context (its contract is bytes/agreement, not
    the 8k gather roofline — three full 8k prefills would blow the
    suite budget on the CPU container).

    The model is the longctx smoke's needle construction
    (channel-sparse embeddings + identity q/k, hidden 128):
    random-init attention is diffuse — no block selection can serve
    it — while the needle model attends like a trained one, and the
    128-wide head puts CPU decode in the KV-gather-bound regime the
    sparse path targets (at hidden 32 the per-step selection overhead
    outweighs the tiny gathers and sparse measures SLOWER — the same
    inversion a real model sees between short and long context)."""
    import importlib.util
    import os
    import time as _time

    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.kv_cache import PagedKVCache

    spec = importlib.util.spec_from_file_location(
        "longctx_smoke", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools", "longctx_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    CTX, NEW, BS = 8192, 48, 16
    model = smoke.needle_model(hidden=128, maxpos=CTX + 256)
    rng = np.random.RandomState(0)

    def lane(ctx, sparse=None, **kw):
        geo = dict(max_slots=2, block_size=BS, max_seq_len=ctx + 128,
                   cache_dtype="float32", seed=0)
        pkw = dict(track_summaries=True) if sparse else {}
        skw = dict(sparse_blocks=sparse, sparse_recent=4) \
            if sparse else {}
        pre = ServingEngine(model, role="prefill", token_budget=1024,
                            **geo, **pkw, **kw)
        dec = ServingEngine(model, role="decode", **geo, **skw, **kw)
        prompts = [rng.randint(2, 64, ctx).tolist()]
        reqs = [pre.submit(p, NEW) for p in prompts]
        while any(r.state != "handoff" for r in reqs):
            pre.step()
        dreqs = [dec.submit_migrated(pre.extract_request(r))
                 for r in reqs]
        for _ in range(5):
            dec.step()
        already = sum(len(r.output) for r in dreqs)
        t0 = _time.perf_counter()
        dec.run()
        wall = _time.perf_counter() - t0
        served = sum(len(r.output) for r in dreqs) - already
        return {
            "decode_tokens_per_sec": round(served / wall, 1),
            "kv_bytes_per_token": int(dec.kv.kv_bytes_per_token),
            "skip_ratio": round(dec.sparse_skip_ratio(), 4),
            "outputs": [list(r.output) for r in dreqs],
        }

    rng = np.random.RandomState(0)
    dense = lane(CTX)
    rng = np.random.RandomState(0)          # same prompt per lane
    sparse = lane(CTX, sparse=24)
    rng = np.random.RandomState(0)
    fp32_2k = lane(2048, sparse=24)
    rng = np.random.RandomState(0)
    fp8_2k = lane(2048, sparse=24, kv_dtype="fp8_e4m3")

    def agreement(a, b):
        tot = sum(len(o) for o in a["outputs"])
        return round(sum(x == y for p, q in zip(a["outputs"],
                                                b["outputs"])
                         for x, y in zip(p, q)) / max(1, tot), 4)

    ag_sparse = agreement(dense, sparse)
    ag_fp8 = agreement(fp32_2k, fp8_2k)
    for r in (dense, sparse, fp32_2k, fp8_2k):
        del r["outputs"]

    def _block_bytes(kv_dtype):
        return PagedKVCache(
            2, 1, 32, num_blocks=2, block_size=BS, max_slots=1,
            max_blocks_per_slot=1, dtype="float32",
            kv_dtype=kv_dtype).block_bytes

    return {
        "metric": "serving_long_context",
        "value": sparse["decode_tokens_per_sec"],
        "unit": "decode tokens/sec",
        "context_len": CTX,
        "dense": dense, "sparse": sparse,
        "sparse_fp32_2k": fp32_2k, "sparse_fp8_2k": fp8_2k,
        "speedup_sparse_vs_dense": round(
            sparse["decode_tokens_per_sec"]
            / max(1e-9, dense["decode_tokens_per_sec"]), 2),
        "greedy_agreement_sparse": ag_sparse,
        "greedy_agreement_fp8_vs_fp32_sparse": ag_fp8,
        "fp8_equal_hbm_capacity_ratio": round(
            _block_bytes(None) / _block_bytes("fp8_e4m3"), 2),
    }


def bench_serving_fleet_ops():
    """Fleet control plane extra (ISSUE 17, every platform): cold-start
    seconds from ONE exported bundle for jit-boot vs AOT-boot vs
    AOT+warm-prefix (engine construction through the first
    shared-prefix batch), aggregate tokens/sec through a live
    rolling-upgrade window on a 2-replica fleet (every mid-stream
    request must land on exactly the old or the new checkpoint, never
    a token mix), and autoscaler reaction: simulated burn-to-decision
    seconds (the sustain_s hysteresis floor) plus the real wall
    seconds the applied AOT scale-up boot costs."""
    import asyncio
    import os
    import shutil
    import tempfile
    import time as _time

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.serving.distributed import ReplicaRouter
    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.fleet import (AutoscalerPolicy, FleetBundle,
                                          FleetController, SLOAutoscaler,
                                          boot_engine_from_bundle,
                                          export_bundle,
                                          weights_from_model)
    from paddle_tpu.serving.frontend import ServingFrontend
    from paddle_tpu.serving.slo import SLOMonitor

    rng = np.random.RandomState(3)
    V, T_new, N = 193, 8, 12
    kw = dict(max_slots=4, block_size=4, num_blocks=64, max_seq_len=64,
              token_budget=64, cache_dtype="float32", seed=0,
              prefix_caching=True)

    def _model(seed):
        paddle.seed(seed)
        m = GPTForGeneration(vocab_size=V, hidden_size=32, num_layers=2,
                             num_attention_heads=4,
                             max_position_embeddings=128,
                             compute_dtype="float32")
        m.eval()
        return m

    head = rng.randint(1, V, 12).tolist()
    prompts = [head + rng.randint(1, V, int(n)).tolist()
               for n in rng.randint(3, 9, N)]

    tmp = tempfile.mkdtemp(prefix="paddle_tpu_bench_fleet_")
    try:
        exporter = ServingEngine(_model(1234), name="exporter", **kw)
        exporter.generate_batch(prompts[:4], max_new_tokens=T_new)
        bundle = FleetBundle(export_bundle(exporter, tmp, version="v1"))
        spill = os.path.join(tmp, "prefix.pkl")
        exporter.close(spill_prefix=spill)

        def _boot(kind):
            t0 = _time.perf_counter()
            if kind == "jit":
                eng = boot_engine_from_bundle(bundle, aot=False,
                                              name="b_jit")
            elif kind == "aot":
                eng = boot_engine_from_bundle(bundle, name="b_aot")
            else:
                eng = boot_engine_from_bundle(bundle, warm_prefix=spill,
                                              name="b_warm")
            eng.generate_batch(prompts[:2], max_new_tokens=1)
            return eng, _time.perf_counter() - t0

        jit_eng, jit_s = _boot("jit")
        aot_eng, aot_s = _boot("aot")
        warm_eng, warm_s = _boot("warm")
        aot_eng.close()
        warm_eng.close()

        # v1/v2 greedy references from the already-booted jit engine:
        # a mid-upgrade request is valid iff its tokens match exactly
        # one of the two (version purity, never a mix)
        w2 = weights_from_model(_model(777))
        ref1 = jit_eng.generate_batch(prompts, max_new_tokens=T_new)
        jit_eng.swap_weights(w2, "v2")
        ref2 = jit_eng.generate_batch(prompts, max_new_tokens=T_new)
        jit_eng.close()

        fes = [ServingFrontend(
            boot_engine_from_bundle(bundle, name=f"fleet{i}"),
            max_pending=32) for i in range(2)]
        router = ReplicaRouter(fes, probe_interval=0.02)
        ctl = FleetController(router, bundle, spill_dir=tmp)

        clk = [1000.0]
        monitor = SLOMonitor({"default": {"ttft_p95": 0.1},
                              "window_s": 30.0}, clock=lambda: clk[0])
        scaler = SLOAutoscaler(
            ctl, monitor, clock=lambda: clk[0],
            policy=AutoscalerPolicy(min_replicas=2, max_replicas=3,
                                    sustain_s=1.0, recovery_s=2.0,
                                    cooldown_s=3.0))

        async def drive():
            async with router:
                t0 = _time.perf_counter()
                tasks = [asyncio.create_task(
                    router.submit(list(p), max_new_tokens=T_new))
                    for p in prompts]
                await asyncio.sleep(0.01)
                flipped = await ctl.rolling_upgrade(w2, "v2")
                outs = await asyncio.gather(*tasks)
                wall = _time.perf_counter() - t0

                # engineered burn: advance the fake clock until the
                # sustained-burn decision fires, then time the real
                # AOT boot the applied scale-up performs
                burn_t0 = clk[0]
                d = None
                while d is None and clk[0] - burn_t0 < 10.0:
                    monitor.on_ttft("t", 5.0, clk[0])
                    s0 = _time.perf_counter()
                    d = await scaler.step()
                    boot_wall = _time.perf_counter() - s0
                    clk[0] += 0.25
                return flipped, outs, wall, d, burn_t0, boot_wall

        flipped, outs, wall, d, burn_t0, boot_wall = asyncio.run(drive())
        served = sum(len(o) for o in outs)
        n_v2 = sum(o == r2 for o, r2 in zip(outs, ref2))
        pure = all(o in (r1, r2)
                   for o, r1, r2 in zip(outs, ref1, ref2))
        return {
            "metric": "serving_fleet_ops",
            "value": round(served / wall, 1), "unit": "tokens/sec",
            "cold_start_seconds": {
                "jit_boot": round(jit_s, 2),
                "aot_boot": round(aot_s, 2),
                "aot_warm_prefix": round(warm_s, 2),
            },
            "aot_boot_speedup": round(jit_s / max(aot_s, 1e-9), 2),
            "upgrade": {
                "replicas": 2, "requests": N,
                "served_tokens": int(served),
                "flipped": list(flipped),
                "on_new_version": int(n_v2),
                "version_pure_outputs": bool(pure),
            },
            "autoscaler": {
                "reaction_seconds_simulated": round(
                    (d["ts"] - burn_t0) if d else -1.0, 2),
                "sustain_s": 1.0,
                "scale_up_boot_wall_seconds": round(boot_wall, 2),
                "replicas_after": len(ctl.active_replicas()),
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_gpt_moe(on_tpu):
    """ISSUE 10 extra: the MoE GPT lane — hybrid-trainer tokens/sec
    (top-k capacity router, fixed [E, C, d] dispatch einsums) and MoE
    serving tokens/sec through the one-compile mixed step, with the
    expert-utilization entropy / dropped-token / aux-loss record in
    the JSON. min-of-k timed windows per the PR 7 convention. On TPU
    the train config is MoE-350M-class: the 350M dense config with
    its FFN swapped for 8 experts top-2 (~350M active params per
    token, ~1.1B resident); CPU runs a tiny smoke of the same shape."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.hybrid_gpt import GPTConfig, HybridGPT
    from paddle_tpu.profiler import metrics as _pm

    dev = jax.devices()[0]
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, seq_len=1024, d_model=1024,
                        n_heads=16, n_layers=24, moe_num_experts=8,
                        moe_top_k=2, moe_capacity_factor=1.25,
                        remat=True, fused_ce=True, ce_seq_chunks=4,
                        bf16_grads=True, compute_dtype=jnp.bfloat16)
        batch, iters, windows = 16, 8, 3
    else:
        cfg = GPTConfig(vocab_size=1024, seq_len=128, d_model=128,
                        n_heads=4, n_layers=2, moe_num_experts=4,
                        moe_top_k=2, moe_capacity_factor=1.25,
                        remat=False, compute_dtype=jnp.float32)
        batch, iters, windows = 4, 3, 2

    trainer = HybridGPT(cfg, devices=[dev])
    params, opt = trainer.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (batch, cfg.seq_len)), jnp.int32)
    lab = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (batch, cfg.seq_len)), jnp.int32)
    for w in range(3):
        params, opt, loss = trainer.train_step(params, opt, tok, lab,
                                               step_num=w + 1)
        float(jax.device_get(loss))
    step_num, best = 4, float("inf")
    for k in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt, loss = trainer.train_step(
                params, opt, tok, lab, step_num=step_num)
            step_num += 1
        final_loss = float(jax.device_get(loss))
        best = min(best, time.perf_counter() - t0)
        if k and _budget_left() < 120:
            break
    assert np.isfinite(final_loss)
    train_tps = batch * cfg.seq_len * iters / best
    trainer.flush_moe_metrics()      # drain the one-step metric lag
    tstats = jax.device_get(trainer.last_moe_stats)
    # MFU against ACTIVE params (top_k experts per token), the MoE
    # convention — total params would flatter a sparse model. ONE
    # formula: auto_tuner.ModelSpec.active_params/useful_flops is the
    # same accounting the placement search predicts MFU with
    from paddle_tpu.parallel.auto_tuner import ModelSpec
    mspec = ModelSpec(
        n_layers=cfg.n_layers, d_model=cfg.d_model,
        seq_len=cfg.seq_len, vocab_size=cfg.vocab_size, d_ff=cfg.d_ff,
        global_batch=batch, moe_experts=cfg.moe_experts,
        moe_top_k=cfg.moe_top_k,
        moe_capacity_factor=cfg.moe_capacity_factor)
    flops_tok = mspec.useful_flops() / (batch * cfg.seq_len)
    train_mfu = train_tps * flops_tok / PEAK_FLOPS

    # serving phase: tiny MoE engine on every platform (the serving
    # extras discipline), greedy stream through the ONE mixed step
    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.serving.engine import ServingEngine
    m = GPTForGeneration(vocab_size=1024, hidden_size=128,
                         num_layers=2, num_attention_heads=4,
                         max_position_embeddings=512,
                         compute_dtype="float32",
                         moe=dict(num_expert=4, top_k=2,
                                  capacity_factor=2.0))
    m.eval()
    prompts = [rng.randint(1, 1024, int(n)).astype(np.int32)
               for n in rng.randint(8, 56, 16)]
    eng = ServingEngine(m, max_slots=8, block_size=16,
                        max_seq_len=128, cache_dtype="float32", seed=0)
    eng.generate_batch([prompts[0]], max_new_tokens=2)    # compile
    t0 = time.perf_counter()
    outs = eng.generate_batch(prompts, max_new_tokens=12)
    serve_wall = time.perf_counter() - t0
    served = sum(len(o) for o in outs)
    if _pm._enabled:
        _pm.TOKENS_PER_SEC.set(train_tps)
    return {
        "metric": "gpt_moe",
        "value": round(train_tps, 1), "unit": "tokens/sec",
        "train_tokens_per_sec": round(train_tps, 1),
        "train_mfu_active": round(train_mfu, 4) if on_tpu else None,
        "train_loss": round(final_loss, 4),
        "train_aux_loss": round(float(tstats["balance"]), 4),
        "train_dropped_tokens": int(tstats["dropped"]),
        "serving_tokens_per_sec": round(served / serve_wall, 1),
        "moe_expert_utilization": round(
            eng.moe_utilization_entropy(), 4),
        "moe_dropped_tokens_total": int(eng.moe_dropped_total),
        "moe_aux_loss": round(eng.moe_last_aux, 4),
        "experts": cfg.moe_experts, "top_k": cfg.moe_top_k,
        "capacity_factor": cfg.moe_capacity_factor,
    }


def bench_kernel_autotune(on_tpu):
    """ISSUE 11 extra: the measurement-driven Pallas kernel autotuner.

    Three records, every platform:
      * paged decode tuned-vs-default tok/s — the engine-level KV
        block-size search (`tune_block_size`, parity-gated candidates
        sharing the dispatch gate's alignment predicate) and the same
        decode-heavy stream served at the default 16 vs the winner;
      * MoE dispatch einsum-vs-indexed tok/s — the one-hot [T,k,C]
        dispatch/combine einsums against the index-table gather pair
        the grouped-expert kernel rides (backend-independent; on TPU
        the grouped Pallas matmul adds MXU tiling on top —
        docs/KERNELS.md carries the expected-effect analysis);
      * cache contract — search seconds, cache-hit ratio for this
        process, and the hit-is-zero-cost assertion (1k lookups, no
        searcher invocation).
    Searches persist into a throwaway cache so a bench run never
    mutates the operator's tuned cache."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import GPTForGeneration
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas import paged_attention as pa_mod
    from paddle_tpu.parallel import moe_utils
    from paddle_tpu.serving.engine import ServingEngine

    tmp = tempfile.NamedTemporaryFile(suffix=".json", delete=False)
    tmp.close()
    old_cache = os.environ.get("PADDLE_TPU_KERNEL_CACHE")
    os.environ["PADDLE_TPU_KERNEL_CACHE"] = tmp.name
    autotune.reset_for_tests()
    try:
        import paddle_tpu as paddle
        paddle.seed(1234)
        m = GPTForGeneration(vocab_size=512, hidden_size=64,
                             num_layers=2, num_attention_heads=4,
                             max_position_embeddings=256,
                             compute_dtype="float32")
        m.eval()
        H, Dh = 4, 16
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 512, int(n)).astype(np.int32)
                   for n in rng.randint(16, 48, 16)]

        def serve_tok_s(block_size):
            eng = ServingEngine(m, max_slots=8, block_size=block_size,
                                max_seq_len=128,
                                cache_dtype="float32", seed=0)
            eng.generate_batch([prompts[0]], max_new_tokens=2)
            t0 = time.perf_counter()
            outs = eng.generate_batch(prompts, max_new_tokens=24)
            dt = time.perf_counter() - t0
            return sum(len(o) for o in outs) / dt, outs

        default_tok_s, ref_outs = serve_tok_s(16)
        res = pa_mod.tune_block_size(8, H, Dh, context_len=64,
                                     budget_s=20.0)
        tuned_bs = int(res.config["block_size"])
        tuned_tok_s, tuned_outs = serve_tok_s(tuned_bs)
        assert tuned_outs == ref_outs    # block size never changes tokens

        # MoE dispatch representation: one-hot einsums vs index tables
        T, E, k, d = 256, 8, 2, 128
        C = moe_utils.expert_capacity(T, E, k, 1.25)
        logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
        x = jnp.asarray(rng.randn(T, d).astype(np.float32))
        r = moe_utils.top_k_routing(logits, k, C)

        @jax.jit
        def einsum_pair(x):
            disp = moe_utils.dispatch_tokens(x, r.plan)
            return moe_utils.combine_tokens(disp, r.plan)

        @jax.jit
        def indexed_pair(x):
            disp = moe_utils.dispatch_tokens_indexed(x, r.plan, E, C)
            return moe_utils.combine_tokens_indexed(disp, r.plan)

        def rate(fn):
            fn(x).block_until_ready()
            iters = 30
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x)
            out.block_until_ready()
            return T * iters / (time.perf_counter() - t0)

        einsum_tok_s = rate(einsum_pair)
        indexed_tok_s = rate(indexed_pair)

        # cache contract: the tuned winner is a hit, hits cost nothing
        bucket = autotune.shape_bucket(8, H, Dh)
        t0 = time.perf_counter()
        for _ in range(1000):
            cfg = autotune.ensure(
                "paged_block_size", bucket, np.float32, default=None,
                searcher=lambda: (_ for _ in ()).throw(
                    AssertionError("searched on a cache hit")))
        lookup_ms = (time.perf_counter() - t0) * 1e3
        assert cfg == res.config
        req = autotune.requested()
        hit_ratio = (sum(req.values()) / len(req)) if req else 0.0

        return {
            "metric": "kernel_autotune",
            "value": round(tuned_tok_s, 1), "unit": "tokens/sec",
            "paged_decode": {
                "default_block_size": 16,
                "tuned_block_size": tuned_bs,
                "default_tokens_per_sec": round(default_tok_s, 1),
                "tuned_tokens_per_sec": round(tuned_tok_s, 1),
                "outputs_identical": True,
                "search_seconds": round(res.elapsed, 2),
                "candidates_tried": res.tried,
                "candidates_rejected_parity": res.rejected,
            },
            "moe_dispatch": {
                "einsum_tokens_per_sec": round(einsum_tok_s, 1),
                "indexed_tokens_per_sec": round(indexed_tok_s, 1),
                "speedup": round(indexed_tok_s / einsum_tok_s, 2),
                "shape": {"T": T, "E": E, "top_k": k, "d": d, "C": C},
            },
            "cache_hit_ratio": round(hit_ratio, 3),
            "cache_lookup_ms_per_1k": round(lookup_ms, 2),
            "cache_hit_zero_cost": lookup_ms < 200.0,
            "backend": autotune.backend_key(),
            "note": (None if on_tpu else
                     "CPU: search machinery + cache contract; the "
                     "Pallas tile wins are TPU-only by design "
                     "(docs/KERNELS.md expected-effect analysis)"),
        }
    finally:
        autotune.reset_for_tests()
        if old_cache is None:
            os.environ.pop("PADDLE_TPU_KERNEL_CACHE", None)
        else:
            os.environ["PADDLE_TPU_KERNEL_CACHE"] = old_cache
        try:
            os.unlink(tmp.name)
        except OSError:
            pass


def _metrics_extra():
    """Condensed observability snapshot for the benchmark JSON `extras`
    (only when PADDLE_TPU_METRICS is set — instrumentation off keeps the
    headline run unperturbed)."""
    from paddle_tpu.profiler import metrics
    if not metrics._enabled:
        return None
    snap = metrics.REGISTRY.snapshot()

    def total(name):
        return round(sum(
            v for v in snap.get(name, {}).get("values", {}).values()
            if isinstance(v, (int, float))), 3)

    return {
        "metric": "observability_snapshot",
        "dispatch_ops": total("paddle_tpu_dispatch_ops_total"),
        "jit_compiles": total("paddle_tpu_jit_compiles_total"),
        "jit_compile_seconds": total(
            "paddle_tpu_jit_compile_seconds_total"),
        "collective_bytes": total("paddle_tpu_collective_bytes_total"),
        "grad_buckets": total("paddle_tpu_grad_buckets"),
        "pipeline_bubble_ticks": total(
            "paddle_tpu_pipeline_stage_bubble_ticks"),
        "pipeline_bubble_ratio": round(
            metrics.PIPELINE_BUBBLE_RATIO.value, 4),
        "tokens_per_sec_gauge": round(metrics.TOKENS_PER_SEC.value, 1),
        "moe_expert_tokens": total("paddle_tpu_moe_expert_tokens_total"),
        "moe_dropped_tokens": total(
            "paddle_tpu_moe_dropped_tokens_total"),
        "moe_expert_utilization": round(
            metrics.MOE_EXPERT_UTILIZATION.labels("serving").value, 4),
        # expert-weight HBM per dtype for the gpt_moe bench shape
        # (ISSUE 14): what the weight-only knob buys at serving time —
        # analytic, scales included (grouped_matmul.expert_weight_bytes)
        "moe_expert_weight_bytes": _expert_weight_bytes_by_dtype(),
        # request tracing + SLO plane (ISSUE 16): nonzero when the run
        # also sets PADDLE_TPU_TRACE=1 (tracing, like the rest of the
        # instrumentation, stays off unless asked for)
        "trace_requests": total("paddle_tpu_serving_trace_requests_total"),
        "trace_events": total("paddle_tpu_serving_trace_events_total"),
        "trace_events_dropped": total(
            "paddle_tpu_serving_trace_events_dropped_total"),
        "trace_open": total("paddle_tpu_serving_trace_active"),
        "slo_breaches": total("paddle_tpu_serving_slo_breaches_total"),
        "flight_steps": _flight_steps(),
    }


def _flight_steps():
    """Per-step flight-recorder coverage across every engine this bench
    process created (serving.tracing.StepFlightRecorder)."""
    from paddle_tpu.serving import tracing
    return int(sum(rec.steps for rec in tracing.flight_recorders()))


def _expert_weight_bytes_by_dtype():
    """bf16 / int8 / int4 expert-stack bytes (both FFN mats + scales)
    for the gpt_moe bench shape — 8 experts on the 350M-class config."""
    from paddle_tpu.ops.pallas.grouped_matmul import expert_weight_bytes
    L, E, D, F = 24, 8, 1024, 4096
    return {dt: int(expert_weight_bytes(E, D, F, dt, L)
                    + expert_weight_bytes(E, F, D, dt, L))
            for dt in ("bfloat16", "int8", "int4")}


def main():
    import os

    import jax
    if os.environ.get("PADDLE_TPU_METRICS"):
        from paddle_tpu.profiler import metrics as _m
        _m.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    tps, gpt_mfu, tuner_extra = bench_gpt(on_tpu)
    result = {
        "metric": ("gpt2_350m_train_tokens_per_sec_per_chip" if on_tpu
                   else "gpt_tiny_cpu_smoke_tokens_per_sec"),
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / 10_000.0, 3) if on_tpu else None,
        "mfu": round(gpt_mfu, 4) if on_tpu else None,
        "extras": [],
    }
    # placement-search extra (ISSUE 7): the tuner's predicted MFU rides
    # next to the measured number so the prediction gap is in the record
    result["extras"].append(tuner_extra)

    # layout mode of record for the vision configs (ISSUE 4): which
    # path produced the resnet/lenet numbers in this run
    from paddle_tpu.core import layout as _layout_mod
    result["extras"].append({
        "metric": "layout_mode",
        "value": ("nhwc_propagated" if _layout_mod.enabled()
                  else "nchw_per_op"),
        "s2d_stem": _layout_mod.s2d_stem_enabled(),
    })

    # serving extra runs on every platform (CPU tiny GPT) and carries
    # the continuous-batching >= 2x-vs-sequential driver contract —
    # run it BEFORE the TPU extras so a long compile tail (e.g. the
    # speculative decode extra) can't starve it out of the budget
    try:
        result["extras"].append(bench_serving())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_continuous_batching",
             "error": f"{type(e).__name__}: {e}"})

    # prefix-cache extra: same every-platform discipline (tiny GPT,
    # shared-system-prompt stream, hit ratio + throughput vs cache-off)
    try:
        result["extras"].append(bench_serving_prefix_cache())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_prefix_cache",
             "error": f"{type(e).__name__}: {e}"})

    # multi-replica router extra: every-platform (2 CPU-capable tiny
    # replicas, Poisson multi-tenant stream, forced mid-stream crash)
    try:
        result["extras"].append(bench_serving_router())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_router",
             "error": f"{type(e).__name__}: {e}"})

    # multi-tick decode lane (ISSUE 18): every-platform — decode
    # tok/s and inter-token p50/p99 at ticks_per_dispatch 1/4/8, plus
    # the sync-vs-async host-stall share the overlapped readback buys
    try:
        result["extras"].append(bench_serving_multitick())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_multitick",
             "error": f"{type(e).__name__}: {e}"})

    # on-device speculation lane (ISSUE 19): every-platform — draft_k=3
    # inside the N=8 while_loop vs the spec-N1 and nospec-N8 baselines,
    # accept rate on drafter-friendly prompts, token-identity record
    try:
        result["extras"].append(bench_serving_spec_multitick())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_spec_multitick",
             "error": f"{type(e).__name__}: {e}"})

    # disaggregated prefill/decode extra: every-platform (1 prefill +
    # 1 decode vs 2 monolithic replicas at equal chip count, mixed
    # long-prompt/short-decode Poisson stream — p99 inter-token is the
    # interference metric the split exists to fix)
    try:
        result["extras"].append(bench_serving_disagg())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_disagg",
             "error": f"{type(e).__name__}: {e}"})

    # int8-KV extra: every-platform (fp32 vs int8 pools at equal HBM
    # budget on the same Poisson stream — capacity + agreement record)
    try:
        result["extras"].append(bench_serving_kv_int8())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_kv_int8",
             "error": f"{type(e).__name__}: {e}"})

    # multi-LoRA lane (ISSUE 14): K tenants through one engine vs K
    # per-tenant engines at equal HBM — every platform
    try:
        result["extras"].append(bench_serving_multi_lora())
    except Exception as e:  # noqa: BLE001
        result["extras"].append(
            {"metric": "serving_multi_lora",
             "error": f"{type(e).__name__}: {e}"})

    # fleet control plane lane (ISSUE 17): every-platform — jit vs AOT
    # vs AOT+warm-prefix cold-start seconds from one bundle, tokens/sec
    # through a live rolling-upgrade window, autoscaler reaction record
    if _budget_left() > 120:
        try:
            result["extras"].append(bench_serving_fleet_ops())
        except Exception as e:  # noqa: BLE001
            result["extras"].append(
                {"metric": "serving_fleet_ops",
                 "error": f"{type(e).__name__}: {e}"})
    else:
        result["extras"].append(
            {"metric": "serving_fleet_ops", "skipped": "time budget"})

    # long-context lane (ISSUE 15): 8k-token prompts migrated onto
    # decode-role engines — dense vs block-sparse decode tok/s +
    # agreement, fp8 pools at 2k + equal-HBM capacity. The two
    # 8k-token prefills dominate its wall time (~80 s each on the
    # 1-core CPU container), so it needs real budget headroom.
    if _budget_left() > 360:
        try:
            result["extras"].append(bench_serving_long_context())
        except Exception as e:  # noqa: BLE001
            result["extras"].append(
                {"metric": "serving_long_context",
                 "error": f"{type(e).__name__}: {e}"})
    else:
        result["extras"].append(
            {"metric": "serving_long_context",
             "skipped": "insufficient wall-clock budget (needs ~5-6 "
                        "min: two 8k-context prefills + fp8 lanes on "
                        "CPU)"})

    # MoE lane (ISSUE 10): every-platform — hybrid MoE train tok/s
    # (MoE-350M-class on TPU) + MoE serving tok/s + utilization record
    if _budget_left() > 90:
        try:
            result["extras"].append(bench_gpt_moe(on_tpu))
        except Exception as e:  # noqa: BLE001
            result["extras"].append(
                {"metric": "gpt_moe",
                 "error": f"{type(e).__name__}: {e}"})
    else:
        result["extras"].append(
            {"metric": "gpt_moe", "skipped": "time budget"})

    # kernel-autotune extra (ISSUE 11): every-platform — block-size
    # search + tuned-vs-default decode tok/s, MoE dispatch
    # representation A/B, cache-hit-zero-cost contract
    if _budget_left() > 60:
        try:
            result["extras"].append(bench_kernel_autotune(on_tpu))
        except Exception as e:  # noqa: BLE001
            result["extras"].append(
                {"metric": "kernel_autotune",
                 "error": f"{type(e).__name__}: {e}"})
    else:
        result["extras"].append(
            {"metric": "kernel_autotune", "skipped": "time budget"})

    # embedding-engine extra: every-platform (localhost PS servers +
    # CPU dense step) with the >= 1.3x-vs-direct driver contract
    if _budget_left() > 60:
        try:
            result["extras"].append(bench_wide_deep_heter())
        except Exception as e:  # noqa: BLE001
            result["extras"].append(
                {"metric": "wide_deep_heter_examples_per_sec",
                 "error": f"{type(e).__name__}: {e}"})
    else:
        result["extras"].append(
            {"metric": "wide_deep_heter_examples_per_sec",
             "skipped": "time budget"})

    # graph-engine lane (ISSUE 20): every-platform (localhost PS
    # servers + jitted SAGE step) with the pipelined >= 1.2x-vs-direct
    # driver contract
    if _budget_left() > 60:
        try:
            result["extras"].append(bench_graph_sage())
        except Exception as e:  # noqa: BLE001
            result["extras"].append(
                {"metric": "graph_sage_examples_per_sec",
                 "error": f"{type(e).__name__}: {e}"})
    else:
        result["extras"].append(
            {"metric": "graph_sage_examples_per_sec",
             "skipped": "time budget"})

    if on_tpu:
        for name, fn, unit in (
                ("resnet50_train_imgs_per_sec_per_chip", bench_resnet50,
                 "imgs/sec"),
                ("bert_base_lamb_train_seqs_per_sec_per_chip", bench_bert,
                 "seqs/sec"),
                ("lenet_fit_steps_per_sec", bench_lenet, "steps/sec"),
                ("wide_deep_ps_examples_per_sec", bench_wide_deep,
                 "examples/sec"),
                ("gpt2_350m_decode_tokens_per_sec_per_chip", bench_decode,
                 "tokens/sec"),
                ("gpt2_350m_decode_speculative_b1_tokens_per_sec",
                 bench_decode_speculative, "tokens/sec")):
            # drop the previous config's device buffers: trainers hold
            # reference cycles (mesh/jit closures), so HBM is only
            # reclaimed after a cycle collection
            import gc
            gc.collect()
            if _budget_left() < 60:
                result["extras"].append(
                    {"metric": name, "skipped": "time budget"})
                continue
            try:
                res = fn()
            except Exception as e:
                result["extras"].append(
                    {"metric": name, "error": f"{type(e).__name__}: {e}"})
                continue
            # (value, mfu) or (value, mfu, secondary-metric dict)
            val, mfu, extra_metric = (tuple(res) + (None,))[:3]
            if val is None:
                result["extras"].append(
                    {"metric": name, "skipped": "not available"})
                continue
            result["extras"].append({
                "metric": name, "value": round(val, 1), "unit": unit,
                "mfu": round(mfu, 4) if mfu else None})
            if extra_metric is not None:
                result["extras"].append(extra_metric)

    obs = _metrics_extra()
    if obs is not None:
        result["extras"].append(obs)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
