"""BASELINE config 5: Wide&Deep on Criteo-style slot data with the native
parameter-server engine (C++ tables + DataFeed; AUC metric).

Single-process by default; set the PS env for true client/server mode:
  TRAINING_ROLE=PSERVER PADDLE_PSERVERS_IP_PORT_LIST=... (server)
  TRAINING_ROLE=TRAINER PADDLE_PSERVERS_IP_PORT_LIST=... (trainer)
"""
import os
import tempfile

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.ps import InMemoryDataset, SparseEmbedding
from paddle_tpu.ps.runtime import get_ps_runtime


def make_slot_files(path, n=20000, slots=(1, 2, 3, 4), vocab=10000,
                    zipf=None):
    """`zipf` (e.g. 1.3) skews the sign distribution the way real CTR
    traffic is skewed — the hot head is what the ps.heter hot-ID cache
    exists for; None keeps the original uniform draw."""
    rng = np.random.RandomState(0)
    with open(path, "w") as f:
        for _ in range(n):
            if zipf is not None:
                feats = [int(rng.zipf(zipf) % vocab) for _ in slots]
            else:
                feats = [rng.randint(0, vocab) for _ in slots]
            label = int((feats[0] % 3 == 0) ^ (feats[1] % 2 == 0))
            f.write(f"{label} " + " ".join(
                f"{s}:{s * 100000 + v}" for s, v in zip(slots, feats))
                + "\n")
    return path


def make_raw_logs(path, n=20000, n_slots=4, vocab=10000):
    """Raw click logs: `<click> <f1> <f2> <f3> <f4>` — NOT the slot
    format; the DataGenerator below parses them (fleet data_generator
    deployment mode)."""
    rng = np.random.RandomState(0)
    with open(path, "w") as f:
        for _ in range(n):
            feats = [rng.randint(0, vocab) for _ in range(n_slots)]
            label = int((feats[0] % 3 == 0) ^ (feats[1] % 2 == 0))
            f.write(f"{label} " + " ".join(map(str, feats)) + "\n")
    return path


class WideDeepGenerator:
    """User parser (fleet data_generator.py parity): raw log line ->
    [(slot_name, [sign...]), ...]."""

    def generate_sample(self, line):
        def local_iter():
            parts = line.split()
            label = int(parts[0])
            yield [("label", [label])] + [
                (f"slot{i+1}", [(i + 1) * 100000 + int(v)])
                for i, v in enumerate(parts[1:])]
        return local_iter


def main(epochs=3, batch_size=512, dim=8, use_data_generator=True):
    from paddle_tpu.ps.data_generator import MultiSlotDataGenerator
    tmp = tempfile.mkdtemp()
    slots = [1, 2, 3, 4]

    ds = InMemoryDataset()
    ds.init(batch_size=batch_size, slots=slots, max_per_slot=1)
    if use_data_generator:
        raw = make_raw_logs(os.path.join(tmp, "raw-0.txt"))

        class Gen(WideDeepGenerator, MultiSlotDataGenerator):
            pass

        gen = Gen()
        gen.set_slots([f"slot{i}" for i in slots])
        ds.load_from_generator(gen, [raw])
    else:
        data = make_slot_files(os.path.join(tmp, "part-0.txt"))
        ds.set_filelist([data])
        ds.load_into_memory()
    ds.global_shuffle(seed=42)
    print("records:", ds.get_memory_data_size())

    rt = get_ps_runtime()
    table = rt.create_sparse_table(0, dim=dim, sgd_rule="adagrad",
                                   learning_rate=0.1)
    emb = SparseEmbedding(dim=dim, table=table)
    deep = nn.Sequential(nn.Linear(len(slots) * dim, 64), nn.ReLU(),
                         nn.Linear(64, 32), nn.ReLU(), nn.Linear(32, 1))
    wide = nn.Linear(len(slots) * dim, 1)
    opt = paddle.optimizer.Adam(
        1e-3, parameters=deep.parameters() + wide.parameters())
    auc = paddle.metric.Auc()

    for epoch in range(epochs):
        auc.reset()
        for keys, labels in ds:
            n = keys.shape[0]
            acts = emb(keys).reshape([n, len(slots) * dim])
            logits = (deep(acts) + wide(acts)).reshape([n])
            loss = nn.functional.binary_cross_entropy_with_logits(
                logits, paddle.to_tensor(labels))
            loss.backward()
            opt.step()
            opt.clear_grad()
            auc.update(1 / (1 + np.exp(-logits.numpy())), labels)
        print(f"epoch {epoch}: loss {float(loss):.4f} "
              f"auc {auc.accumulate():.4f} "
              f"table {len(table)} features")
    rt.save_persistables(os.path.join(tmp, "ps_model"))
    print("saved to", os.path.join(tmp, "ps_model"))


def run_bench(batch_size=512, dim=8, n=20000):
    """bench.py hook: examples/sec through pull -> COMPILED dense step ->
    push after one warmup epoch. The dense model is the framework's own
    nn stack compiled by jit.CompiledTrainStep (donated buffers, fused
    Adam) with input_grads=True, whose extra output — the embedding-
    activation gradient — is pushed back into the C++ tables: the PSGPU
    pull/train/push cycle with the train leg on the accelerator."""
    import time

    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import CompiledTrainStep

    tmp = tempfile.mkdtemp()
    data = make_slot_files(os.path.join(tmp, "part-0.txt"), n=n)
    slots = [1, 2, 3, 4]
    ds = InMemoryDataset()
    ds.init(batch_size=batch_size, slots=slots, max_per_slot=1)
    ds.set_filelist([data])
    ds.load_into_memory()
    rt = get_ps_runtime()
    table = rt.create_sparse_table(0, dim=dim, sgd_rule="adagrad",
                                   learning_rate=0.1)
    feat = len(slots) * dim

    class WideDeep(nn.Layer):
        def __init__(self):
            super().__init__()
            self.deep = nn.Sequential(
                nn.Linear(feat, 64), nn.ReLU(), nn.Linear(64, 32),
                nn.ReLU(), nn.Linear(32, 1))
            self.wide = nn.Linear(feat, 1)

        def forward(self, acts):
            return (self.deep(acts) + self.wide(acts)).reshape([-1])

    net = WideDeep()
    opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
    step = CompiledTrainStep(
        net, nn.functional.binary_cross_entropy_with_logits, opt,
        n_labels=1, input_grads=True)

    from paddle_tpu.ps.pipeline import PullPushPipeline
    pipe = PullPushPipeline(prefetch_depth=8, push_depth=4)
    last = {}
    GROUP = 4   # K pull/train/push cycles per device dispatch, to
    #             amortise per-dispatch host latency (not measured on
    #             the direct backend)

    def pull_fn(batch):
        keys, labels = batch
        bsz = keys.shape[0]
        return (table.pull(keys.astype(np.uint64)).reshape(bsz, feat),
                np.asarray(labels, np.float32))

    group = []

    def _flush_group():
        items = group[:]
        group.clear()
        batches = [(acts, lab) for _, (acts, lab) in items]
        losses, (acts_grads,) = step.run_many(batches,
                                              with_in_grads=True)
        last["loss"] = losses
        return ([k for k, _ in items], acts_grads)

    def step_fn(batch, pulled):
        keys, _ = batch
        push_item = None
        if group and group[0][1][0].shape != pulled[0].shape:
            push_item = _flush_group()   # ragged batch: new group
        group.append((keys, pulled))
        if len(group) >= GROUP:
            assert push_item is None
            push_item = _flush_group()
        return keys.shape[0], push_item

    def push_fn(item):
        keys_list, acts_grads = item
        # the device->host gradient fetch blocks HERE, off the critical
        # path (VERDICT r3 #2: the serial loop paid one sync per batch)
        g = acts_grads.numpy()
        for i, keys in enumerate(keys_list):
            bsz = keys.shape[0]
            table.push(keys.astype(np.uint64),
                       g[i].reshape(bsz, len(slots), 1, dim))

    def epoch():
        group.clear()
        seen = pipe.run(iter(ds), pull_fn, step_fn, push_fn)
        # drain a ragged tail group
        if group:
            push_fn(_flush_group())
        float(jax.device_get(last["loss"]._data[-1]))
        return seen

    epoch()  # warmup/compile
    t0 = time.perf_counter()
    seen = epoch()
    eps = seen / (time.perf_counter() - t0)
    # training AUC on a sample (BASELINE config 5's second metric) via
    # the bucketed metric stack: a real quality signal, not just ex/s
    from paddle_tpu.metric import Auc
    auc = Auc(num_thresholds=2048)
    ds.rewind()
    it = iter(ds)
    for _ in range(8):
        batch = next(it, None)
        if batch is None:
            break
        keys, labels = batch
        acts, lab = pull_fn((keys, labels))
        logits = net(paddle.to_tensor(jnp.asarray(acts)))
        probs = 1.0 / (1.0 + np.exp(-np.asarray(logits.numpy(),
                                                np.float64)))
        preds = np.stack([1.0 - probs, probs], axis=1)
        auc.update(preds, lab.reshape(-1, 1))
    return eps, float(auc.accumulate())


def main_heter(epochs=2, batch_size=512, dim=8, vocab=10000,
               num_shards=4, cache_capacity=4096):
    """Wide&Deep through the HeterPS-style embedding engine
    (`paddle_tpu.ps.heter`): one logical table sharded 4 ways, hot-ID
    cache in front, pulls/pushes dedup-merged — same model code as
    main(), just `SparseEmbedding(engine=...)`."""
    from paddle_tpu.ps import (HeterEmbeddingEngine, LookupService,
                               ShardedSparseTable)
    tmp = tempfile.mkdtemp()
    slots = [1, 2, 3, 4]
    ds = InMemoryDataset()
    ds.init(batch_size=batch_size, slots=slots, max_per_slot=1)
    data = make_slot_files(os.path.join(tmp, "part-0.txt"),
                           vocab=vocab, zipf=1.3)
    ds.set_filelist([data])
    ds.load_into_memory()
    ds.global_shuffle(seed=42)

    table = ShardedSparseTable(num_shards=num_shards, dim=dim,
                               sgd_rule="adagrad", learning_rate=0.1)
    engine = HeterEmbeddingEngine(table, cache_capacity=cache_capacity,
                                  mode="strict")
    emb = SparseEmbedding(dim=dim, engine=engine)
    deep = nn.Sequential(nn.Linear(len(slots) * dim, 64), nn.ReLU(),
                         nn.Linear(64, 32), nn.ReLU(), nn.Linear(32, 1))
    wide = nn.Linear(len(slots) * dim, 1)
    opt = paddle.optimizer.Adam(
        1e-3, parameters=deep.parameters() + wide.parameters())
    auc = paddle.metric.Auc()

    for epoch in range(epochs):
        auc.reset()
        for keys, labels in ds:
            n = keys.shape[0]
            acts = emb(keys).reshape([n, len(slots) * dim])
            logits = (deep(acts) + wide(acts)).reshape([n])
            loss = nn.functional.binary_cross_entropy_with_logits(
                logits, paddle.to_tensor(labels))
            loss.backward()
            opt.step()
            opt.clear_grad()
            auc.update(1 / (1 + np.exp(-logits.numpy())), labels)
        emb.flush()
        print(f"epoch {epoch}: loss {float(loss):.4f} "
              f"auc {auc.accumulate():.4f} "
              f"cache hit ratio {engine.hit_ratio():.3f} "
              f"dedup ratio {engine.dedup_ratio():.3f} "
              f"shards {table.shard_sizes()}")
    # read-only lookup serving over the SAME warm cache
    svc = LookupService(engine)
    probe = np.asarray([100001, 200002, 300003], np.uint64)
    print("lookup service:", svc.lookup(probe).shape,
          "state:", svc.state())
    engine.close()


def run_bench_heter(batch_size=512, dim=8, n_batches=64, vocab=10000,
                    per_slot=4, num_servers=2, cache_capacity=32768):
    """bench.py hook: the engine lane vs the direct-table lane against
    REAL parameter servers (the client/server deployment this example
    documents in its header), on the SAME zipf-skewed key stream —
    recommender traffic is zipfian (the hot head is what the hot-ID
    cache exists for) and slots are multi-valued (user behaviour
    history), so a batch carries heavy intra-batch key duplication.

    direct lane: synchronous RPC pull -> COMPILED step -> grad fetch
    -> RPC push per batch (the plain `SparseEmbedding` order of
    operations over `RemoteSparseTable` — every batch pays two
    full-payload round trips to the servers).
    engine lane: stream-mode `HeterEmbeddingEngine` over the same
    servers — hot ids served from the dense cache, batch N+1's misses
    prefetched over RPC while batch N trains, gradients dedup-merged
    (one wire row per unique key) and drained on a background thread
    up to `staleness_bound` batches late, so both the device->host
    gradient sync AND the push RPC leave the critical path
    (push-as-you-train, the reference AsyncCommunicator window).

    In-process tables are NOT the engine's regime: the native C hash
    table resolves a key in ~100ns, so cache bookkeeping costs more
    than it saves (docs/EMBEDDING.md shows that measurement); the
    engine pays off exactly when pulls cross a process/RPC/disk
    boundary, which is what a real PS deployment does.

    Returns (engine_eps, direct_eps, stats)."""
    import queue
    import threading
    import time

    import jax.numpy as jnp

    from paddle_tpu.jit import CompiledTrainStep
    from paddle_tpu.ps import HeterEmbeddingEngine
    from paddle_tpu.ps.service import (PSClient, PSServer,
                                       RemoteSparseTable)

    slots = [1, 2, 3, 4]
    feat = len(slots) * per_slot * dim
    rng = np.random.RandomState(0)

    def zipf_batch():
        keys = np.empty((batch_size, len(slots), per_slot), np.uint64)
        for j, s in enumerate(slots):
            v = rng.zipf(1.3, (batch_size, per_slot)) % vocab
            keys[:, j, :] = s * 100000 + v
        labels = (rng.rand(batch_size) < 0.5).astype(np.float32)
        return keys, labels

    batches = [zipf_batch() for _ in range(n_batches)]

    class WideDeep(nn.Layer):
        def __init__(self):
            super().__init__()
            self.deep = nn.Sequential(
                nn.Linear(feat, 64), nn.ReLU(), nn.Linear(64, 32),
                nn.ReLU(), nn.Linear(32, 1))
            self.wide = nn.Linear(feat, 1)

        def forward(self, acts):
            return (self.deep(acts) + self.wide(acts)).reshape([-1])

    def build_step():
        paddle.seed(0)
        net = WideDeep()
        opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
        return CompiledTrainStep(
            net, nn.functional.binary_cross_entropy_with_logits, opt,
            n_labels=1, input_grads=True)

    def start_servers(table_id):
        servers = [PSServer() for _ in range(num_servers)]
        for s in servers:
            s.register_sparse_table(table_id, dim=dim,
                                    sgd_rule="adagrad",
                                    learning_rate=0.1)
            s.run(background=True)
        client = PSClient([f"127.0.0.1:{s.port}" for s in servers])
        return servers, client

    # K pull/train/push cycles per device dispatch in BOTH lanes (the
    # bench_wide_deep GROUP discipline: per-step dispatch overhead
    # would otherwise dominate this small dense model)
    GROUP = 8
    groups = []
    for g0 in range(0, n_batches, GROUP):
        chunk = batches[g0:g0 + GROUP]
        keys_g = np.concatenate([k for k, _ in chunk])
        groups.append((keys_g, chunk))

    def _run_group(step, acts_flat, chunk):
        """One grouped dispatch -> stacked input grads [K, bsz, feat]."""
        acts = acts_flat.reshape(len(chunk), batch_size, feat)
        stacked = [(jnp.asarray(acts[i]), jnp.asarray(lab))
                   for i, (_, lab) in enumerate(chunk)]
        _, (g,) = step.run_many(stacked, with_in_grads=True)
        return g

    # ---- direct lane: sync RPC pull -> step -> fetch -> RPC push ----
    def run_direct():
        servers, client = start_servers(0)
        table = RemoteSparseTable(client, 0, dim=dim)
        step = build_step()

        def one_pass():
            t0 = time.perf_counter()
            for keys_g, chunk in groups:
                acts_flat = table.pull(keys_g)
                g = _run_group(step, acts_flat, chunk)
                table.push(keys_g, g.numpy().reshape(
                    keys_g.shape[0], len(slots), per_slot, dim))
            return time.perf_counter() - t0
        one_pass()                          # warmup/compile
        # min-of-2 timed passes (BASELINE.md host-variance hardening)
        eps = batch_size * n_batches / min(one_pass(), one_pass())
        client.close()
        for s in servers:
            s.stop()
        return eps

    # ---- engine lane: cached pulls + prefetch + late pushes ----
    def run_engine():
        servers, client = start_servers(0)
        table = RemoteSparseTable(client, 0, dim=dim)
        engine = HeterEmbeddingEngine(table,
                                      cache_capacity=cache_capacity,
                                      mode="stream", staleness_bound=8)
        step = build_step()
        depth = 2                           # device-sync lag (groups)

        def one_pass():
            # stream-mode pushes are thread-safe: a drain thread takes
            # the gradient fetch AND the push RPC off the critical
            # path (bounded queue = the staleness window)
            pq = queue.Queue(maxsize=depth)

            def drain_loop():
                while True:
                    item = pq.get()
                    if item is None:
                        return
                    keys_g, g = item
                    engine.push(keys_g, g.numpy().reshape(
                        keys_g.shape[0], len(slots), per_slot, dim))
            drain = threading.Thread(target=drain_loop, daemon=True)
            drain.start()
            t0 = time.perf_counter()
            for i, (keys_g, chunk) in enumerate(groups):
                acts_flat = engine.pull(keys_g)
                if i + 1 < len(groups):
                    # submit BEFORE the step so the worker's dedup +
                    # miss RPC overlaps the dense compute
                    engine.prefetch(groups[i + 1][0])
                g = _run_group(step, acts_flat, chunk)
                pq.put((keys_g, g))
            pq.put(None)
            drain.join()
            engine.flush()
            return time.perf_counter() - t0
        one_pass()                          # warmup/compile
        # min-of-2 timed passes (BASELINE.md host-variance hardening)
        eps = batch_size * n_batches / min(one_pass(), one_pass())
        stats = {"cache_hit_ratio": round(engine.hit_ratio(), 4),
                 "dedup_ratio": round(engine.dedup_ratio(), 4),
                 "evictions": engine.cache.evictions,
                 "prefetch": {"hits": engine.prefetch_hits,
                              "repairs": engine.prefetch_repairs,
                              "unused": engine.prefetch_unused}}
        engine.close()
        client.close()
        for s in servers:
            s.stop()
        return eps, stats

    direct_eps = run_direct()
    engine_eps, stats = run_engine()
    return engine_eps, direct_eps, stats


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1 and sys.argv[1] == "heter":
        main_heter()
    else:
        main()
