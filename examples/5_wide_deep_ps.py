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


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1 and sys.argv[1] == "heter":
        main_heter()
    else:
        main()
