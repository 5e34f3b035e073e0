"""Overlap / bucketing / zero-bubble contract smoke (ISSUE 7 CI check).

Three structural contracts, all checkable on the CPU test mesh (the
wall-clock win is TPU-targeted; the STRUCTURE is what this gates):

1. **Bucketed DP grad reduction**: the optimized HLO of the
   `grad_bucket_bytes`-enabled hybrid train step reduces exactly
   `grad_bucket_count(params, bucket)` non-scalar buffers per dtype —
   i.e. ceil(total_grad_bytes / bucket_size) — instead of the
   per-parameter-leaf count of the legacy path, with the reduced byte
   total unchanged (sum of all-reduce operand bytes == grad bytes).
   What is counted is reduced BUFFERS, whichever op carries them: the
   installed XLA:CPU (jaxlib 0.9.0) expands the optimization_barrier
   chain before its all-reduce combiner runs and folds the buckets into
   one variadic all-reduce, so "one op per bucket" is not a property
   the CPU compiler keeps. The TPU compiler does keep it — the same
   step compiled ahead of time for a v5e 2x2 has one all-reduce per
   bucket (PR 21) — and it is there that the overlap matters.

2. **Zero-bubble schedule**: `schedule_bubble_ticks("zero_bubble", ...)`
   strictly below the 1f1b gauge at the same (pp, v, M), and the live
   PIPELINE_BUBBLE_TICKS gauges a CompiledPipeline publishes agree.

3. **One compile per entry point**: two bucketed train steps still
   compile `HybridGPT.train_step` exactly once.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python tools/overlap_smoke.py
(also wired into tests/test_overlap.py)
"""
from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BUCKET_BYTES = 4096
BATCH = 8

# an all-reduce's result type: one `f32[1024]{0}` or, for a variadic
# all-reduce, a tuple of them
_ALL_REDUCE_RE = re.compile(r"^[^=]*= (.*?) all-reduce(?:-start)?\(",
                            re.MULTILINE)
_BUFFER_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


_HLO_ITEMSIZE = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                 "u32": 4, "s64": 8, "u64": 8, "s8": 1, "u8": 1}


def count_allreduces(hlo_text: str):
    """(non_scalar_count, payload_bytes, scalar_count) over the
    buffers the optimized HLO's all-reduce ops reduce (each element of
    a variadic all-reduce counts as one)."""
    import numpy as np
    non_scalar, scalar, payload = 0, 0, 0
    for m in _ALL_REDUCE_RE.finditer(hlo_text):
        for dt, shape in _BUFFER_RE.findall(m.group(1)):
            if not shape:
                scalar += 1
                continue
            non_scalar += 1
            elems = int(np.prod([int(d) for d in shape.split(",") if d]))
            payload += elems * _HLO_ITEMSIZE.get(dt, 4)
    return non_scalar, payload, scalar


def _tiny_cfg(**kw):
    import jax.numpy as jnp
    from paddle_tpu.parallel.hybrid_gpt import GPTConfig
    base = dict(vocab_size=64, seq_len=16, d_model=32, n_heads=4,
                n_layers=4, d_ff=64, micro_batches=1, remat=False,
                zero_stage=0, grad_clip=1.0, compute_dtype=jnp.float32)
    base.update(kw)
    return GPTConfig(**base)


def lower_step_hlo(cfg):
    """Optimized-HLO text of the hybrid train step + its params."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel.hybrid_gpt import HybridGPT

    tr = HybridGPT(cfg)
    p, o = tr.init(jax.random.PRNGKey(0))
    tok, lab = tr.shard_data(np.zeros((BATCH, cfg.seq_len), np.int32),
                             np.zeros((BATCH, cfg.seq_len), np.int32))
    lr = jnp.asarray(1e-3, jnp.float32)
    t = jnp.asarray(1.0, jnp.float32)
    txt = tr._step._jitted.lower(p, o, tok, lab, lr, t).compile().as_text()
    return txt, p


def check_bucketing():
    from paddle_tpu.parallel.hybrid_gpt import grad_bucket_count

    cfg = _tiny_cfg(dp=2, grad_bucket_bytes=BUCKET_BYTES)
    hlo, params = lower_step_hlo(cfg)
    n, payload, n_scalar = count_allreduces(hlo)
    expected = grad_bucket_count(params, BUCKET_BYTES)
    import jax
    import numpy as np
    grad_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                     for l in jax.tree.leaves(params))

    hlo_legacy, _ = lower_step_hlo(_tiny_cfg(dp=2))
    n_legacy, _, _ = count_allreduces(hlo_legacy)

    ok = True
    print(f"overlap_smoke: bucketed all-reduce ops = {n} "
          f"(contract: <= ceil(grad_bytes/bucket) = {expected}), "
          f"legacy per-leaf path = {n_legacy}, "
          f"scalar (loss) = {n_scalar}")
    if n > expected:
        print("overlap_smoke: FAIL — more all-reduces than buckets "
              "(XLA re-combined or bucketing regressed)")
        ok = False
    print(f"overlap_smoke: bucketed all-reduce payload = {payload} B "
          f"(grad bytes = {grad_bytes})")
    if payload != grad_bytes:
        print("overlap_smoke: FAIL — reduced byte total != grad bytes")
        ok = False
    # one-bucket config must also beat the per-leaf count (the drop from
    # n_params to bucket count the ISSUE names)
    hlo_one, params_one = lower_step_hlo(
        _tiny_cfg(dp=2, grad_bucket_bytes=1 << 30))
    n_one, _, _ = count_allreduces(hlo_one)
    print(f"overlap_smoke: one-bucket all-reduce ops = {n_one} "
          f"(legacy {n_legacy})")
    if n_one != grad_bucket_count(params_one, 1 << 30):
        print("overlap_smoke: FAIL — one-bucket count off")
        ok = False
    if n_one >= n_legacy:
        print("overlap_smoke: FAIL — bucketing did not reduce the "
              "collective count")
        ok = False
    return ok


def check_zero_bubble():
    from paddle_tpu.parallel.pipeline_schedule import schedule_bubble_ticks

    ok = True
    for pp, v, M in ((2, 1, 4), (4, 1, 8), (2, 2, 4)):
        fb, _ = schedule_bubble_ticks("1f1b", pp, v, M)
        zbb, _ = schedule_bubble_ticks("zero_bubble", pp, v, M)
        print(f"overlap_smoke: bubbles pp={pp} v={v} M={M}: "
              f"1f1b={fb[0]} zero_bubble={zbb[0]}")
        if not all(z < f for z, f in zip(zbb, fb)):
            print("overlap_smoke: FAIL — zero_bubble not strictly "
                  "fewer bubble ticks")
            ok = False
    # live gauge agreement (CompiledPipeline publishes on build)
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.parallel.pipeline import PipelineLayer, LayerDesc
    from paddle_tpu.parallel.pipeline_schedule import CompiledPipeline
    from paddle_tpu.profiler import metrics as pm

    was = pm._enabled
    pm.enable()
    try:
        gauges = {}
        for schedule in ("1f1b", "zero_bubble"):
            paddle.seed(0)
            model = PipelineLayer(
                layers=[LayerDesc(nn.Linear, 4, 8), LayerDesc(nn.Tanh),
                        LayerDesc(nn.Linear, 8, 8)],
                num_stages=2, loss_fn=nn.MSELoss())
            CompiledPipeline(model, micro_batches=4, schedule=schedule)
            gauges[schedule] = pm.PIPELINE_BUBBLE_TICKS.labels("0").value
        print(f"overlap_smoke: live bubble gauges = {gauges}")
        if not gauges["zero_bubble"] < gauges["1f1b"]:
            print("overlap_smoke: FAIL — live zero_bubble gauge not "
                  "below 1f1b")
            ok = False
    finally:
        if not was:
            pm.disable()
    return ok


def check_one_compile():
    import jax
    import numpy as np
    from paddle_tpu.parallel.hybrid_gpt import HybridGPT
    from paddle_tpu.profiler import metrics as pm

    was = pm._enabled
    pm.enable()
    pm.REGISTRY.reset()
    try:
        cfg = _tiny_cfg(dp=2, grad_bucket_bytes=BUCKET_BYTES)
        tr = HybridGPT(cfg)
        p, o = tr.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        for i in range(2):
            tok = rng.randint(0, 64, (BATCH, 16)).astype(np.int32)
            lab = rng.randint(0, 64, (BATCH, 16)).astype(np.int32)
            tok, lab = tr.shard_data(tok, lab)
            p, o, loss = tr.train_step(p, o, tok, lab, step_num=i + 1)
        compiles = pm.JIT_COMPILES.labels("HybridGPT.train_step").value
        buckets = pm.GRAD_BUCKETS.labels("compiled").value
    finally:
        if not was:
            pm.disable()
    print(f"overlap_smoke: train_step compiles = {compiles:g} "
          f"(contract: 1), grad-bucket gauge = {buckets:g}")
    if compiles != 1:
        print("overlap_smoke: FAIL — bucketed step retraced")
        return False
    if buckets <= 0:
        print("overlap_smoke: FAIL — bucket gauge not published")
        return False
    return bool(np.isfinite(float(loss)))


def main():
    # runtime sanitizers (ISSUE 12): transfer guard + compile watchdog
    from paddle_tpu.analysis import guards
    with guards.sanitize() as wd:
        ok = check_bucketing()
        ok = check_zero_bubble() and ok
        ok = check_one_compile() and ok
    for v in wd.violations:
        print(f"overlap_smoke: compile watchdog: {v}")
        ok = False
    print("overlap_smoke: " + ("OK" if ok else "FAIL"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
