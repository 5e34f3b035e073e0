"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two flagship entry points once, at the full
width of a model the repo supports, and checks what comes out:

* **train** — `parallel.hybrid_gpt.HybridGPT`, GPT-2 350M widths (the
  train cell's, `benchmarks/configs/gpt2_medium_train.json`), one compile and five steps on one seeded
  batch, every step ended by `block_until_ready`;
* **serve** — `inference.create_serving_frontend` ->
  `serving.ServingEngine` over `GPTForGeneration` at the `gpt3_1p3b`
  widths, nine greedy requests through `submit()`;
* **kernels** — the Pallas kernels on those two paths against their XLA
  oracles at the shapes the phases really run (the `validate_*` bodies
  of `tools/tpu_tile_validate.py`).

It passes only on `platform == "tpu"`: without an accelerator it exits
non-zero and prints no result. `--rehearse` is the one exception, and it
says so in every line that matters: the same phases at tiny sizes,
pinned to the CPU, kernels in Pallas interpret mode — to debug the
script before it is sent to a chip, never to validate one. The sizes
and the interpret switch hang on that flag; nothing here asks jax what
platform it is on except the gate below.

`--chips 4` (a four-chip host) runs the train phase on a dp2 x mp2 mesh
with sequence parallelism and ZeRO-1, checks its first-step loss
against the one-chip forward loss, and serves through
`create_serving_router(num_replicas=4)`, one replica per chip.

Wall seconds are printed as information. This script computes no rate
and no utilization; the benchmark does that.

The last line of standard output is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import importlib.metadata
import json
import os
import re
import sys
import time
import traceback

TRAIN_LOSS_TOL = 0.05    # |loss(N chips) - loss(1 chip)| at step 1, in nats


def _version(pkg):
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def _mosaic_kernels(lowered_text):
    """Names of the Mosaic (Pallas TPU) custom calls in a lowered step."""
    if "tpu_custom_call" not in lowered_text:
        return set()
    return set(re.findall(r'kernel_name\s*=\s*"([^"]+)"', lowered_text))


def _pallas_kernels(jaxpr):
    """Names of every `pallas_call` in a traced step, nested jaxprs
    included: which implementation the step was BUILT from, whether the
    kernels then lower to Mosaic or run interpreted."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(str(eqn.params["name"]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    names |= _pallas_kernels(sub)
    return names


def check(ok, message):
    """The smoke's assertion: raises whatever `python -O` strips."""
    if not ok:
        raise AssertionError(message)


def _need(names, wanted, where):
    missing = [w for w in wanted if not any(w in n for n in names)]
    if missing:
        raise AssertionError(
            f"{where}: kernels {missing} are not in the step "
            f"(found {sorted(names)}): an XLA fallback ran in their place")


# --------------------------------------------------------------- train


def train_phase(args, devices):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.hybrid_gpt import GPTConfig, HybridGPT

    if args.rehearse:
        width = dict(vocab_size=512, seq_len=128, d_model=128, n_heads=2,
                     n_layers=2)
        batch = 4 * args.chips    # add_ln tiles 256 local rows
    else:
        # the train cell's GPT-2 350M (head_dim 64), full depth
        width = dict(vocab_size=50304, seq_len=1024, d_model=1024,
                     n_heads=16, n_layers=24)
        batch = 32
    common = dict(micro_batches=1, remat=True,
                  remat_policy="save_splash_residuals", fused_ce=True,
                  ce_seq_chunks=4, bf16_grads=True,
                  compute_dtype=jnp.bfloat16, **width)
    one_chip = GPTConfig(dp=1, pp=1, mp=1, zero_stage=0, **common)
    if args.chips == 1:
        cfg = one_chip
    else:
        # two mesh axes above 1, sequence parallel over mp, optimizer
        # state sharded over all four chips
        cfg = GPTConfig(dp=2, pp=1, mp=2, sequence_parallel=True,
                        zero_stage=1, **common)
    rng = np.random.RandomState(0)
    tok = rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len)).astype(
        np.int32)
    lab = rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len)).astype(
        np.int32)
    key = jax.random.PRNGKey(0)

    ref_loss = None
    if args.chips > 1:
        # the one-chip reference: same seed, same batch, forward only
        ref = HybridGPT(one_chip, devices=devices[:1])
        p_ref, _ = ref.init(key)
        ref_loss = float(jax.device_get(ref.loss(p_ref, *ref.shard_data(
            tok, lab))))
        del ref, p_ref
        gc.collect()

    trainer = HybridGPT(cfg, devices=devices)
    params, opt = trainer.init(key)
    tok_d, lab_d = trainer.shard_data(tok, lab)
    if args.chips > 1:
        w = params["blocks"]["w_qkv"]
        held = {s.device for s in w.addressable_shards}
        check(held == set(devices),
              f"w_qkv lives on {held}, not on all of {devices}")
        check(all(s.data.shape != w.shape for s in w.addressable_shards),
              "w_qkv is replicated, not sharded")
        m = opt["blocks"]["w_qkv"]["m"]
        check(len({s.index for s in m.addressable_shards}) == len(devices),
              "ZeRO-1 moments are not split over every chip")

    # which implementation the step is built from / lowers to, read
    # before the first call (lowering donates nothing)
    lr = jnp.asarray(cfg.learning_rate, jnp.float32)
    traced = trainer._step._jitted.trace(
        params, opt, tok_d, lab_d, lr, jnp.asarray(1.0, jnp.float32))
    wanted = ("splash_mha_fwd", "splash_mha_dkv", "add_ln_fwd",
              "add_ln_bwd")
    _need(_pallas_kernels(traced.jaxpr.jaxpr), wanted, "train step jaxpr")
    if not args.rehearse:
        _need(_mosaic_kernels(traced.lower().as_text()), wanted,
              "lowered train step")

    losses, secs = [], []
    for step in range(1, 6):
        t0 = time.perf_counter()
        params, opt, loss = trainer.train_step(params, opt, tok_d, lab_d,
                                               step_num=step)
        jax.block_until_ready((params, opt, loss))
        secs.append(time.perf_counter() - t0)
        losses.append(float(jax.device_get(loss)))
    compiles = trainer._step.compile_count()
    print(f"train: losses {' '.join(f'{x:.4f}' for x in losses)}")
    print(f"train: step 1 (compile included) {secs[0]:.1f} s wall, steps "
          f"2-5 {' '.join(f'{s:.3f}' for s in secs[1:])} s wall; "
          f"{compiles} compile of the step")
    check(all(np.isfinite(losses)),
          f"non-finite loss in {losses}")
    check(losses[4] < losses[0],
          f"loss did not fall over 5 steps on one batch: {losses}")
    check(compiles == 1,
          f"train step compiled {compiles}x, not once")
    if ref_loss is not None:
        print(f"train: first-step loss {losses[0]:.4f} on {args.chips} "
              f"chips, {ref_loss:.4f} on one chip "
              f"(tolerance {TRAIN_LOSS_TOL})")
        check(abs(losses[0] - ref_loss) <= TRAIN_LOSS_TOL,
              f"first-step loss {losses[0]} vs one-chip {ref_loss}")
    mesh = dict(trainer.mesh.shape)
    return (f"train {'x'.join(f'{k}{v}' for k, v in mesh.items())} "
            f"{cfg.n_layers}L d{cfg.d_model} loss "
            f"{losses[0]:.3f}->{losses[4]:.3f}")


# --------------------------------------------------------------- serve


def serve_phase(args, devices):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.models.gpt import GPTForGeneration

    if args.rehearse:
        width = dict(vocab_size=512, hidden_size=256, num_layers=2,
                     num_attention_heads=2, max_position_embeddings=256)
        lo, hi, new_tokens = 4, 64, 8
    else:
        # models/gpt.py gpt3_1p3b (head_dim 128), full depth
        width = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                     num_attention_heads=16, max_position_embeddings=2048)
        lo, hi, new_tokens = 64, 1024, 32
    paddle.seed(0)
    model = GPTForGeneration(compute_dtype="bfloat16", **width)
    model.eval()
    vocab = width["vocab_size"]
    rng = np.random.RandomState(1)
    n_req = 8 * args.chips
    lens = np.linspace(lo, hi, n_req).astype(int)
    rng.shuffle(lens)
    prompts = [rng.randint(0, vocab, int(n)).tolist() for n in lens]
    # the same prompt once more, last: whatever slot, chunking and batch
    # company it gets, a paged engine must answer it the same way
    prompts.append(list(prompts[0]))

    cfg = inference.Config().enable_continuous_batching(
        num_replicas=args.chips if args.chips > 1 else None)
    if args.chips == 1:
        server = inference.create_serving_frontend(cfg, model)
        engines = [server.engine]
    else:
        server = inference.create_serving_router(cfg, model)
        engines = [f.engine for f in server.frontends]
        homes = [next(iter(e.kv.k_pool.devices())) for e in engines]
        check(homes == list(devices),
              f"replica pools on {homes}, wanted one each on {devices}")
        for e, d in zip(engines, homes):
            check(all(a.devices() == {d} for a in e._arrays),
                  f"{e.name}: weights are not all on {d}")

    for e in engines:
        traced = e._step_fn._jitted.trace(*e.example_step_args())
        _need(_pallas_kernels(traced.jaxpr.jaxpr), ("paged_ragged",),
              f"{e.name} mixed step jaxpr")
        if not args.rehearse:
            _need(_mosaic_kernels(traced.lower().as_text()),
                  ("paged_ragged",), f"lowered {e.name} mixed step")

    async def drive():
        async with server:
            t0 = time.perf_counter()
            # warm-up: one request per replica compiles each mixed step
            first = await asyncio.gather(*[
                server.submit(p, max_new_tokens=new_tokens)
                for p in prompts[:args.chips]])
            t1 = time.perf_counter()
            warm = [e.step_compile_count() for e in engines]
            rest = await asyncio.gather(*[
                server.submit(p, max_new_tokens=new_tokens)
                for p in prompts[args.chips:]])
            return first + rest, warm, t1 - t0, time.perf_counter() - t1

    outs, warm, t_first, t_rest = asyncio.run(drive())
    after = [e.step_compile_count() for e in engines]
    steps = [e.steps_run for e in engines]
    print(f"serve: {len(outs)} requests x {new_tokens} new tokens, "
          f"prompt lengths {sorted(len(p) for p in prompts)}")
    print(f"serve: warm-up request(s) (compile included) {t_first:.1f} s "
          f"wall, the other {len(outs) - args.chips} in {t_rest:.1f} s "
          f"wall; engine steps {steps}; mixed-step compiles {after}")
    for p, o in zip(prompts, outs):
        check(len(o) == new_tokens,
              f"prompt of {len(p)} tokens got {len(o)} tokens back")
        check(all(0 <= int(t) < vocab for t in o),
              f"token out of vocab: {o}")
    check(outs[-1] == outs[0],
          f"the repeated prompt was answered differently: {outs[0]} "
          f"vs {outs[-1]}")
    check(warm == [1] * len(engines),
          f"mixed step compiles after warm-up {warm}, wanted 1 per replica")
    check(after == warm,
          f"mixed step recompiled after the first request: {warm} -> {after}")
    check(all(s > 0 for s in steps),
          f"an idle replica: steps {steps}")
    for e in engines:
        check(e.kv.blocks_in_use == 0,
              f"{e.name} leaked KV blocks")
    return (f"serve {len(engines)}x{width['num_layers']}L "
            f"h{width['hidden_size']} {len(outs)} requests x "
            f"{new_tokens} tokens")


# ------------------------------------------------------------- kernels


def kernel_phase(args, devices):
    del devices
    from tools import tpu_tile_validate as tv

    if args.rehearse:
        cells = tv.validate_paged(H=2, Dh=128, BS=16, max_blocks=4,
                                  ragged_n=4, slots=2, verify_width=4,
                                  sparse_blocks=0,
                                  dtypes=("float32", "bfloat16"))
        cells += tv.validate_add_ln(rows=256, d=128, dtype="bfloat16")
        cells += tv.validate_splash(B=1, H=2, S=128, D=64,
                                    dtype="bfloat16")
    else:
        # the engine's tiles: 16 heads x 128, block 16, 128-block
        # tables, T=32 flat tokens, 8 slots, a draft_k=3 verify window
        cells = tv.validate_paged(H=16, Dh=128, BS=16, max_blocks=128,
                                  ragged_n=32, slots=8, verify_width=4,
                                  sparse_blocks=0,
                                  dtypes=("float32", "bfloat16"))
        # the trainer's: [32 * 1024, 1024] rows, [B, 16, 1024, 64] heads
        cells += tv.validate_add_ln(rows=32 * 1024, d=1024,
                                    dtype="bfloat16")
        cells += tv.validate_splash(B=2, H=16, S=1024, D=64,
                                    dtype="bfloat16")
    for c in cells:
        print(f"kernels: {c}")
    bad = [c.name for c in cells if not c.ok]
    check(not bad,
          f"kernels disagree with their XLA oracles: {bad}")
    return f"kernels {len(cells)}/{len(cells)} cells"


# ---------------------------------------------------------------- main


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Drive the trainer and the serving engine once on "
                    "the chip and check what comes out.")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="devices to use: jax.devices()[:N]")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes with the kernels "
                         "in interpret mode; validates no chip")
    args = ap.parse_args(argv)
    if args.rehearse:
        # before jax is imported: pin the rehearsal to the CPU and give
        # it as many virtual devices as the run wants chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.chips}")

    import jax
    from paddle_tpu.analysis import guards
    from paddle_tpu.core.compile_cache import use_compile_cache
    from paddle_tpu.ops.pallas import interpret_mode

    cache_dir = use_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1
    jax.monitoring.register_event_listener(count)

    t_start = time.perf_counter()
    found = jax.devices()
    dev0 = found[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(found)}
    label = "chip_smoke CPU REHEARSAL" if args.rehearse else "chip_smoke"
    print(f"{label}: platform={dev0.platform} "
          f"device_kind={dev0.device_kind!r} devices={len(found)} "
          f"jax={jax.__version__} jaxlib={_version('jaxlib')} "
          f"libtpu={_version('libtpu')}", flush=True)
    if not args.rehearse and dev0.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev0.platform!r}); nothing "
              "was run. `--rehearse` debugs the script on the CPU.",
              file=sys.stderr)
        return 4
    if len(found) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax has "
              f"{len(found)} device(s)", file=sys.stderr)
        return 4
    devices = found[:args.chips]
    print(f"{label}: compile cache at {cache_dir}", flush=True)

    summary, failed = [], []
    mode = interpret_mode() if args.rehearse else contextlib.nullcontext()
    # the compile-count watchdog, and only that: the device-to-host
    # transfer guard has never run on a device (PERF.md, open questions)
    with mode, guards.sanitize(transfer_guard=None) as watchdog:
        for phase in (train_phase, serve_phase, kernel_phase):
            name = phase.__name__
            t0 = time.perf_counter()
            try:
                summary.append(phase(args, devices))
            except Exception:    # noqa: BLE001 — report, run the next phase
                failed.append(name)
                print(f"{label}: {name} FAILED\n{traceback.format_exc()}",
                      flush=True)
            print(f"{label}: {name} took "
                  f"{time.perf_counter() - t0:.1f} s wall", flush=True)
            gc.collect()
            jax.clear_caches()
    if watchdog.violations:
        failed.append("compile watchdog: " + "; ".join(
            str(v) for v in watchdog.violations))
    print(f"{label}: compile cache {cache['hits']} hits, "
          f"{cache['misses']} misses; total "
          f"{time.perf_counter() - t_start:.1f} s wall")
    if failed:
        print(f"{label}: FAILED — {failed}")
        return 1
    print(f"{label}: {'; '.join(summary)}")
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
