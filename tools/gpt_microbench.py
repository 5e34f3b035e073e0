"""GPT single-chip step micro-bench for perf iteration.

Runs the train cell's config (GPT-2 350M, B=32, S=1024) with
config overrides from the command line, prints ms/step and tok/s.

Usage:
    python tools/gpt_microbench.py [key=value ...]
e.g.
    python tools/gpt_microbench.py ce_seq_chunks=1 iters=8
"""
import sys
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.hybrid_gpt import GPTConfig, HybridGPT

    overrides = {}
    iters = 10
    _string_keys = ("trace", "remat_policy")
    for arg in sys.argv[1:]:
        k, v = arg.split("=", 1)
        if k == "iters":
            iters = int(v)
            continue
        try:
            v = int(v)
        except ValueError:
            if v.lower() in ("true", "false"):
                v = v.lower() == "true"
            elif k not in _string_keys:
                raise SystemExit(
                    f"{k}={v}: expected int or true/false "
                    f"(string values only for {_string_keys})")
        overrides[k] = v

    trace_dir = overrides.pop("trace", None)
    kw = dict(vocab_size=50304, seq_len=1024, d_model=1024,
              n_heads=16, n_layers=24, dp=1, pp=1, mp=1,
              micro_batches=1, remat=True, zero_stage=0,
              remat_policy="save_splash_residuals",
              fused_ce=True, ce_seq_chunks=2, bf16_grads=True,
              compute_dtype=jnp.bfloat16)
    batch = int(overrides.pop("batch", 32))
    kw.update(overrides)
    cfg = GPTConfig(**kw)
    print("config overrides:", overrides, "batch:", batch, flush=True)

    dev = jax.devices()[0]
    trainer = HybridGPT(cfg, devices=[dev])
    params, opt = trainer.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len)),
                      jnp.int32)
    lab = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len)),
                      jnp.int32)
    t0 = time.perf_counter()
    params, opt, loss = trainer.train_step(params, opt, tok, lab,
                                           step_num=1)
    print(f"compile+1st step: {time.perf_counter() - t0:.1f}s "
          f"loss={float(jax.device_get(loss)):.4f}", flush=True)

    t0 = time.perf_counter()
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            for i in range(3):
                params, opt, loss = trainer.train_step(
                    params, opt, tok, lab, step_num=i + 2)
            float(jax.device_get(loss))
        iters = 3
    else:
        for i in range(iters):
            params, opt, loss = trainer.train_step(params, opt, tok, lab,
                                                   step_num=i + 2)
    final = float(jax.device_get(loss))
    dt = time.perf_counter() - t0
    assert np.isfinite(final), final
    toks = batch * cfg.seq_len * iters
    print(f"{dt / iters * 1e3:.1f} ms/step  {toks / dt:,.0f} tok/s  "
          f"loss={final:.4f}", flush=True)


if __name__ == "__main__":
    main()
