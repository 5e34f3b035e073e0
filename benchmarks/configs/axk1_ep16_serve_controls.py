"""What the comparison that decides `correct` in the cells of
`axk1_ep16_serve` tells apart. Every control goes through the driver's
own `compare` (`drivers/serve_frontend_axk1.py`), at the cell's sizes,
on the cell's sentinel, and prints what the cell's limits make of it:

    python3 benchmarks/configs/axk1_ep16_serve_controls.py \
        --seed N [--only engine,fp8_pool] [--rehearse]

  engine            the program as it is: must pass
  bf16_reference    the reference with operands rounded to bfloat16 and
                    float32 sums, the configuration's own precision: a
                    correct computation, must pass
  fp8_operands      every product's operands rounded to float8_e4m3 (a
                    scale a tensor), float32 sums: the nearest precision
                    below the configuration's, must fail
  fp8_pool          the reference with its latent rows rounded to
                    float8_e4m3 (a scale a row, as a quantized pool keeps
                    them) where attention reads them: must fail
  plain_rope        the rotary at the plain frequencies base^(-2i/64),
                    no YaRN: must fail
  no_mscale         the softmax scale without mscale^2 (x 0.55): must fail
  no_group_limit    routing over all 192 experts, no group limit: must fail
  half_rotary       the rotary on the other layout (pairs i, i + 32, the
                    half-split) instead of the interleaved pairs: must fail
  kpe_wrong_slice   the rotary key taken from the first 64 numbers of the
                    latent projection instead of its last 64: must fail

Every control but the engine is the reference computed otherwise: its
rows of logits, its tokens (their argmax) and its layer-0 latent rows
are held against the float32 reference as the engine's are. The faults
live here, not in the reference and not in the program. The last line
is a JSON object of the readings; `chiprun_out/controls/` keeps it, by
seed.
"""
import argparse
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

from harness import traffic as traffic_mod           # noqa: E402
from harness.files import load_json, load_module     # noqa: E402

CONFIG = "axk1_ep16_serve"
TRAFFIC = "longctx_closed_16"
CONTROLS = ("engine", "bf16_reference", "fp8_operands", "fp8_pool",
            "plain_rope", "no_mscale", "no_group_limit", "half_rotary",
            "kpe_wrong_slice")


def e4m3(x):
    """x rounded to float8_e4m3's 3 mantissa bits, kept in float32: a
    rounding the TPU compiler keeps (it folds a cast to a narrower type
    and back away). Its 4-bit exponent keeps an infinity, so the largest
    finite value is 240, not float8_e4m3fn's 448: callers scale to 240."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def control(kind, cfg):
    """(a copy of the reference module computing `kind`, its cfg)."""
    import jax.numpy as jnp
    ref = load_module("configs", CONFIG + "_reference")
    f32, bf16 = jnp.float32, jnp.bfloat16
    if kind == "bf16_reference":
        ref.mm = lambda x, w: jnp.dot(x.astype(bf16), w.astype(bf16),
                                      preferred_element_type=f32)
        ref.dots = lambda spec, a, b: jnp.einsum(
            spec, a.astype(bf16), b.astype(bf16),
            preferred_element_type=f32)
    elif kind == "fp8_pool":
        rows = ref.latent_rows

        def fp8(lw, x, pos, c):
            r = rows(lw, x, pos, c)
            s = jnp.max(jnp.abs(r), -1, keepdims=True) / 240.0 + 1e-30
            return e4m3(r / s) * s
        ref.latent_rows = fp8
    elif kind == "fp8_operands":
        def fp8(x):
            s = jnp.max(jnp.abs(x)).astype(f32) / 240.0 + 1e-30
            return e4m3(x.astype(f32) / s) * s
        ref.mm = lambda x, w: jnp.dot(fp8(x), fp8(w))
        ref.dots = lambda spec, a, b: jnp.einsum(spec, fp8(a), fp8(b))
    elif kind == "plain_rope":
        # the frequencies of factor 1; the scale stays YaRN's
        cfg = dict(ref.numbers(dict(cfg, factor=1.0)), scale=cfg["scale"])
    elif kind == "no_mscale":
        cfg = dict(cfg, scale=(cfg["nope_dim"] + cfg["rope_dim"]) ** -0.5)
    elif kind == "no_group_limit":
        cfg = dict(cfg, n_group=1, topk_group=1)
    elif kind == "half_rotary":
        cfg = dict(cfg, rope_layout="half")
    elif kind == "kpe_wrong_slice":
        def wrong(lw, x, pos, c):
            Dc, Dr = c["kv_lora_rank"], c["rope_dim"]
            kv = ref.mm(x, lw["kv_a"])
            return jnp.concatenate(
                [ref.rms(kv[:, :Dc], lw["kv_a_norm"], c["eps"]),
                 ref.rotary(kv[:, :Dr], pos, c)], -1)
        ref.latent_rows = wrong
    return ref, cfg


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    only = args.only.split(",")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.core.compile_cache import use_compile_cache
    from paddle_tpu.ops.pallas import interpret_mode
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def log(msg):
        print(f"controls: {msg}", flush=True)

    config = traffic_mod.with_rehearsal(
        load_json(BENCH, "configs", CONFIG + ".json"), args.rehearse)
    traffic = traffic_mod.with_rehearsal(
        load_json(BENCH, "traffic", TRAFFIC + ".json"), args.rehearse)
    limits = {k: v for k, v in config["reference"].items()
              if isinstance(v, (int, float))}
    log(f"{jax.devices()[0].device_kind}, seed {args.seed}, limits "
        f"{limits}")
    drivers = load_module("drivers", config["driver"])
    env = types.SimpleNamespace(
        config=config, config_name=CONFIG, traffic=traffic,
        seed=args.seed, rehearse=args.rehearse, log=log)

    def verdict(name, got, t0):
        bad = good.faults(got)
        err = got["err"]
        log(f"{name}: logits' error worst {err.max():.4f} mean "
            f"{err.mean():.4f} least {err.min():.4f} sigma, "
            f"{int((err > limits['logit_err_sigmas']).sum())} of "
            f"{len(err)} positions over; token margin worst "
            f"{got['margin'].max():.4f}; layer-0 latent rows "
            f"{got['cache_err0']:.4f}; {got['passes']} passes; "
            f"{'NOT CORRECT by ' + ', '.join(bad) if bad else 'correct'}"
            f" ({time.monotonic() - t0:.1f} s)")
        return {"err": [round(float(e), 5) for e in err],
                "err0": [round(float(e), 5) for e in got["err0"]],
                "margin": [round(float(m), 4) for m in got["margin"]],
                "cache_err0": round(got["cache_err0"], 5),
                "passes": got["passes"], "not_correct_by": sorted(bad),
                "swaps": {str(p): list(c)
                          for p, c in enumerate(got["swaps"]) if c}}

    out = {"seed": args.seed, "limits": limits}
    mode = interpret_mode() if args.rehearse else contextlib.nullcontext()
    with mode:
        t0 = time.monotonic()
        good = drivers.Driver(env)
        good.setup()
        answer, rows = good.sentinel_rows()
        prompt = good.source.sentinel().prompt
        if "engine" in only:
            out["engine"] = verdict(
                "engine", good.compare(prompt, answer, rows), t0)
        base = drivers.reference_cfg(
            good.model.arch, load_module("configs", CONFIG + "_reference"))
        N = len(answer)
        ids = jnp.asarray(list(prompt) + answer[:-1], jnp.int32)
        n = ids.shape[0] - N
        w = good.model.weights
        for kind in CONTROLS[1:]:
            if kind not in only:
                continue
            t0 = time.monotonic()
            ref, cfg = control(kind, base)
            lats = ref.prefix(w, ids[:n], cfg)
            z = np.asarray(jax.jit(lambda w, c, i: ref.forward(
                w, i, cfg, cache=c)[0])(w, lats, ids[n:]))
            out[kind] = verdict(kind, good.compare(
                prompt, answer, z, tokens=z.argmax(-1),
                cache0=np.asarray(lats[0])), t0)
            del lats
        good.loop.close()
    os.makedirs(os.path.join(ROOT, "chiprun_out", "controls"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls",
                           f"axk1_{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps({k: (v if not isinstance(v, dict) or "err" not in v
                          else {"worst_err": max(v["err"]),
                                "mean_err": round(float(np.mean(v["err"])),
                                                  5),
                                "cache_err0": v["cache_err0"],
                                "not_correct_by": v["not_correct_by"]})
                      for k, v in out.items()}), flush=True)


if __name__ == "__main__":
    main()
