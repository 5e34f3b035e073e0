"""What the comparison that decides `correct` in the cells of
`olmo_hybrid_7b_serve` tells apart. Every control goes through the
driver's own `compare` (`drivers/serve_frontend_olmo_hybrid.py`), at
the cell's sizes, on the cell's sentinel (4608 -> 32: nine prefill
chunks of state carry, then decode), and prints what the cell's limits
make of it:

    python3 benchmarks/configs/olmo_hybrid_7b_serve_controls.py \
        --seed N [--only engine,bf16_state] [--rehearse]

  engine          the program as it is: must pass
  bf16_operands   the reference in the configuration's own precision:
                  the operands of every product with a weight matrix and
                  of attention rounded to bfloat16, float32 sums, the
                  delta rule on float32 operands and a float32 state: a
                  correct computation, must pass
  bf16_everywhere the delta rule's operands (the state among them)
                  rounded to bfloat16 at every product too: one step
                  below what the configuration states; reported
  fp8_operands    every operand rounded to float8_e4m3 (a scale a
                  tensor), the nearest precision below: must fail
  bf16_state      the PROGRAM keeping each slot's recurrent state in
                  bfloat16 between steps: must fail
  dropped_carry   the PROGRAM starting every later prefill chunk of a
                  prompt from the zero state (the carry between two
                  chunks dropped): must fail
  dropped_tail    the PROGRAM starting every later prefill chunk with
                  zeros in place of the convolution's last three
                  inputs: must fail
  beta_x1         the PROGRAM with beta = sigmoid(.) and not twice it
                  (`linear_allow_neg_eigval` ignored): must fail

The faults live here, not in the reference and not in the program
(`faulty_program` patches the program's modules and undoes it). The last
line is a JSON object of the readings; `chiprun_out/controls/` keeps it,
by seed.
"""
import argparse
import contextlib
import gc
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

CONFIG = "olmo_hybrid_7b_serve"
TRAFFIC = "mixed_len_closed_32"
REFERENCE = ("bf16_operands", "bf16_everywhere", "fp8_operands")
PROGRAM = ("bf16_state", "dropped_carry", "dropped_tail", "beta_x1")
CONTROLS = ("engine",) + REFERENCE + PROGRAM


def low_precision_reference(kind):
    """A copy of the reference module whose products are taken with
    operands in a lower precision (sums and the carried state stay
    float32)."""
    import jax.numpy as jnp
    ref = load_module("configs", CONFIG + "_reference")
    f32, bf16 = jnp.float32, jnp.bfloat16

    def fp8(x):
        s = jnp.max(jnp.abs(x)).astype(f32) / 448.0 + 1e-30
        return ((x.astype(f32) / s).astype(jnp.float8_e4m3fn)
                .astype(f32) * s).astype(bf16)

    cast = fp8 if kind == "fp8_operands" else (lambda x: x.astype(bf16))
    ref.mm = lambda x, w: jnp.dot(cast(x), cast(w),
                                  preferred_element_type=f32)
    ref.dots = lambda spec, a, b: jnp.einsum(
        spec, cast(a), cast(b), preferred_element_type=f32)
    if kind != "bf16_operands":
        ref.state_dots = ref.dots
    return ref


@contextlib.contextmanager
def faulty_program(kind):
    """Patch the program so that it computes `kind`'s fault while the
    block is open. `beta_x1` is a fault of the configuration and is
    made by the caller (an architecture with `allow_neg_eigval` off)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import gated_delta as gd
    real = {n: getattr(gd, n) for n in
            ("gated_delta_ragged", "delta_chunks", "token_runs")}

    def rounded_state(*a, **kw):
        # `reduce_precision`: the TPU compiler folds a float32 ->
        # bfloat16 -> float32 pair of converts away
        import jax
        o, state = real["gated_delta_ragged"](*a, **kw)
        return o, jax.lax.reduce_precision(state, exponent_bits=8,
                                           mantissa_bits=7)

    def every_chunk_from_zero(runs, *a, **kw):
        n, start, length, slot, first = runs
        return real["delta_chunks"](
            (n, start, length, slot, jnp.where(length > 1, 0, first)),
            *a, **kw)

    def no_tail(runs, T):
        r, valid, off, fresh = real["token_runs"](runs, T)
        return r, valid, off, fresh | (runs[2][r] > 1)

    patch = {"bf16_state": ("gated_delta_ragged", rounded_state),
             "dropped_carry": ("delta_chunks", every_chunk_from_zero),
             "dropped_tail": ("token_runs", no_tail)}.get(kind)
    if patch:
        setattr(gd, *patch)
    try:
        yield
    finally:
        if patch:
            setattr(gd, patch[0], real[patch[0]])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    only = args.only.split(",")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

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
    limits = config["reference"]
    log(f"{jax.devices()[0].device_kind}, seed {args.seed}, limits "
        f"logit_err_sigmas {limits['logit_err_sigmas']} "
        f"logit_err_mean_sigmas {limits['logit_err_mean_sigmas']} "
        f"margin_sigmas {limits['margin_sigmas']}")
    drivers = load_module("drivers", config["driver"])

    def driver(cfg):
        env = types.SimpleNamespace(
            config=cfg, config_name=CONFIG, traffic=traffic,
            seed=args.seed, rehearse=args.rehearse, log=log)
        d = drivers.Driver(env)
        d.setup()
        return d

    def verdict(name, got, t0):
        err, margin = got["err"], got["margin"]
        bad = [n for n, v in (("logit_err_sigmas", err.max()),
                              ("logit_err_mean_sigmas", err.mean()),
                              ("margin_sigmas", margin.max()))
               if v > limits[n]]
        log(f"{name}: logits' error worst {err.max():.4f} mean "
            f"{err.mean():.4f} least {err.min():.4f} sigma, "
            f"{int((err > limits['logit_err_sigmas']).sum())} of "
            f"{len(err)} positions over; token margin worst "
            f"{margin.max():.4f}, {int((margin > 0).sum())} tokens off; "
            f"{'NOT CORRECT by ' + ', '.join(bad) if bad else 'correct'}"
            f" ({time.monotonic() - t0:.1f} s)")
        return {"err": [round(float(e), 5) for e in err],
                "margin": [round(float(m), 4) for m in margin],
                "not_correct_by": bad}

    out = {"seed": args.seed, "limits": {k: limits[k] for k in (
        "logit_err_sigmas", "logit_err_mean_sigmas", "margin_sigmas")}}
    mode = interpret_mode() if args.rehearse else contextlib.nullcontext()
    with mode:
        t0 = time.monotonic()
        good = driver(config)
        answer, rows = good.sentinel_rows()
        prompt = good.source.sentinel().prompt
        cfg = drivers.reference_cfg(good.model.arch)
        if "engine" in only:
            out["engine"] = verdict(
                "engine", good.compare(prompt, answer, rows), t0)
        N = len(answer)
        ids = jnp.asarray(list(prompt) + answer[:-1], jnp.int32)
        for kind in REFERENCE:
            if kind not in only:
                continue
            t0 = time.monotonic()
            ref = low_precision_reference(kind)
            z = np.asarray(jax.jit(lambda w, i: ref.logits(
                w, i, cfg, last=N))(good.model.weights, ids))
            out[kind] = verdict(kind, good.compare(
                prompt, answer, z, tokens=z.argmax(-1)), t0)
        good.loop.close()
        del good
        gc.collect()
        for kind in PROGRAM:
            if kind not in only:
                continue
            t0 = time.monotonic()
            with faulty_program(kind):
                bad = driver(dict(config, linear_allow_neg_eigval=False)
                             if kind == "beta_x1" else config)
                answer, rows = bad.sentinel_rows()
            # against the reference of the configuration AS PUBLISHED
            out[kind] = verdict(kind, bad.compare(
                prompt, answer, rows, cfg=cfg), t0)
            bad.loop.close()
            del bad
            gc.collect()
    os.makedirs(os.path.join(ROOT, "chiprun_out", "controls"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls",
                           f"olmo_{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps({k: (v if not isinstance(v, dict) or "err" not in v
                          else {"worst_err": max(v["err"]),
                                "mean_err": sum(v["err"]) / len(v["err"]),
                                "least_err": min(v["err"]),
                                "worst_margin": max(v["margin"]),
                                "not_correct_by": v["not_correct_by"]})
                      for k, v in out.items()}), flush=True)


if __name__ == "__main__":
    main()
