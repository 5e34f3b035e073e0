"""What the comparison that decides `correct` in the cells of
`trinity_large_ep8_serve` tells apart. Every control goes through the
driver's own `compare` (`drivers/serve_frontend_afmoe.py`), at the
cell's sizes, on the cell's sentinel, and prints what the cell's limits
make of it:

    python3 benchmarks/configs/trinity_large_ep8_serve_controls.py \
        --seed N [--only engine,window_short] [--rehearse]

  engine           the program as it is: must pass
  bf16_operands    the reference with operands rounded to bfloat16 and
                   float32 sums, the configuration's own precision: a
                   correct computation, must pass
  fp8_operands     operands rounded to float8_e4m3 (a scale a tensor),
                   the nearest precision below: must fail
  bf16_accumulate  bfloat16 operands and a running sum rounded to
                   bfloat16 after every 8 terms: must fail
  window_short     the PROGRAM with a window one block short (engine
                   and kernel at `sliding_window - block_size`) against
                   the reference at the source's window: must fail
  dropped_pair     the PROGRAM dropping one (token, expert) pair a step
                   in its last expert layer (the first held choice of
                   the step's first token), as a capacity slot that
                   overflowed would: must fail

The faults live here, not in the reference and not in the program. The
last line is a JSON object of the readings; `chiprun_out/controls/`
keeps it, by seed.
"""
import argparse
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

CONFIG = "trinity_large_ep8_serve"
TRAFFIC = "mixed_len_closed_32"
CONTROLS = ("engine", "bf16_operands", "fp8_operands", "bf16_accumulate",
            "window_short", "dropped_pair")
ACCUMULATE_TERMS = 8


def low_precision_reference(kind):
    """A copy of the reference module whose products are taken in a
    lower precision."""
    import jax
    import jax.numpy as jnp
    ref = load_module("configs", CONFIG + "_reference")
    f32, bf16 = jnp.float32, jnp.bfloat16

    def fp8(x):
        s = jnp.max(jnp.abs(x)).astype(f32) / 448.0 + 1e-30
        return ((x.astype(f32) / s).astype(jnp.float8_e4m3fn)
                .astype(f32) * s).astype(bf16)

    cast = fp8 if kind == "fp8_operands" else (lambda x: x.astype(bf16))

    def mm(x, w):
        if kind != "bf16_accumulate":
            return jnp.dot(cast(x), cast(w), preferred_element_type=f32)
        c = min(ACCUMULATE_TERMS, x.shape[1])
        xs = cast(x).reshape(x.shape[0], -1, c).swapaxes(0, 1)
        ws = cast(w).reshape(-1, c, w.shape[1])

        def add(acc, part):
            y = jnp.dot(part[0], part[1], preferred_element_type=f32)
            return (acc.astype(f32) + y).astype(bf16), None

        acc, _ = jax.lax.scan(
            add, jnp.zeros((x.shape[0], w.shape[1]), bf16), (xs, ws))
        return acc.astype(f32)

    ref.mm = mm
    ref.dots = lambda spec, a, b: jnp.einsum(
        spec, cast(a), cast(b), preferred_element_type=f32)
    return ref


def drop_a_pair(afmoe, expert_layers, held, rank):
    """Patch the program's router: in the last expert layer, the first
    choice of the step's first token that lands on a held expert gets
    weight 0. Returns the undo."""
    import jax.numpy as jnp
    real, calls = afmoe.route_sigmoid_topk, [0]

    def faulty(x, router, bias, top_k, **kw):
        idx, wts = real(x, router, bias, top_k, **kw)
        calls[0] += 1
        if calls[0] % expert_layers:
            return idx, wts
        held_here = idx[0] // held == rank                       # [k]
        first = jnp.argmax(held_here)
        gone = held_here.any() & (jnp.arange(idx.shape[1]) == first)
        return idx, wts.at[0].set(jnp.where(gone, 0.0, wts[0]))

    afmoe.route_sigmoid_topk = faulty
    return lambda: setattr(afmoe, "route_sigmoid_topk", real)


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
    from paddle_tpu.models import afmoe
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
        f"logit_err_sigmas {limits['logit_err_sigmas']} margin_sigmas "
        f"{limits['margin_sigmas']} tie_gap {limits['tie_gap']}")
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
        bad = [n for n, v, lim in (
            ("logit_err_sigmas", err.max(), limits["logit_err_sigmas"]),
            ("margin_sigmas", margin.max(), limits["margin_sigmas"]))
            if v > lim]
        log(f"{name}: logits' error worst {err.max():.4f} mean "
            f"{err.mean():.4f} least {err.min():.4f} sigma, "
            f"{int((err > limits['logit_err_sigmas']).sum())} of "
            f"{len(err)} positions over; token margin worst "
            f"{margin.max():.4f}, {int((margin > 0).sum())} tokens off; "
            f"{got['passes']} passes; "
            f"{'NOT CORRECT by ' + ', '.join(bad) if bad else 'correct'}"
            f" ({time.monotonic() - t0:.1f} s)")
        return {"err": [round(float(e), 5) for e in err],
                "err0": [round(float(e), 5) for e in got["err0"]],
                "margin": [round(float(m), 4) for m in margin],
                "passes": got["passes"], "not_correct_by": bad,
                "swaps": {str(p): list(c)
                          for p, c in enumerate(got["swaps"]) if c}}

    out = {"seed": args.seed, "limits": {k: limits[k] for k in (
        "logit_err_sigmas", "margin_sigmas", "tie_gap")}}
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
        for kind in ("bf16_operands", "fp8_operands", "bf16_accumulate"):
            if kind not in only:
                continue
            t0 = time.monotonic()
            ref = low_precision_reference(kind)
            z = np.asarray(jax.jit(lambda w, i: ref.logits(
                w, i, cfg, last=N)[0])(good.model.weights, ids))
            out[kind] = verdict(kind, good.compare(
                prompt, answer, z, tokens=z.argmax(-1)), t0)
        block = good.engine.block_size
        moe = good.model.arch.moe
        expert_layers = sum(l.ffn == afmoe.MOE
                            for l in good.model.arch.layers)
        good.loop.close()
        del good
        gc.collect()
        for kind in ("window_short", "dropped_pair"):
            if kind not in only:
                continue
            t0, undo = time.monotonic(), lambda: None
            if kind == "window_short":
                window = config["sliding_window"]
                bad = driver(dict(
                    config, sliding_window=window - block
                    if window > block else window // 2))
            else:
                undo = drop_a_pair(afmoe, expert_layers,
                                   moe.experts_held, moe.expert_rank)
                bad = driver(config)
            try:
                answer, rows = bad.sentinel_rows()
            finally:
                undo()
            # against the reference of the configuration AS PUBLISHED
            out[kind] = verdict(kind, bad.compare(
                prompt, answer, rows, cfg=cfg), t0)
            bad.loop.close()
            del bad
            gc.collect()
    os.makedirs(os.path.join(ROOT, "chiprun_out", "controls"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls",
                           f"{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps({k: (v if not isinstance(v, dict) or "err" not in v
                          else {"worst_err": max(v["err"]),
                                "worst_margin": max(v["margin"]),
                                "not_correct_by": v["not_correct_by"]})
                      for k, v in out.items()}), flush=True)


if __name__ == "__main__":
    main()
