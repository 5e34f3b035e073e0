"""What the comparison that decides `correct` in the cell of
`sdar_30b_a3b_pp8_serve` tells apart. Every control goes through the
driver's own `compare` (`drivers/serve_frontend_sdar.py`), at the
cell's sizes, on the cell's sentinel, and prints what the cell's limits
make of it:

    python3 benchmarks/configs/sdar_30b_a3b_pp8_serve_controls.py \
        --seed N [--only engine,causal_mask] [--rehearse]

  engine           the program as it is: must pass
  bf16_operands    the reference with operands rounded to bfloat16 and
                   float32 sums, the configuration's own precision, fed
                   the engine's block states: a correct computation,
                   must pass
  fp8_operands     operands rounded to float8_e4m3 (a scale a tensor),
                   the nearest precision below: must fail
  causal_mask      the PROGRAM under a causal mask (the kernel and its
                   fallback given no `causal_block`): a masked row no
                   longer sees the decided rows behind it in its block
  dropped_pair     the PROGRAM dropping one (token, expert) pair a step
                   in its last layer (the first choice of the step's
                   first token), as a capacity slot that overflowed would
  sigmoid_router   the PROGRAM weighting the experts by sigmoid scores
                   (renormalised over the top-8) instead of softmax
                   probabilities
  stale_block      the PROGRAM without the commit pass: a block whose
                   positions are all decided counts as committed at
                   once, so the next block reads the K/V its last
                   denoise pass left (one position still masked)

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

CONFIG = "sdar_30b_a3b_pp8_serve"
TRAFFIC = "mixed_len_closed_32"
REFERENCE = ("bf16_operands", "fp8_operands")
PROGRAM = ("causal_mask", "dropped_pair", "sigmoid_router", "stale_block")
CONTROLS = ("engine",) + REFERENCE + PROGRAM


def low_precision_reference(kind):
    """A copy of the reference module whose products are taken in a
    lower precision (float32 sums)."""
    import jax
    import jax.numpy as jnp
    ref = load_module("configs", CONFIG + "_reference")
    f32, bf16 = jnp.float32, jnp.bfloat16

    def fp8(x):
        s = jnp.max(jnp.abs(x)).astype(f32) / 448.0 + 1e-30
        return ((x.astype(f32) / s).astype(jnp.float8_e4m3fn)
                .astype(f32) * s).astype(bf16)

    def bf(x):
        # the TPU compiler folds astype(bf16).astype(f32) away
        return jax.lax.reduce_precision(
            x.astype(f32), exponent_bits=8, mantissa_bits=7).astype(bf16)

    cast = fp8 if kind == "fp8_operands" else bf
    ref.mm = lambda x, w: jnp.dot(cast(x), cast(w),
                                  preferred_element_type=f32)
    ref.dots = lambda spec, a, b: jnp.einsum(
        spec, cast(a), cast(b), preferred_element_type=f32)
    return ref


def plant(kind, layers):
    """Patch the program with fault `kind`. Returns the undo."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import sdar_moe
    from paddle_tpu.serving import scheduler as scheduler_mod

    if kind == "causal_mask":
        from paddle_tpu.ops.pallas import flash_attention as fa
        real = fa.ragged_paged_attention

        def causal(*a, causal_block=None, **kw):
            return real(*a, **kw)

        fa.ragged_paged_attention = causal
        return lambda: setattr(fa, "ragged_paged_attention", real)
    if kind in ("dropped_pair", "sigmoid_router"):
        real, calls = sdar_moe.route_softmax_topk, [0]

        def faulty(x, w_router, top_k, **kw):
            idx, wts = real(x, w_router, top_k, **kw)
            if kind == "sigmoid_router":
                s = jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.float32), w_router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST))
                w = jnp.take_along_axis(s, idx, axis=1)
                return idx, w / jnp.sum(w, axis=-1, keepdims=True)
            calls[0] += 1
            if calls[0] % layers:
                return idx, wts
            return idx, wts.at[0, 0].set(0.0)

        sdar_moe.route_softmax_topk = faulty
        return lambda: setattr(sdar_moe, "route_softmax_topk", real)
    if kind == "stale_block":
        real = scheduler_mod.Scheduler.plan

        def plan(self):
            for req in self.slots:
                if req is not None and req.state == "decode" \
                        and all(req.block_decided):
                    L = len(req.block_decided)
                    self.kv.slot_lens[req.slot] = req.block_start + L
                    self._open_block(req, req.block_start + L)
            return real(self)

        scheduler_mod.Scheduler.plan = plan
        return lambda: setattr(scheduler_mod.Scheduler, "plan", real)
    raise ValueError(kind)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", default="", metavar="KEY=VALUE,...",
                    help="try other limits than the configuration's "
                         "(`reference` keys), to see what they would "
                         "make of the same readings")
    args = ap.parse_args()
    only = args.only.split(",")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import contextlib

    import jax
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
    for pair in filter(None, args.set.split(",")):
        key, value = pair.split("=")
        config["reference"][key] = type(config["reference"][key])(value)
    limits = {k: config["reference"][k] for k in (
        "logit_err_sigmas", "logit_err_mean_sigmas", "logit_search_sigmas",
        "margin_sigmas", "tie_gap", "confidence_tie_gap", "max_passes")}
    log(f"{jax.devices()[0].device_kind}, seed {args.seed}, limits "
        f"{limits}")
    drivers = load_module("drivers", config["driver"])

    def driver():
        env = types.SimpleNamespace(
            config=config, config_name=CONFIG, traffic=traffic,
            seed=args.seed, rehearse=args.rehearse, log=log)
        d = drivers.Driver(env)
        d.setup()
        return d

    def verdict(name, got, t0):
        err, margin = got["err"], got["margin"]
        bad = [n for n, v, lim in (
            ("logit_err_sigmas", err.max(), limits["logit_err_sigmas"]),
            ("logit_err_mean_sigmas", err.mean(),
             limits["logit_err_mean_sigmas"]),
            ("margin_sigmas", margin.max() if len(margin) else 0.0,
             limits["margin_sigmas"]),
            ("decisions", len(got["faults"]), 0)) if v > lim]
        log(f"{name}: rows' error worst {err.max():.4f} mean "
            f"{err.mean():.4f} least {err.min():.4f} sigma, "
            f"{int((err > limits['logit_err_sigmas']).sum())} of "
            f"{err.size} rows over; against the reference's own choice "
            f"worst {got['err0'].max():.4f} mean {got['err0'].mean():.4f}"
            f"; {len(got['swaps'])} rows took a near-tie's other answer; "
            f"token margin worst "
            f"{margin.max() if len(margin) else 0.0:.4f} over "
            f"{len(margin)} decisions; decision faults "
            f"{got['faults'][:2] or 'none'}; {got['passes']} passes; "
            f"the largest rows "
            f"{[round(float(e), 4) for e in sorted(err.ravel())[-8:]]}; "
            f"{'NOT CORRECT by ' + ', '.join(bad) if bad else 'correct'}"
            f" ({time.monotonic() - t0:.1f} s)")
        return {"worst_err": round(float(err.max()), 5),
                "mean_err": round(float(err.mean()), 5),
                "least_err": round(float(err.min()), 5),
                "worst_err0": round(float(got["err0"].max()), 5),
                "rows_over": int((err > limits["logit_err_sigmas"]).sum()),
                "rows": int(err.size), "swapped_rows": len(got["swaps"]),
                "worst_margin": round(float(
                    margin.max() if len(margin) else 0.0), 4),
                "decision_faults": len(got["faults"]),
                "passes": got["passes"], "not_correct_by": bad}

    out = {"seed": args.seed, "limits": limits}
    mode = interpret_mode() if args.rehearse else contextlib.nullcontext()
    with mode:
        t0 = time.monotonic()
        good = driver()
        prompt = good.source.sentinel().prompt
        layers = good.model.arch.num_layers
        answer, passes = good.sentinel_rows()
        log(f"the sentinel ({len(prompt)} -> {len(answer)}) took "
            f"{len(passes)} passes")
        if "engine" in only:
            out["engine"] = verdict("engine",
                                    good.compare(prompt, passes), t0)
        for kind in REFERENCE:
            if kind not in only:
                continue
            # the low-precision computation's rows, fed the engine's
            # block states, in place of the engine's rows
            t0 = time.monotonic()
            low = low_precision_reference(kind)
            rows = good.compare(prompt, passes, ref=low, rows_only=True)
            # its own candidates at the positions the engine decided
            out[kind] = verdict(kind, good.compare(prompt, [
                p[:4] + (tuple(int(z[i].argmax()) for i in p[3]), z)
                for p, z in zip(passes, rows)]), t0)
        good.loop.close()
        del good
        gc.collect()
        for kind in PROGRAM:
            if kind not in only:
                continue
            t0, undo = time.monotonic(), plant(kind, layers)
            try:
                bad = driver()
                answer, passes = bad.sentinel_rows()
            finally:
                undo()
            out[kind] = verdict(kind, bad.compare(prompt, passes), t0)
            bad.loop.close()
            del bad
            gc.collect()
    os.makedirs(os.path.join(ROOT, "chiprun_out", "controls"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls",
                           f"sdar_{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
