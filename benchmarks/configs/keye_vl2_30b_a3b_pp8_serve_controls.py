"""What the comparison that decides `correct` in the cell of
`keye_vl2_30b_a3b_pp8_serve` tells apart. Every control goes through the
driver's own `compare` (`drivers/serve_frontend_keye.py`), at the cell's
widths, on the cell's sentinel (8192 -> 32), and prints what the cell's
limits make of it:

    python3 benchmarks/configs/keye_vl2_30b_a3b_pp8_serve_controls.py \
        --seed N [--only engine,no_relu] [--rehearse]

  engine           the program as it is: must pass
  bf16_operands    the reference with operands rounded to bfloat16 and
                   float32 sums, the configuration's own precision (its
                   own cache and selections, from its own bf16 scores):
                   a correct computation, must pass
  fp8_operands     operands rounded to float8_e4m3 (a scale a tensor)
                   in every product, the nearest precision below: must
                   fail
  dense            the PROGRAM with no selection: every row attends
                   every key the causal rule allows
  select_1024      the PROGRAM selecting the 1024 best keys
  no_relu          the PROGRAM's indexer scores without the relu
  unweighted       the PROGRAM's indexer heads unweighted (w = the
                   constant scale)
  stale_key        the PROGRAM not writing a decode token's indexer key
                   (the pool keeps what lay there)
  previous_row     the PROGRAM's decode rows attending the selection of
                   the row before them in the step (an off-by-one in the
                   gather)

The faults live here, not in the reference and not in the program. The
pools are cut to 2049 blocks (the sentinel alone holds 515): the faults'
temporaries (a dense gather is 1.1 GB) then fit beside the weights;
nothing the comparison reads depends on the pool's size. The last line
is a JSON object of the readings; `chiprun_out/controls/` keeps it, by
seed.
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

CONFIG = "keye_vl2_30b_a3b_pp8_serve"
TRAFFIC = "longctx_closed_16"
REFERENCE = ("bf16_operands", "fp8_operands")
PROGRAM = ("dense", "select_1024", "no_relu", "unweighted", "stale_key",
           "previous_row")
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


def plant(kind):
    """Patch the program with fault `kind`. Returns the undo."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import sdar_moe
    from paddle_tpu.ops.pallas import topk_select
    from paddle_tpu.serving import engine as engine_mod

    if kind == "dense":
        mask, at = topk_select.topk_mask, topk_select.mask_positions
        topk_select.topk_mask = lambda scores, k, cand: cand
        topk_select.mask_positions = lambda keep, k: at(
            keep, keep.shape[1])        # every candidate's position

        def undo():
            topk_select.topk_mask, topk_select.mask_positions = mask, at
        return undo
    if kind == "no_relu":
        real = jax.nn.relu
        jax.nn.relu = lambda x: x
        return lambda: setattr(jax.nn, "relu", real)
    if kind == "unweighted":
        real = sdar_moe.indexer_proj

        def proj(arch, lw, x, positions):
            qI, kI, w = real(arch, lw, x, positions)
            return qI, kI, jnp.full_like(w, arch.selection.scale)

        sdar_moe.indexer_proj = proj
        return lambda: setattr(sdar_moe, "indexer_proj", real)
    if kind == "stale_key":
        real = engine_mod._SparseLayers.attend

        def attend(self, pools, at, ix, q, k, v, idx, wb, wo, *rest):
            old = pools[ix]
            out = real(self, pools, at, ix, q, k, v, idx, wb, wo, *rest)
            sp = rest[-1]
            # the one-token runs' rows of the pool, as they were
            live = sp["d_to"] < wb.shape[0]
            blk = jnp.where(live, wb[sp["d_row"]], 0)
            off = wo[sp["d_row"]]
            pools[ix] = pools[ix].at[blk, off].set(old[blk, off])
            return out

        engine_mod._SparseLayers.attend = attend
        return lambda: setattr(engine_mod._SparseLayers, "attend", real)
    if kind == "previous_row":
        real = topk_select.mask_positions

        def shifted(keep, k):
            return jnp.roll(real(keep, k), 1, axis=0)

        topk_select.mask_positions = shifted
        return lambda: setattr(topk_select, "mask_positions", real)
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
    import copy

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
    if not args.rehearse:
        config["engine"]["num_blocks"] = 2049
    for pair in filter(None, args.set.split(",")):
        key, value = pair.split("=")
        config["reference"][key] = type(config["reference"][key])(value)
    limits = {k: v for k, v in config["reference"].items()
              if isinstance(v, (int, float))}
    log(f"{jax.devices()[0].device_kind}, seed {args.seed}, limits "
        f"{limits}")

    def driver(cfg=config):
        env = types.SimpleNamespace(
            config=cfg, config_name=CONFIG, traffic=traffic,
            seed=args.seed, rehearse=args.rehearse, log=log)
        d = load_module("drivers", cfg["driver"]).Driver(env)
        d.setup()
        return d

    def verdict(name, d, got, t0):
        err, fwd, margin = got["err"], got["err_fwd"], got["margin"]
        bad = sorted(d.faults(got))
        log(f"{name}: rows' error worst {err.max():.4f} mean "
            f"{err.mean():.4f} least {err.min():.4f} sigma; against the "
            f"reference's full forward worst {fwd.max():.4f} mean "
            f"{fwd.mean():.4f}; before the routers' search worst "
            f"{got['err_sel'].max():.4f}; "
            f"{sum(1 for s in got['swaps'] if s)} rows took a router's "
            f"near-tie's other answer; token margin worst "
            f"{margin.max():.4f}; selections: at most "
            f"{got['sel_members']} members of a row differ, the widest "
            f"{got['sel_gap']:.4f} sigma from the k-th largest score, "
            f"{len(got['sel_faults'])} rows at fault "
            f"{got['sel_faults'][:2] or ''}; cache: layer 0 largest "
            f"{got['cache_err'][0]:.4f}, later medians "
            f"{[round(v, 4) for v in got['cache_err'][1:]]}, K rows "
            f"moved {[round(v, 3) for v in got['cache_moved']]}; "
            f"{got['passes']} passes; "
            f"{'NOT CORRECT by ' + ', '.join(bad) if bad else 'correct'}"
            f" ({time.monotonic() - t0:.1f} s)")
        return {"worst_err": round(float(err.max()), 5),
                "mean_err": round(float(err.mean()), 5),
                "worst_err_fwd": round(float(fwd.max()), 5),
                "mean_err_fwd": round(float(fwd.mean()), 5),
                "worst_err_sel": round(float(got["err_sel"].max()), 5),
                "swapped_rows": sum(1 for s in got["swaps"] if s),
                "worst_margin": round(float(margin.max()), 4),
                "sel_members": int(got["sel_members"]),
                "sel_gap": round(float(got["sel_gap"]), 5),
                "sel_faults": len(got["sel_faults"]),
                "cache_err": [round(v, 5) for v in got["cache_err"]],
                "cache_moved": [round(v, 4) for v in got["cache_moved"]],
                "passes": got["passes"], "not_correct_by": bad}

    out = {"seed": args.seed, "limits": limits}
    mode = interpret_mode() if args.rehearse else contextlib.nullcontext()
    with mode:
        t0 = time.monotonic()
        good = driver()
        prompt = good.source.sentinel().prompt
        arch = good.model.arch
        answer, rows = good.sentinel_rows()
        log(f"the sentinel ({len(prompt)} -> {len(answer)}) served")
        if "engine" in only:
            out["engine"] = verdict(
                "engine", good, good.compare(prompt, answer, rows), t0)
        for kind in REFERENCE:
            if kind not in only:
                continue
            # the low-precision computation's own rows and selections,
            # teacher-forced along the engine's tokens, in place of the
            # engine's
            t0 = time.monotonic()
            low = low_precision_reference(kind)
            z, sels, cache = good.compare(prompt, answer, rows, ref=low,
                                          rows_only=True)
            out[kind] = verdict(kind, good, good.compare(
                prompt, answer, z, tokens=z.argmax(-1), selections=sels,
                cache=cache), t0)
        good.loop.close()
        del good, rows
        gc.collect()
        for kind in PROGRAM:
            if kind not in only:
                continue
            t0 = time.monotonic()
            cfg = config
            if kind == "select_1024":
                cfg = copy.deepcopy(config)
                cfg["sa_config"]["topk"] //= 2
                undo = lambda: None                       # noqa: E731
            else:
                undo = plant(kind)
            try:
                bad = driver(cfg)
                answer, rows = bad.sentinel_rows()
            finally:
                undo()
            # held against the reference of the REAL architecture
            bad.model = types.SimpleNamespace(
                arch=arch, weights=bad.model.weights)
            out[kind] = verdict(kind, bad,
                                bad.compare(prompt, answer, rows), t0)
            bad.loop.close()
            del bad, rows
            gc.collect()
    os.makedirs(os.path.join(ROOT, "chiprun_out", "controls"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls",
                           f"keye_{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
