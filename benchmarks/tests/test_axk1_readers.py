"""The readers of the latent attention (`kernels.mla_attend_roofline`,
`mla.attn_ms_per_step`, `mla.useful_logits_pct`) on hand-made flight
records and scope splits with known answers, and on the records of a
program that has none of their fields (the parent commit): nothing, and
no exception. The harness's least-work count at the A.X-K1 widths; the
manifest's new entries; the configuration file against the catalog's
keys; what the cell's driver makes of a comparison's readings."""
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import device_scopes, mla_attention  # noqa: E402
from harness.files import load_module  # noqa: E402

CELL = "serve_axk1_ep16_longctx"
with open(os.path.join(BENCH, "configs", "axk1_ep16_serve.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("kernels.mla_attend_roofline", "mla.attn_ms_per_step",
       "mla.useful_logits_pct")


class Trace:
    def __init__(self, seconds, steps):
        self.seconds, self.steps = seconds, steps

    def seconds_of(self, name):
        return self.seconds.get(name, 0.0)

    def calls_of(self, name, kind=None):
        return self.steps if kind == "modules" else 7 * self.steps


def context(flight, seconds=None, steps=100, scopes=None):
    logged = []
    ctx = types.SimpleNamespace(
        trace=Trace(seconds or {}, steps), spans=[], flight=flight,
        steps=None, counters={}, config=CONFIG, traffic={}, peaks=PEAKS,
        log=logged.append)
    ctx._device_scopes = None if scopes is None else device_scopes.Split(
        steps=steps, known=1.0,
        ops={s: {"fusion": ms} for s, ms in scopes.items()})
    return ctx, logged


def record(**more):
    # 10 decode rows at ~20k beside 4 chunks of 125 rows at ~16k, 7 layers
    L = 7
    return dict({"ts": 100.0, "dur": 0.06, "prefill_tokens": 500,
                 "decode_tokens": 10, "mla_rows_decode": L * 10,
                 "mla_rows_chunk": L * 500, "mla_pairs_decode": L * 200000,
                 "mla_pairs_chunk": L * 8031250,
                 "mla_tokens_read": L * (200000 + 64500),
                 "mla_logits_useful": 64 * L * 8231250,
                 "mla_logits_issued": 64 * L * 8800000}, **more)


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_least_work_at_the_published_widths():
    """The issue's numbers: 139,264 FLOPs a pair absorbed, 40,960
    expanded, 16.8 MFLOPs a row absorbed and a context token expanded,
    1152 B a cached token, 16.8 MB of W_UK and W_UV a layer."""
    one = dict(layers=1, heads=64, latent=512, rope=64, nope=128,
               value=128)
    f, b = mla_attention.mla_least_step(0, 1, 0, 1000, 1000, **one)
    assert f == 1000 * 139264 + 2 * 64 * 128 * 512 * 2
    assert b == 1000 * 1152 + 512 * 64 * 256 * 2 + 64 * 320 * 2
    # a run of 200 rows over 200 keys: expanded is the lesser
    rows, pairs = 200, 200 * 201 // 2
    f, _ = mla_attention.mla_least_step(0, rows, 0, pairs, 200, **one)
    assert f == pairs * 40960 + 200 * 2 * 512 * 64 * 256
    assert f < pairs * 139264 + rows * 2 * 64 * 128 * 512 * 2


def test_roofline_known_answer():
    ctx, logged = context([record()], scopes={"attn_latent": 30.0,
                                              "mla_absorb": 2.0})
    r = record()
    flops, nbytes = mla_attention.mla_least_step(
        r["mla_rows_decode"], r["mla_rows_chunk"], r["mla_pairs_decode"],
        r["mla_pairs_chunk"], r["mla_tokens_read"], 7, 64, 512, 64, 128,
        128)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read(NEW[0], ctx) == pytest.approx(100 * least / 0.032)
    assert "compute bound" in logged[-1]


def test_ms_per_step_sums_the_two_scopes():
    ctx, logged = context([record()], seconds={"mla_attend": 2.9},
                          scopes={"attn_latent": 30.0, "mla_absorb": 2.0,
                                  "attn_qkv": 1.0})
    assert read(NEW[1], ctx) == pytest.approx(32.0)
    assert "the kernel 29.000" in logged[-1]
    assert "2.43% are decode rows'" in logged[-1]


def test_useful_share_known_answer():
    ctx, logged = context([record(), record(mla_logits_issued=64 * 7 *
                                            9000000)])
    assert read(NEW[2], ctx) == pytest.approx(
        100 * 2 * 8231250 / (8800000 + 9000000))


def test_nothing_to_read_is_none():
    old = [{"ts": 100.0, "dur": 0.03, "prefill_tokens": 96,
            "decode_tokens": 32, "kv_tokens_read": 9000}]
    for flight in (old, []):
        for name in NEW:
            assert read(name, context(flight)[0]) is None
    # the fields but no such scope (a split of another program)
    ctx, _ = context([record()], scopes={"attn_full": 3.0})
    assert read(NEW[0], ctx) is None and read(NEW[1], ctx) is None


def test_the_manifest_s_new_entries():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL] == dict(
        name=CELL, config="axk1_ep16_serve", traffic="longctx_closed_16",
        chips=1, why=cells[CELL]["why"])
    assert len(cells[CELL]["why"]) <= 200
    per = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert per[name]["workloads"] == [CELL]
        assert per[name]["moves"] == "serve_tokens_per_s"
    assert [m["name"] for m in MANIFEST["per_layer"][-3:]] == list(NEW)


def test_the_configuration_keeps_the_catalog_s_numbers():
    """Every number of the source's config is in the file under its
    key, but the four cuts `reduced` names; nested groups whole."""
    src = CONFIG["source_config"]
    for k, v in src.items():
        if k in CONFIG["reduced"]:
            assert CONFIG[k] != v
        else:
            assert CONFIG[k] == v, k
    assert CONFIG["n_routed_experts_published"] == 192
    assert CONFIG["vocab_size"] * 8 == CONFIG["vocab_size_published"]


def test_the_driver_s_faults():
    env = types.SimpleNamespace(config=CONFIG, log=print)
    d = load_module("drivers", "serve_frontend_axk1").Driver(env)
    import numpy as np
    rc = CONFIG["reference"]
    fine = {"err": np.full(4, rc["logit_err_sigmas"] / 2),
            "margin": np.zeros(4), "cache_err0": 0.0}
    assert d.faults(fine) == {}
    bad = dict(fine, cache_err0=2 * rc["cache_err_layer0"])
    assert list(d.faults(bad)) == ["cache_err_layer0"]
    d.loop.close()
