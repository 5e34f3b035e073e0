"""The readers of PR 37 (`sparse_attn.kv_read_share_pct`,
`sparse_attn.indexer_ms_per_step`, `kernels.sparse_attend_roofline`) on
hand-made flight records and scope splits with known answers, and on
the records of a program that has none of their fields (the parent
commit): nothing, and no exception. The shared paged-attention and
expert readers on the Keye cell's configuration; the manifest's new
entries; the configuration file against the catalog's keys; what the
cell's driver makes of a comparison's readings (`faults`), that the
sentinel through the frontend is tied to the rows that were compared,
and that the routers' search ends on ONE pass that holds every row
(`settle_routers`: a row tipped by an earlier row's answer is searched
again)."""
import json
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import (device_scopes, moe_experts,  # noqa: E402
                     paged_attention_gqa, sparse_attention)
from harness.files import load_module  # noqa: E402

CELL = "serve_keye_vl2_30b_a3b_longctx"
with open(os.path.join(BENCH, "configs",
                       "keye_vl2_30b_a3b_pp8_serve.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("sparse_attn.kv_read_share_pct", "sparse_attn.indexer_ms_per_step",
       "kernels.sparse_attend_roofline")


class Trace:
    def __init__(self, seconds, steps):
        self.seconds, self.steps = seconds, steps

    def seconds_of(self, name):
        return self.seconds.get(name, 0.0)

    def calls_of(self, name, kind=None):
        return self.steps if kind == "modules" else 6 * self.steps


def context(flight, seconds=None, steps=100, scopes=None, **more):
    logged = []
    ctx = types.SimpleNamespace(
        trace=Trace(seconds or {}, steps), spans=[], flight=flight,
        steps=None, counters={}, config=CONFIG, traffic={}, peaks=PEAKS,
        log=logged.append, **more)
    # the split a device trace would give, handed over ready-made
    ctx._device_scopes = None if scopes is None else device_scopes.Split(
        steps=steps, known=1.0,
        ops={s: {"fusion": ms} for s, ms in scopes.items()})
    return ctx, logged


def record(**more):
    # 12 decode rows at ~18k beside 500 chunk rows of one prompt at 9k
    return dict({"ts": 100.0, "dur": 0.07, "prefill_tokens": 500,
                 "decode_tokens": 12, "sparse_rows_decode": 12,
                 "sparse_rows_chunk": 500,
                 "sparse_kv_tokens_context": 216000,
                 "sparse_kv_tokens_read": 24576,
                 "idx_keys_scored": 4900000,
                 "sparse_pairs_causal": 4625000,
                 "sparse_pairs_kept": 1024000, "idx_pool_bytes": 565e6,
                 "kv_tokens_read_window": 0, "attn_pairs_window": 0,
                 "kv_tokens_read_full": 9500,
                 "attn_pairs_full": 4625000, "moe_pairs_local": 24576,
                 "moe_pairs_total": 24576, "moe_experts_hit": 760,
                 "moe_max_expert_pairs": 60}, **more)


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_read_share_known_answer():
    flight = [record(), record(sparse_kv_tokens_context=40000,
                               sparse_kv_tokens_read=20480,
                               sparse_rows_decode=10)]
    ctx, logged = context(flight)
    assert read(NEW[0], ctx) == pytest.approx(
        100 * (24576 + 20480) / (216000 + 40000))
    assert "11.0 decode rows a step" in logged[-1]
    assert "keep 22.1%" in logged[-1]


def test_indexer_sums_its_three_scopes():
    ctx, logged = context([record()], scopes={
        "idx_proj": 0.2, "idx_score": 9.5, "idx_select": 8.0,
        "attn_sparse": 7.9, "attn_full": 0.7})
    assert read(NEW[1], ctx) == pytest.approx(17.7)
    assert "idx_score 9.500" in logged[-1]


def test_sparse_attend_roofline_known_answer():
    ctx, logged = context([record()], scopes={"attn_sparse": 8.0})
    flops, nbytes = sparse_attention.sparse_attend_step(
        24576, 12, 6, heads=32, kv_heads=4, head_dim=128)
    assert nbytes == 6 * (24576 * 2 * 4 * 128 * 2 + 12 * 2 * 32 * 128 * 2)
    assert flops == 6 * 24576 * 4 * 32 * 128
    least = max(flops / 197e12, nbytes / 819e9)
    assert read(NEW[2], ctx) == pytest.approx(100 * least / 0.008)
    assert "bandwidth bound" in logged[-1]


def test_nothing_to_read_is_none():
    old = [{"ts": 100.0, "dur": 0.03, "prefill_tokens": 96,
            "decode_tokens": 32, "kv_tokens_read": 9000}]
    for flight in (old, []):
        for name in NEW:
            assert read(name, context(flight)[0]) is None
    # a program with the fields but no such scope; a step with no
    # decode row
    assert read(NEW[1], context([record()], scopes={"mlp": 1.0})[0]) \
        is None
    none = record(sparse_rows_decode=0, sparse_kv_tokens_context=0,
                  sparse_kv_tokens_read=0)
    assert read(NEW[0], context([none])[0]) is None
    assert read(NEW[2], context([none],
                                scopes={"attn_sparse": 1.0})[0]) is None


def test_the_shared_readers_take_this_configuration():
    ctx, _ = context([record()], {"paged_ragged": 2.4,
                                  "moe_experts": 1.0})
    flops, nbytes = paged_attention_gqa.paged_gqa_step(
        {"window": 0, "full": 9500}, {"window": 0, "full": 4625000},
        {"window": 0, "full": 6}, query_tokens=512, heads=32, kv_heads=4,
        head_dim=128)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("kernels.paged_gqa_window_roofline", ctx) == \
        pytest.approx(100 * least / 0.024)
    flops, nbytes = moe_experts.routed_experts_step(24576, 760, 2048, 768)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("kernels.moe_experts_roofline", ctx) == \
        pytest.approx(100 * least / 0.010)
    assert read("moe.local_pairs_per_step", ctx) == 24576
    assert read("scheduler.tokens_per_step", ctx) == pytest.approx(512)


def test_manifest_entries():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL]["config"] == "keye_vl2_30b_a3b_pp8_serve"
    assert cells[CELL]["traffic"] == "longctx_closed_16"
    assert cells[CELL]["chips"] == 1
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", name + ".py"))
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["ttft_p90_ms"]["workloads"]
    # the shares of a roofline the cell reports
    for name in ("kernels.moe_experts_roofline",
                 "kernels.paged_gqa_window_roofline"):
        assert CELL in per_layer[name]["workloads"]


def test_configuration_holds_the_published_widths():
    src = CONFIG["source_config"]
    for key, value in src.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert CONFIG["num_hidden_layers"] == 6
    assert CONFIG["max_position_embeddings"] == 33792 \
        == CONFIG["engine"]["max_seq_len"]
    assert CONFIG["sa_config"] == src["sa_config"]
    for key in ("indexer_rope", "indexer_key_norm", "indexer_scale",
                "selection", "tower", "mrope_section", "weights"):
        assert key in CONFIG["assumed"]
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", "longctx_closed_16.json")))
    assert traffic["clients"] == CONFIG["engine"]["max_slots"] == 16
    assert traffic["sentinel"] == {"prompt_len": 8192, "output_len": 32}


def _driver(monkeypatch, got):
    """The cell's driver with nothing built: `compare` hands back `got`."""
    mod = load_module("drivers", "serve_frontend_keye")
    d = object.__new__(mod.Driver)
    d.env = types.SimpleNamespace(config=CONFIG)
    d.logged = []
    d.log = d.logged.append
    d.direct, d.rows = [5, 6, 7], np.zeros((3, 8), np.float32)
    d.selections = np.zeros((3, 6, 16), bool)
    d.compare = lambda prompt, answer, rows: got
    monkeypatch.setattr(mod._block.Driver, "check", lambda self: {
        "sentinel": None, "reference logits": "inherited words"})
    return d


def readings(err=0.008, fwd=(0.05, 0.04, 0.09), margin=0.0,
             cache=(0.005, 0.07, 0.12, 0.05, 0.04, 0.03)):
    """What `compare` gives for a sound engine (the chip's readings)."""
    n = len(fwd)
    return {"err": np.full(n, err), "err_fwd": np.asarray(fwd),
            "err_sel": np.full(n, err), "margin": np.full(n, margin),
            "swaps": [()] * n, "passes": 3, "sel_faults": [],
            "sel_members": 20, "sel_gap": 0.05, "cache_err": list(cache),
            "cache_moved": [0.0] * 6}


def test_sound_readings_are_correct(monkeypatch):
    d = _driver(monkeypatch, readings())
    assert d._against_reference([1, 2], [5, 6, 7]) == (1.0, 0.0)
    assert d.ref_faults == {}
    assert d.check() == {"sentinel": None}


def test_the_frontend_s_sentinel_is_tied_to_the_compared_rows(monkeypatch):
    # sound readings off the engine stepped alone, other tokens through
    # the frontend (the timed path): not correct
    d = _driver(monkeypatch, readings())
    d._against_reference([1, 2], [5, 6, 9])
    assert list(d.ref_faults) == ["sentinel through the frontend"]
    checks = d.check()
    assert "[5, 6, 9]" in checks["reference sentinel through the frontend"]
    assert "reference logits" not in checks


@pytest.mark.parametrize("more, limit", [
    # float8's readings on the chip: no row over the loose limit, every
    # row moved
    (dict(fwd=(0.166, 0.13, 0.12)), "logit_err_forward_mean_sigmas"),
    (dict(fwd=(0.23, 0.01, 0.01)), "logit_err_forward_sigmas"),
    (dict(err=0.05), "logit_err_sigmas"),
    (dict(margin=0.08), "margin_sigmas"),
    (dict(cache=(0.03, 0.07, 0.12, 0.05, 0.04, 0.03)), "cache_err_layer0"),
    (dict(cache=(0.005, 0.07, 0.4, 0.05, 0.04, 0.03)), "cache_err_median"),
])
def test_each_limit_is_a_fault_of_its_own(monkeypatch, more, limit):
    d = _driver(monkeypatch, readings(**more))
    d._against_reference([1, 2], [5, 6, 7])
    assert list(d.ref_faults) == [limit]
    assert list(d.check()) == ["sentinel", "reference " + limit]


def _tipped_rows(swaps):
    """A pass of a made-up reference over three rows, two layers, top-2
    of which one rank either side is reported (R = 1): row 0 is the
    computation's once it swaps in layer 0; row 1 always is; row 2
    attends row 0's keys, and its layer-1 router sits at a near-tie that
    row 0's answer tips: beside the unswapped row 0 it is the
    computation's as it stands, beside the swapped one only once it
    swaps too. -> (z [3, 1]: the row's error itself, the routers'
    log-probabilities [2, 3, 2]: every boundary a near-tie)."""
    first = swaps[0] == ((0, 1, 2),)
    right = [first, True,
             swaps[2] == (((1, 1, 2),) if first else ())]
    z = np.asarray([[0.001 if ok else 0.03] for ok in right])
    return z, np.tile(np.asarray([-2.0, -2.01]), (2, 3, 1))


def test_a_row_tipped_by_an_earlier_row_s_answer_is_searched_again():
    # seed 730233921 on the chip: the search left row 15 at the first
    # pass, row 5 took two swaps later, and the last pass held row 15 on
    # the wrong side of its own near-tie: 131 members of its selection
    # differed from a mis-routed reference's
    mod = load_module("drivers", "serve_frontend_keye")
    rc = dict(CONFIG["reference"], max_passes=8)
    asked = []

    def run(swaps):
        asked.append(list(swaps))
        return _tipped_rows(swaps)
    swaps, err, err_sel, passes, last = mod.settle_routers(
        run, lambda z, p: float(z[p, 0]), (3, 2, 2, 1), rc)
    assert swaps == [((0, 1, 2),), (), ((1, 1, 2),)]
    # the errors are the last pass's, in which every row routes as kept
    assert asked[-1] == swaps and passes == len(asked)
    assert err.tolist() == [0.001, 0.001, 0.001]
    assert np.asarray(last[0]).tolist() == [[0.001]] * 3
    # before any search: row 2 read sound beside the unswapped row 0
    assert err_sel.tolist() == [0.03, 0.001, 0.001]
    # a row at rest is passed over as kept, not reset: row 1 never swaps
    assert all(sw[1] == () for sw in asked)


def test_a_search_that_nothing_tips_ends_after_one_round():
    mod = load_module("drivers", "serve_frontend_keye")
    asked = []

    def run(swaps):
        asked.append(list(swaps))
        z = np.asarray([[0.001], [0.001 if swaps[1] else 0.03]])
        return z, np.tile(np.asarray([-2.0, -2.01]), (2, 2, 1))
    swaps, err, _, passes, _ = mod.settle_routers(
        run, lambda z, p: float(z[p, 0]), (2, 2, 2, 1),
        CONFIG["reference"])
    # first pass, row 1's closest near-tie, the last pass
    assert swaps == [(), ((0, 1, 2),)] and passes == 3
    assert err.tolist() == [0.001, 0.001]


def test_a_row_no_swap_brings_home_is_not_searched_for_ever():
    # row 1 is over the search's limit whatever it swaps: its error is
    # reported as the last pass reads it, after row 0 has settled
    mod = load_module("drivers", "serve_frontend_keye")
    rc = dict(CONFIG["reference"], max_passes=8)
    asked = []

    def run(swaps):
        asked.append(list(swaps))
        z = np.asarray([[0.001 if swaps[0] else 0.03], [0.02]])
        return z, np.tile(np.asarray([-2.0, -2.01]), (2, 2, 1))
    swaps, err, _, passes, _ = mod.settle_routers(
        run, lambda z, p: float(z[p, 0]), (2, 2, 2, 1), rc)
    assert swaps[0] == ((0, 1, 2),) and err.tolist() == [0.001, 0.02]
    assert passes == len(asked) <= 2 * 8
