"""The reader of PR 33 (`diffusion.tokens_per_slot_pass`) on hand-made
flight records with known answers, and on the records of a program that
has none of its fields (the parent commit): nothing, and no exception.
The shared paged-attention and expert readers on the SDAR cell's
configuration: 4 KV x 8 query heads, six full layers, 128 experts of
2048 x 768."""
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import moe_experts, paged_attention_gqa  # noqa: E402
from harness.files import load_module  # noqa: E402

with open(os.path.join(BENCH, "configs",
                       "sdar_30b_a3b_pp8_serve.json")) as _f:
    CONFIG = json.load(_f)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAME = "diffusion.tokens_per_slot_pass"


class Trace:
    def __init__(self, seconds, steps):
        self.seconds, self.steps = seconds, steps

    def seconds_of(self, name):
        return self.seconds.get(name, 0.0)

    def calls_of(self, name, kind=None):
        return self.steps if kind == "modules" else 6 * self.steps


def context(flight, seconds=None, steps=100, **more):
    logged = []
    return types.SimpleNamespace(
        trace=Trace(seconds or {}, steps), spans=[], flight=flight,
        steps=None, counters={}, config=CONFIG, traffic={}, peaks=PEAKS,
        log=logged.append, **more), logged


def record(**more):
    # 32 slots feed their blocks (6 of them commits) beside 384 rows of
    # prefill
    return dict({"ts": 100.0, "dur": 0.02, "prefill_tokens": 384,
                 "decode_tokens": 128, "diff_block_len": 4,
                 "diff_slot_passes": 32, "diff_rows_masked": 65,
                 "diff_tokens_decided": 26, "diff_commits": 6,
                 "diff_blocks_committed": 6, "kv_tokens_read_window": 0,
                 "attn_pairs_window": 0, "kv_tokens_read_full": 90000,
                 "attn_pairs_full": 1500000, "moe_pairs_local": 24000,
                 "moe_pairs_total": 24000, "moe_experts_hit": 760,
                 "moe_max_expert_pairs": 60}, **more)


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_known_answer():
    flight = [record(), record(diff_slot_passes=30, diff_commits=10,
                               diff_tokens_decided=20,
                               diff_rows_masked=50)]
    ctx, logged = context(flight)
    assert read(NAME, ctx) == pytest.approx(46 / 62)
    assert "8.0 commits" in logged[-1] and "of 4 rows" in logged[-1]


def test_one_position_a_pass_reads_four_fifths():
    # every block: four denoise passes of one position and a commit
    flight = [record(diff_slot_passes=5, diff_commits=1,
                     diff_tokens_decided=4, diff_rows_masked=10)]
    assert read(NAME, context(flight)[0]) == pytest.approx(0.8)


def test_nothing_to_read_is_none():
    old = [{"ts": 100.0, "dur": 0.03, "prefill_tokens": 96,
            "decode_tokens": 32, "kv_tokens_read": 9000}]
    assert read(NAME, context(old)[0]) is None
    assert read(NAME, context([])[0]) is None
    # a step that fed prefill alone: no slot pass, nothing to divide by
    assert read(NAME, context([record(diff_slot_passes=0)])[0]) is None


def test_the_shared_readers_take_this_configuration():
    ctx, _ = context([record()], {"paged_ragged": 0.3,
                                  "moe_experts": 0.9})
    flops, nbytes = paged_attention_gqa.paged_gqa_step(
        {"window": 0, "full": 90000}, {"window": 0, "full": 1500000},
        {"window": 0, "full": 6}, query_tokens=512, heads=32, kv_heads=4,
        head_dim=128)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("kernels.paged_gqa_window_roofline", ctx) == \
        pytest.approx(100 * least / 0.003)
    flops, nbytes = moe_experts.routed_experts_step(24000, 760, 2048, 768)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read("kernels.moe_experts_roofline", ctx) == \
        pytest.approx(100 * least / 0.009)
    assert read("moe.local_pairs_per_step", ctx) == 24000
    assert read("scheduler.tokens_per_step", ctx) == pytest.approx(512)
