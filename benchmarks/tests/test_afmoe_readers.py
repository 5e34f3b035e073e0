"""The readers and harness helpers of PR 27 (the routed experts, the
grouped-query paged kernel under a window, the window allocator) on
hand-made flight records and a hand-made trace with known answers, and
on the records of a program that has none of their fields (the parent
commit): nothing, and no exception."""
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
                       "trinity_large_ep8_serve.json")) as _f:
    CONFIG = json.load(_f)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("kernels.moe_experts_ms_per_step", "kernels.moe_experts_roofline",
       "kernels.paged_gqa_window_roofline", "moe.local_pairs_per_step",
       "kv_manager.window_held_pct")


class Trace:
    """Device seconds by kernel name and program executions, as
    `harness.trace_reduce` reports them."""

    def __init__(self, seconds, steps):
        self.seconds, self.steps = seconds, steps

    def seconds_of(self, name):
        return self.seconds.get(name, 0.0)

    def calls_of(self, name, kind=None):
        return self.steps if kind == "modules" else 4 * self.steps


def context(flight, seconds=None, steps=100):
    logged = []
    return types.SimpleNamespace(
        trace=Trace(seconds or {}, steps), spans=[], flight=flight,
        steps=None, counters={}, config=CONFIG, traffic={}, peaks=PEAKS,
        log=logged.append), logged


def record(**more):
    return dict({"ts": 100.0, "dur": 0.03, "prefill_tokens": 480,
                 "decode_tokens": 32, "moe_pairs_total": 8192,
                 "moe_pairs_local": 1024, "moe_experts_hit": 128,
                 "moe_max_expert_pairs": 16,
                 "kv_tokens_read_window": 60000,
                 "kv_tokens_read_full": 100000,
                 "attn_pairs_window": 1500000, "attn_pairs_full": 2000000,
                 "kv_blocks_in_use_window": 4000,
                 "kv_blocks_in_use_full": 7000,
                 "kv_blocks_released_behind_window": 3,
                 "kv_tokens_held_window": 60000,
                 "kv_tokens_context": 100000}, **more)


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_expert_arithmetic():
    flops, nbytes = moe_experts.routed_experts_step(1024, 128, 3072, 3072)
    assert flops == 1024 * 6 * 3072 * 3072
    assert nbytes == 128 * 3 * 3072 * 3072 * 2 + 1024 * 2 * 6144 * 2
    # an expert no pair reached reads nothing
    assert moe_experts.routed_experts_step(0, 0, 3072, 3072) == (0, 0)


def test_attention_arithmetic():
    flops, nbytes = paged_attention_gqa.paged_gqa_step(
        {"window": 100, "full": 300}, {"window": 1000, "full": 5000},
        {"window": 4, "full": 1}, query_tokens=10, heads=48, kv_heads=8,
        head_dim=128)
    assert flops == (4 * 1000 + 5000) * 4 * 48 * 128
    kv = (4 * 100 + 300) * 2 * 8 * 128 * 2
    qo = 5 * 10 * 2 * 48 * 128 * 2
    assert nbytes == kv + qo


def test_known_answers():
    flight = [record(), record(moe_pairs_local=512, moe_experts_hit=64,
                               moe_max_expert_pairs=24)]
    ctx, logged = context(flight, {"moe_experts": 1.2, "paged_ragged": 2.0})
    assert read(NEW[0], ctx) == pytest.approx(12.0)
    flops, nbytes = moe_experts.routed_experts_step(768, 96, 3072, 3072)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read(NEW[1], ctx) == pytest.approx(100 * least / 0.012)
    assert "bandwidth bound" in logged[-1]
    share = read(NEW[2], ctx)
    flops, nbytes = paged_attention_gqa.paged_gqa_step(
        {"window": 60000, "full": 100000},
        {"window": 1500000, "full": 2000000}, {"window": 4, "full": 1},
        512, heads=48, kv_heads=8, head_dim=128)
    assert share == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 0.02)
    assert 0 < share < 100
    assert read(NEW[3], ctx) == pytest.approx(768.0)
    assert "9.38%" in logged[-1]              # 768 of 8192 pairs
    assert read(NEW[4], ctx) == pytest.approx(60.0)
    assert "6 blocks released" in logged[-1]


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("flight", [
    [],
    # the parent commit's records
    [{"ts": 100.0, "prefill_tokens": 100, "decode_tokens": 28,
      "kv_tokens_read": 9000, "attn_pairs": 30000,
      "kv_blocks_in_use": 600, "kv_blocks_total": 897}],
], ids=["no_records", "parent_records"])
def test_nothing_to_read_is_none(name, flight):
    ctx, logged = context(flight, {"paged_ragged": 0.5})
    assert read(name, ctx) is None
    assert not logged


def test_manifest_entries():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    by_name = {e["name"]: e for e in m["per_layer"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for name in NEW:
        e = by_name[name]
        assert e["workloads"] == ["serve_trinity_ep8_mixed_len"]
        # the cell is judged on tokens per second alone (its tails
        # spread over half their bounds): everything it reports moves it
        assert e["moves"] == "serve_tokens_per_s"
        assert "serve_trinity_ep8_mixed_len" in e2e[e["moves"]]["workloads"]
    for tail in ("ttft_p90_ms", "itl_p95_ms"):
        assert "serve_trinity_ep8_mixed_len" not in e2e[tail]["workloads"]
    # the GPT kernel readers count heads = KV heads: not this cell's
    for name in ("kernels.paged_ragged_roofline",
                 "kernels.paged_ragged_ms_per_step",
                 "kernels.paged_ragged_walk_share"):
        assert "serve_trinity_ep8_mixed_len" not in by_name[name]["workloads"]
    assert len(m["workloads"]) == 4
    assert all(w["chips"] == 1 for w in m["workloads"])


def test_the_slice_s_steps_are_the_ones_counted():
    """Where the driver hands the profiled slice's bounds over, the
    rooflines take the counts of the steps inside them."""
    from harness import flight_slice
    light = record(ts=100.0, moe_pairs_local=100, moe_experts_hit=20)
    heavy = record(ts=200.0)
    ctx, logged = context([light, heavy], {"moe_experts": 1.2})
    assert flight_slice.records(ctx, "moe_pairs_local")[1] == "window"
    whole = read(NEW[1], ctx)
    ctx.slice = (199.0, 203.0)
    recs, of = flight_slice.records(ctx, "moe_pairs_local")
    assert recs == [heavy] and of == "slice"
    flops, nbytes = moe_experts.routed_experts_step(1024, 128, 3072, 3072)
    share = read(NEW[1], ctx)
    assert share == pytest.approx(100 * nbytes / 819e9 / 0.012)
    assert share > whole
    assert "of the slice routes" in logged[-1]
    # bounds that hold no record (another clock): the window's records
    ctx.slice = (5.0, 8.0)
    assert flight_slice.records(ctx, "moe_pairs_local")[1] == "window"
    ctx.slice = None
    assert flight_slice.records(ctx, "moe_pairs_local")[1] == "window"
