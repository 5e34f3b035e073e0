"""The readers and the harness helper of PR 31 (the gated delta rule's
kernel time, its roofline share, the chunk rows' fill) on hand-made
flight records and a hand-made trace with known answers, and on the
records of a program that has none of their fields (the parent commit):
nothing, and no exception. The shared paged-attention reader on a cell
with no window layer."""
import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import gated_delta  # noqa: E402
from harness.files import load_module  # noqa: E402

with open(os.path.join(BENCH, "configs",
                       "olmo_hybrid_7b_serve.json")) as _f:
    CONFIG = json.load(_f)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS, ROOF, FILL = ("kernels.gated_delta_ms_per_step",
                  "kernels.gated_delta_roofline",
                  "linear_attn.chunk_fill_pct")


class Trace:
    """Device seconds by kernel name and program executions, as
    `harness.trace_reduce` reports them."""

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
    # 32 decode runs and one chunk of 480: 8 + 32 chunks of 64 rows
    return dict({"ts": 100.0, "dur": 0.03, "prefill_tokens": 480,
                 "decode_tokens": 32, "lin_tokens": 512, "lin_runs": 33,
                 "lin_chunks": 40, "lin_chunk_size": 64,
                 "state_slots_in_use": 32, "kv_tokens_read_window": 0,
                 "attn_pairs_window": 0, "kv_tokens_read_full": 80000,
                 "attn_pairs_full": 2000000}, **more)


def read(name, ctx):
    return load_module("layer_metrics", name).read(ctx)


def test_the_count_is_of_the_work():
    flops, nbytes = gated_delta.gated_delta_step(
        512, 33, layers=6, heads=30, key_dim=96, value_dim=192)
    assert flops == 6 * 512 * 30 * 7 * 96 * 192
    state = 33 * 2 * 30 * 96 * 192 * 4
    rows = 512 * 30 * (2 * 96 + 2 * 192) * 2
    gates = 512 * 30 * 2 * 4
    assert nbytes == 6 * (state + rows + gates)
    # nothing fed: nothing read, nothing computed; no chunk size enters
    assert gated_delta.gated_delta_step(0, 0, 6, 30, 96, 192) == (0, 0)


def test_known_answers():
    flight = [record(), record(lin_tokens=256, lin_runs=17,
                               lin_chunks=20)]
    ctx, logged = context(flight, {"gated_delta": 0.9})
    assert read(MS, ctx) == pytest.approx(9.0)
    flops, nbytes = gated_delta.gated_delta_step(384, 25, 6, 30, 96, 192)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read(ROOF, ctx) == pytest.approx(100 * least / 0.009)
    assert "bandwidth bound" in logged[-1]
    # 768 tokens in 60 chunks of 64 rows
    assert read(FILL, ctx) == pytest.approx(100 * 768 / (60 * 64))


def test_an_impossible_share_is_not_clipped():
    ctx, _ = context([record()], {"gated_delta": 1e-6})
    assert read(ROOF, ctx) > 105.0


def test_the_slice_s_records_where_the_driver_hands_its_bounds():
    flight = [record(ts=50.0, lin_tokens=32, lin_runs=32),
              record(ts=100.0)]
    ctx, logged = context(flight, {"gated_delta": 0.9},
                          slice=(90.0, 110.0))
    flops, nbytes = gated_delta.gated_delta_step(512, 33, 6, 30, 96, 192)
    least = max(flops / 197e12, nbytes / 819e9)
    assert read(ROOF, ctx) == pytest.approx(100 * least / 0.009)
    assert "slice" in logged[-1]


@pytest.mark.parametrize("name", (MS, ROOF, FILL))
def test_nothing_to_read_is_none(name):
    # a program without the fields (the parent), with and without events
    old = [{"ts": 100.0, "dur": 0.03, "prefill_tokens": 96,
            "decode_tokens": 32, "kv_tokens_read": 9000}]
    for seconds in ({}, {"gated_delta": 0.9}):
        ctx, _ = context(old, seconds)
        if name == MS and seconds:
            continue        # the kernel's time needs no flight field
        assert read(name, ctx) is None
    # the fields with no kernel event (a CPU rehearsal)
    ctx, _ = context([record()], {})
    if name != FILL:
        assert read(name, ctx) is None
    ctx, _ = context([], {"gated_delta": 0.9}, steps=0)
    assert read(name, ctx) is None


def test_the_paged_reader_counts_no_window_layer():
    """`kernels.paged_gqa_window_roofline` on this cell: 0 window
    layers, 2 full, 30 = 30 heads: the full layers' kernel share."""
    from harness import paged_attention_gqa
    ctx, _ = context([record()], {"paged_ragged": 0.5})
    share = read("kernels.paged_gqa_window_roofline", ctx)
    flops, nbytes = paged_attention_gqa.paged_gqa_step(
        {"window": 0, "full": 80000}, {"window": 0, "full": 2000000},
        {"window": 0, "full": 2}, query_tokens=512, heads=30,
        kv_heads=30, head_dim=128)
    least = max(flops / 197e12, nbytes / 819e9)
    assert share == pytest.approx(100 * least / 0.005)
