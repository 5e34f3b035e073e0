"""The four readers of PR 25 (host phases, the gap between steps, pool
occupancy, the `paged_ragged` roofline share) on hand-made flight
records with known answers, and on records of a program that has none
of the new fields (the parent commit): nothing, and no exception."""
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import paged_attention, trace_reduce  # noqa: E402
from harness.files import load_json, load_module  # noqa: E402

NEW = ("mixed_step.host_ms_per_step", "frontend.between_steps_ms_p50",
       "kv_manager.pool_in_use_pct", "kernels.paged_ragged_roofline")
CONFIG = load_json(BENCH, "configs", "gpt3_1p3b_serve.json")
PEAKS = load_json(BENCH, "harness", "peaks.json")["TPU v5 lite"]


def record(i, **more):
    """Step i: 2 ms of host phases, a wait of 60 + i ms."""
    r = {"ts": 100.0 + 0.07 * i, "prefill_tokens": 100,
         "decode_tokens": 28, "ph_plan": 0.0004, "ph_pack": 0.0009,
         "ph_dispatch": 0.0003, "ph_wait": 0.060 + 0.001 * i,
         "ph_emit": 0.0003, "ph_note": 0.0001,
         "ph_hop_out": 0.0005, "ph_publish": 0.0004,
         "ph_admit": 0.0002, "ph_hop_in": 0.0006,
         "kv_tokens_read": 9000 + 100 * i, "attn_pairs": 30000,
         "kv_blocks_in_use": 500 + 100 * i, "kv_blocks_total": 1000,
         "preemptions": 3 + (i > 1)}
    r["dur"] = sum(v for k, v in r.items() if k.startswith("ph_")
                   and k[3:] in ("plan", "pack", "dispatch", "wait",
                                 "emit", "note"))
    if i:
        r["gap_before"] = 0.002 + 0.001 * i
    return dict(r, **more)


def old_record(i):
    """What the parent commit notes: none of this PR's fields."""
    return {"ts": 100.0 + 0.07 * i, "dur": 0.069, "prefill_tokens": 100,
            "decode_tokens": 28, "active_slots": 32, "queue_depth": 0}


def reduced(kernel_s=0.0, steps=0):
    dev = {"/device:TPU:0": {"ops": [], "modules": []}}
    t = 0
    for _ in range(steps):
        dev["/device:TPU:0"]["modules"].append(
            ("jit_serving_mixed_step(123)", t, 1000))
        dev["/device:TPU:0"]["ops"].append(
            ("paged_ragged.3", t, int(kernel_s / steps * 1e9)))
        t += int(kernel_s / steps * 1e9) + 1000
    if not steps:
        dev["/device:TPU:0"]["ops"].append(("%fusion.1 = f32[]", 0, 10))
    return trace_reduce.reduce_events(dev)


def context(flight, trace):
    logged = []
    return types.SimpleNamespace(
        trace=trace, spans=[], flight=flight, steps=None, counters={},
        config=CONFIG, traffic={}, peaks=PEAKS,
        log=logged.append), logged


def test_known_answers():
    flight = [record(i) for i in range(3)]
    ctx, logged = context(flight, reduced(kernel_s=0.150, steps=3))
    read = {n: load_module("layer_metrics", n).read(ctx) for n in NEW}
    assert read["mixed_step.host_ms_per_step"] == pytest.approx(2.0)
    assert read["frontend.between_steps_ms_p50"] == pytest.approx(3.5)
    assert read["kv_manager.pool_in_use_pct"] == pytest.approx(60.0)
    # mean step: 9100 KV tokens x 196,608 B + 128 queries' Q and O
    # over 24 layers = 1.7923 GB -> 2.188 ms at 819 GB/s, against
    # 50 ms of kernel time a step; FLOPs 30000 x 4 x 2048 x 24 = 5.9
    # GFLOP -> 0.03 ms: the bandwidth bound applies
    nbytes = 9100 * 196608 + 128 * 2 * 2048 * 2 * 24
    assert read["kernels.paged_ragged_roofline"] == pytest.approx(
        100.0 * nbytes / 819e9 / 0.050)
    text = "\n".join(logged)
    assert "bandwidth bound" in text
    assert "plan 0.400" in text and "wait 61.000" in text
    assert "long step 64.00 ms" in text          # the longest first
    assert "9200 KV tokens read, 30000 pairs" in text
    assert "hop_out 0.500" in text and "hop_in 0.600" in text
    assert "peak 700 (70.0%)" in text and "1 preemptions" in text


def test_work_is_counted_here_not_in_the_program():
    flops, nbytes = paged_attention.paged_attention_step(
        kv_tokens_read=10, attn_pairs=7, query_tokens=3, heads=2,
        head_dim=4, layers=5, kv_dtype_bytes=1, act_dtype_bytes=2)
    assert flops == 7 * 4 * 8 * 5
    assert nbytes == (10 * 2 * 8 * 1 + 3 * 2 * 8 * 2) * 5
    # compute bound once a step attends enough pairs per byte
    ctx, logged = context(
        [record(0, attn_pairs=10 ** 9)], reduced(kernel_s=1.0, steps=1))
    share = load_module(
        "layer_metrics", "kernels.paged_ragged_roofline").read(ctx)
    assert share == pytest.approx(
        100.0 * 1e9 * 4 * 2048 * 24 / 197e12 / 1.0)
    assert "compute bound" in logged[-1]


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(name):
    reader = load_module("layer_metrics", name)
    trace = reduced(kernel_s=0.150, steps=3)
    for flight in ([], [old_record(i) for i in range(3)]):
        ctx, logged = context(flight, trace)
        assert reader.read(ctx) is None and not logged
    if name == "kernels.paged_ragged_roofline":
        # the new fields, but no kernel event: a CPU rehearsal
        ctx, _ = context([record(i) for i in range(3)], reduced())
        assert reader.read(ctx) is None
