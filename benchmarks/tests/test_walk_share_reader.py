"""The reader of PR 26 (`kernels.paged_ragged_walk_share`) on hand-made
flight records with a known answer, and on records of a program that
has none of its fields (the parent commit): nothing, and no exception."""
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness.files import load_module  # noqa: E402

NAME = "kernels.paged_ragged_walk_share"


def context(flight):
    logged = []
    return types.SimpleNamespace(
        trace=None, spans=[], flight=flight, steps=None, counters={},
        config={}, traffic={}, peaks={}, log=logged.append), logged


def record(needed, walked, **more):
    return dict({"ts": 100.0, "dur": 0.02, "prefill_tokens": 100,
                 "decode_tokens": 28, "kv_tokens_read": 9000,
                 "attn_pairs": 30000, "kv_blocks_needed": needed,
                 "kv_blocks_walked": walked}, **more)


@pytest.mark.parametrize("flight,share", [
    ([record(600, 600), record(580, 580)], 100.0),
    # one walk per query token: four times the blocks
    ([record(600, 2400), record(400, 1600)], 25.0),
    # a sum over the window, not a mean of the steps' shares
    ([record(100, 100), record(300, 700)], 50.0),
])
def test_known_answers(flight, share):
    ctx, logged = context(flight)
    assert load_module("layer_metrics", NAME).read(ctx) == \
        pytest.approx(share)
    assert f"over {len(flight)} steps" in logged[-1]


@pytest.mark.parametrize("flight", [
    [],
    # the parent commit's records: the work in tokens, not in blocks
    [{"ts": 100.0, "dur": 0.069, "prefill_tokens": 100,
      "decode_tokens": 28, "kv_tokens_read": 9000, "attn_pairs": 30000}],
], ids=["no_records", "parent_records"])
def test_nothing_to_read_is_none(flight):
    ctx, logged = context(flight)
    assert load_module("layer_metrics", NAME).read(ctx) is None
    assert not logged


def test_manifest_entry():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "itl_p95_ms",
        "workloads": ["serve_gpt3_1p3b_closed",
                      "serve_gpt3_1p3b_closed_b"]}]
