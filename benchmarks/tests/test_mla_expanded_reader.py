"""`mla.expanded_rows_pct` on hand-made flight records with a known
answer, on records of a program without the field (the parent of the
expanded walk) and on a window with no chunk row: nothing, and no
exception, as where no row took the walk; its manifest entry."""
import json
import os
import sys
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness.files import load_module  # noqa: E402

NAME = "mla.expanded_rows_pct"
READER = load_module("layer_metrics", NAME)


def context(flight):
    logged = []
    return types.SimpleNamespace(flight=flight, log=logged.append), logged


def record(chunk, expanded=None):
    r = {"ts": 1.0, "mla_rows_decode": 56, "mla_rows_chunk": chunk}
    if expanded is not None:
        r["mla_rows_expanded"] = expanded
    return r


def test_the_share_of_chunk_rows_that_took_the_walk():
    ctx, logged = context([record(3528, 3528), record(3528, 2352),
                           {"ts": 2.0}])
    assert READER.read(ctx) == 100.0 * (3528 + 2352) / (2 * 3528)
    assert logged and "chunk rows" in logged[0]


def test_nothing_without_the_field_or_a_row_that_took_the_walk():
    for flight in ([record(3528)], [], [record(0, 0)], [record(3528, 0)]):
        ctx, _ = context(flight)
        assert READER.read(ctx) is None


def test_manifest_entry():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    assert entries[0] == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "mla",
                     "moves": "serve_tokens_per_s",
                     "workloads": ["serve_axk1_ep16_longctx"]}
