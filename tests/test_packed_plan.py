"""The mixed step's plan as ONE upload (ISSUE 30): `PlanLayout` /
`PlanBuffers` round trip, the step's tokens against what the program
served BEFORE the plan was packed (recorded from commit 49f3e25 by this
file's own `record`, `packed_plan_parent_tokens.json`), the key that
now lives on the device, the upload counter of the flight record, the
one-compile contract across `example_step_args()`, and the table copy
taken at pack time.

    python tests/test_packed_plan.py OUT.json    # record (any checkout)
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "packed_plan_parent_tokens.json")
VOCAB = 193
STEPS = 40


# ---------------------------------------------------------- the served mix
def _gpt():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForGeneration
    paddle.seed(1234)
    m = GPTForGeneration(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128,
                         compute_dtype="float32")
    m.eval()
    return m


def _afmoe():
    from paddle_tpu.models import afmoe
    arch = afmoe.make_arch(
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        num_dense_layers=1, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, window=16, dense_width=128, vocab_rows=96,
        max_positions=256, compute_dtype="float32",
        moe=dict(num_experts=16, top_k=2, expert_width=32,
                 experts_held=4, expert_rank=0, route_scale=2.448))
    return afmoe.AfmoeForGeneration(arch, seed=3)


CASES = {
    "gpt": dict(),
    "int8_pools": dict(kv_dtype="int8"),
    "lora": dict(max_adapters=3, lora_rank=4),
    "draft3": dict(draft_k=3),
    "ticks4": dict(ticks_per_dispatch=4),
    "tp2": dict(tensor_parallel=2),
    "afmoe_block": dict(),
}


def _sampling(kind):
    from paddle_tpu.serving.batcher import SamplingConfig
    if kind == "greedy":
        return SamplingConfig()
    return SamplingConfig(strategy="sampling", temperature=1.1, top_p=0.9)


def build(case, kind="greedy", sampling=None):
    """The engine of a case: 4 slots (3 for the AFMoE block) on a tiny
    float32 model, seed 7."""
    from paddle_tpu.serving.engine import ServingEngine
    kw = dict(CASES[case], sampling=sampling or _sampling(kind), seed=7,
              cache_dtype="float32")
    if case == "afmoe_block":
        return ServingEngine(_afmoe(), max_slots=3, block_size=4,
                             num_blocks=80, max_seq_len=128,
                             token_budget=16, **kw)
    kw.update(max_slots=4, block_size=4, max_seq_len=64)
    if case == "tp2":
        from paddle_tpu.serving.distributed.tp_engine import \
            TPServingEngine
        return TPServingEngine(_gpt(), **kw)
    return ServingEngine(_gpt(), **kw)


def submit_mix(eng, case):
    """Six requests on the slots: prompts of 3-12 tokens (a repeated
    motif, so the n-gram drafter proposes), 10-14 new tokens each; with
    adapters every other request names one."""
    rng = np.random.RandomState(5)
    vocab = 96 if case == "afmoe_block" else VOCAB
    motif = rng.randint(1, vocab, 3).tolist()
    prompts = [(motif * 4)[:n] + rng.randint(1, vocab, 2).tolist()
               for n in (3, 7, 1, 10, 5, 8)]
    adapter = None
    if case == "lora":
        from paddle_tpu.serving.adapters import make_random_adapter
        eng.register_adapter("t1", make_random_adapter(
            eng.model.decoder, 4, seed=1, scale=0.3))
        adapter = "t1"
    return [eng.submit(p, 10 + i % 5,
                       **({"adapter_id": adapter} if i % 2 else {}))
            for i, p in enumerate(prompts)]


def serve(case, kind):
    """-> (engine, each request's tokens after STEPS engine steps).
    Seeded sampling in the SYNCHRONOUS order (`_depth` 0: dispatch, read
    back, emit; the compiled step is the same): a sample is drawn with
    its step's key, and six requests on four slots make the parent's
    steps only where a slot that frees is filled in the very next plan.
    An engine that dispatches ahead (ISSUE 36) learns the end of a
    request one step later and fills the slot one step later: the same
    distribution under other keys. Greedy tokens are the parent's in
    either order, and are served in the engine's own."""
    eng = build(case, kind)
    if kind != "greedy":
        eng._depth = 0
    reqs = submit_mix(eng, case)
    for _ in range(STEPS):
        if not eng.scheduler.has_work:
            break
        eng.step()
    eng.flush_observability()
    return eng, [list(map(int, r.output)) for r in reqs]


def record(path):
    """What THIS checkout serves, case by case: run on the parent
    commit, it wrote `packed_plan_parent_tokens.json`."""
    out = {f"{case}.{kind}": serve(case, kind)[1]
           for case in CASES for kind in ("greedy", "top_p")}
    with open(path, "w") as f:
        f.write("{\n" + ",\n".join(
            f" {json.dumps(k)}: {json.dumps(out[k])}" for k in sorted(out))
            + "\n}\n")


# ------------------------------------------------------- (a) the layout
def _tables(rng, kinds):
    return [(name, rng.randint(0, 500, (4, 9)).astype(np.int32))
            for name in ("block_tables", "window_tables")[:kinds]]


@pytest.mark.parametrize("kinds", [1, 2], ids=["one_table", "full_window"])
@pytest.mark.parametrize("adapters", [False, True])
def test_layout_round_trip(kinds, adapters):
    """Decodes, two prefill chunks (one completing its prompt) and
    padding: the flat buffer sliced by the layout IS `pack_step`'s four
    arrays and the tables, bit for bit."""
    from paddle_tpu.serving.batcher import (PlanBuffers, PlanLayout,
                                            pack_step)
    rng = np.random.RandomState(0)
    tables = _tables(rng, kinds)
    layout = PlanLayout(32, 4, [(n, t.shape) for n, t in tables],
                        adapters=adapters)
    names = ["token_ids", "slot_ids", "positions", "sample_index"] \
        + [n for n, _ in tables] + (["adapter_ids"] if adapters else [])
    assert list(layout.fields) == names
    assert layout.tables == tuple(n for n, _ in tables)
    assert layout.size == 3 * 32 + 4 + kinds * 36 + (32 if adapters else 0)
    plan = dict(decode=[(2, 7, 5), (0, 9, 3)],
                prefills=[(1, np.arange(10, 16), 4, False),
                          (3, np.arange(20, 25), 0, True)])
    want = pack_step(32, 4, **plan)
    buf = PlanBuffers(layout)
    buf.flat[:] = -7        # the last plan's leftovers
    got = pack_step(32, 4, buffers=buf, **plan)
    assert got.buffers is buf and want.buffers is None
    for name, table in tables:
        np.copyto(getattr(buf, name), table)
    ids = rng.randint(0, 3, 32).astype(np.int32)
    if adapters:
        np.copyto(buf.adapter_ids, ids)
    fields = layout.unpack(buf.flat.copy())     # what the step slices
    for name in names[:4]:
        assert fields[name].dtype == np.int32
        np.testing.assert_array_equal(fields[name], getattr(want, name))
        assert np.shares_memory(getattr(got, name), buf.flat)
    assert want.num_tokens == got.num_tokens == 13
    assert (fields["slot_ids"][13:] == -1).all()
    assert fields["sample_index"].tolist() == [1, -1, 0, 12]
    for name, table in tables:
        np.testing.assert_array_equal(fields[name], table)
    if adapters:
        np.testing.assert_array_equal(fields["adapter_ids"], ids)
    # every int of the buffer belongs to exactly one field
    cover = np.zeros(layout.size, np.int32)
    for at, shape in layout.fields.values():
        cover[at:at + int(np.prod(shape))] += 1
    assert (cover == 1).all()


def test_layout_replace_on_the_device():
    """What the device loop does a tick: the flat tokens swapped, the
    tables left."""
    import jax.numpy as jnp

    from paddle_tpu.serving.batcher import PlanLayout
    layout = PlanLayout(8, 2, [("block_tables", (2, 3))])
    flat = jnp.arange(layout.size, dtype=jnp.int32)
    out = layout.unpack(layout.replace(
        flat, token_ids=jnp.full((8,), 5, jnp.int32),
        sample_index=jnp.asarray([-1, 3], jnp.int32)))
    assert out["token_ids"].tolist() == [5] * 8
    assert out["sample_index"].tolist() == [-1, 3]
    assert out["slot_ids"].tolist() == list(range(8, 16))
    assert out["block_tables"].tolist() == [[26, 27, 28], [29, 30, 31]]


# ------------------------------------- (b) the tokens the parent served
@pytest.fixture(scope="module")
def parent_tokens():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("kind", ["greedy", "top_p"])
@pytest.mark.parametrize("case", list(CASES))
def test_tokens_are_the_parents(parent_tokens, case, kind):
    """The same mix, the same seed: token for token what commit 49f3e25
    served, greedy and seeded top-p, and the key the engine holds is the
    host's old chain (one split a tick), now on the device."""
    import jax
    eng, out = serve(case, kind)
    assert out == parent_tokens[f"{case}.{kind}"]
    assert sum(map(len, out)) >= 60     # the mix really was served
    assert eng.step_compile_count() == 1
    assert isinstance(eng._rng, jax.Array)
    ticks = eng.device_ticks_run if eng._multitick else eng.steps_run
    key = jax.random.PRNGKey(7)
    for _ in range(ticks):
        key = jax.random.split(key)[0]
    np.testing.assert_array_equal(np.asarray(eng._rng), np.asarray(key))


# ---------------------------------------------- (c) the upload counter
@pytest.fixture
def traced():
    from paddle_tpu.serving import tracing
    tracing.enable()
    yield
    tracing.disable()
    tracing.TRACER.reset()


@pytest.mark.parametrize("case,penalized,arrays", [
    ("gpt", False, 1), ("afmoe_block", False, 1), ("lora", False, 1),
    ("gpt", True, 2),
    # the device loop's tail: n, eos, remain, cap
    ("ticks4", False, 5),
])
def test_flight_record_counts_uploads(traced, case, penalized, arrays):
    """One host array a dispatch: the packed plan. Logit processors add
    their counts, the device loop its control tail."""
    from paddle_tpu.serving.batcher import SamplingConfig
    eng = build(case, sampling=SamplingConfig(repetition_penalty=1.3)
                if penalized else None)
    submit_mix(eng, case)
    eng.run()
    recs = list(eng.flight.records)
    assert len(recs) >= 10
    assert {r["h2d_arrays"] for r in recs} == {arrays}
    nbytes = 4 * eng.plan_layout.size
    if penalized:
        nbytes += 4 * 4 * VOCAB             # [S, Vb] float32 counts
    if case == "ticks4":
        nbytes += 4 + 3 * 4 * 4             # n; eos, remain, cap [S]
    assert {r["h2d_bytes"] for r in recs} == {nbytes}


def test_untraced_step_records_nothing():
    eng, out = serve("gpt", "greedy")
    assert not eng.flight.records and sum(map(len, out))


def test_uploads_reader():
    """`mixed_step.uploads_per_step` on hand-made records, and on the
    parent's (no `h2d_arrays`): nothing, and no exception."""
    import types
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness.files import load_module
    read = load_module("layer_metrics", "mixed_step.uploads_per_step").read

    def ctx(flight):
        logged = []
        return types.SimpleNamespace(flight=flight, log=logged.append), \
            logged
    c, logged = ctx([{"h2d_arrays": 1, "h2d_bytes": 17000},
                     {"h2d_arrays": 1, "h2d_bytes": 17000},
                     {"h2d_arrays": 4, "h2d_bytes": 20000}])
    assert read(c) == pytest.approx(2.0)
    assert "18000.0 bytes a step" in logged[-1]
    for flight in ([], [{"ts": 1.0, "dur": 0.02, "ph_pack": 0.003}]):
        c, logged = ctx(flight)
        assert read(c) is None and not logged
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "mixed_step.uploads_per_step")
    # later cells are appended to the list (PR 31: the Olmo-Hybrid cell)
    assert entry["workloads"][:3] == [
        "serve_gpt3_1p3b_closed", "serve_gpt3_1p3b_closed_b",
        "serve_trinity_ep8_mixed_len"]
    assert dict(entry, workloads=None) == {
        "name": "mixed_step.uploads_per_step", "unit": "arrays/step",
        "better": "lower", "source": "program_counter",
        "layer": "mixed_step", "moves": "serve_tokens_per_s",
        "workloads": None}


def test_dispatch_ahead_reader():
    """`mixed_step.dispatch_ahead_pct` on hand-made records, and on the
    parent's (no `ahead`): nothing, and no exception."""
    import types
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness.files import load_module
    read = load_module("layer_metrics", "mixed_step.dispatch_ahead_pct").read

    def ctx(flight):
        logged = []
        return types.SimpleNamespace(flight=flight, log=logged.append), \
            logged
    c, logged = ctx([{"ahead": 0, "ahead_wasted_rows": 0},
                     {"ahead": 1, "ahead_wasted_rows": 2},
                     {"ahead": 1, "ahead_wasted_rows": 0},
                     {"ahead": 1, "ahead_wasted_rows": 1}])
    assert read(c) == pytest.approx(75.0)
    assert "3 of 4 steps" in logged[-1] and "0.750 rows a step" in logged[-1]
    c, logged = ctx([{"ahead": 0, "ahead_wasted_rows": 0}] * 3)
    assert read(c) == 0.0
    for flight in ([], [{"ts": 1.0, "dur": 0.02, "h2d_arrays": 1}]):
        c, logged = ctx(flight)
        assert read(c) is None and not logged
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "mixed_step.dispatch_ahead_pct")
    assert entry == {
        "name": "mixed_step.dispatch_ahead_pct", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "mixed_step", "moves": "serve_tokens_per_s",
        "workloads": [
            "serve_gpt3_1p3b_closed", "serve_gpt3_1p3b_closed_b",
            "serve_trinity_ep8_mixed_len", "serve_olmo_hybrid_mixed_len",
            "serve_sdar_30b_a3b_mixed_len",
            "serve_keye_vl2_30b_a3b_longctx", "serve_axk1_ep16_longctx"]}


# ------------------------------- (d) one compile across example args
@pytest.mark.parametrize("case", ["gpt", "ticks4", "tp2", "afmoe_block"])
def test_example_args_then_live_steps_compile_once(case):
    """`example_step_args()` packs into the engine's own buffers and
    passes its key as it stands: asked for before, between and after
    live steps it neither advances the key nor costs a compile."""
    eng = build(case, "top_p")
    key0 = np.asarray(eng._rng).copy()
    example = eng.example_step_args()
    np.testing.assert_array_equal(np.asarray(eng._rng), key0)
    reqs = submit_mix(eng, case)
    for _ in range(5):
        eng.step()
    eng.example_step_args()
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert eng.step_compile_count() == 1
    again = eng.example_step_args()
    assert [getattr(a, "shape", None) for a in again[1:]] == \
        [getattr(a, "shape", None) for a in example[1:]]
    # and what it served is what an engine never asked serves
    assert [list(r.output) for r in reqs] == \
        [list(r.output) for r in _run_all(build(case, "top_p"), case)]


def _run_all(eng, case):
    reqs = submit_mix(eng, case)
    eng.run()
    return reqs


# ------------------------------------- (e) the table copy at pack time
@pytest.mark.parametrize("case", ["gpt", "afmoe_block"])
def test_tables_are_copied_at_pack_time(case):
    """The KV manager's live tables scribbled over after `_step_args`
    has returned, and left so until the step's results are back: the
    step read its own copy, so its tokens are an undisturbed engine's."""
    import jax
    want = [list(r.output) for r in _run_all(build(case), case)]
    eng = build(case)
    step_fn, scribbled = eng._step_fn, []

    def scribble(*args):
        live = eng.kv.tables()
        saved = [t.copy() for t in live]
        for t in live:
            t[:] = 0
        try:
            return jax.block_until_ready(step_fn(*args))
        finally:
            scribbled.append(1)
            for t, s in zip(live, saved):
                t[:] = s
    eng._step_fn = scribble
    assert [list(r.output) for r in _run_all(eng, case)] == want
    assert len(scribbled) >= 10


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
        + " --xla_force_host_platform_device_count=8"
    sys.path.insert(0, ROOT)
    record(sys.argv[1])
