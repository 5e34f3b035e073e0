"""Olmo-Hybrid through the normal serving path, at a small size on the
CPU: the model's eager forward and the engine's mixed step (per-slot
recurrent state and convolution tail beside paged K/V in one cache
manager, the ragged chunked delta-rule kernel, full-attention layers
through the paged kernel) against the plain reference of
`benchmarks/configs/olmo_hybrid_7b_serve_reference.py`."""
import contextlib
import dataclasses
import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.files import load_module  # noqa: E402

from paddle_tpu.models import olmo_hybrid as oh  # noqa: E402
from paddle_tpu.ops.pallas import gated_delta as gd  # noqa: E402
from paddle_tpu.ops.pallas import interpret_mode  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import paged_runs  # noqa: E402

REF = load_module("configs", "olmo_hybrid_7b_serve_reference")
CONTROLS = load_module("configs", "olmo_hybrid_7b_serve_controls")
DRIVERS = load_module("drivers", "serve_frontend_olmo_hybrid")
VOCAB = 96
#: sigma (rms of a row of logits against the reference's); the float32
#: engine reads 2e-5 to 4e-5, every planted fault over 0.01
LIMIT = 1e-3


def small(dtype="float32", chunk=8, **over):
    """Two periods of [linear, linear, linear, full]: 4 heads of 16 in
    the full layers, 4 heads of key 8 / value 16 in the linear ones,
    delta-rule chunks of 8 so that a prefill chunk of 16 walks two."""
    return oh.OlmoHybridArch(**dict(dict(
        hidden_size=64, num_heads=4, head_dim=16, linear_heads=4,
        linear_key_dim=8, linear_value_dim=16, mlp_width=128,
        vocab_rows=VOCAB, layer_kinds=(oh.LINEAR,) * 3 + (oh.FULL,)
        + (oh.LINEAR,) * 3 + (oh.FULL,), max_positions=256,
        compute_dtype=dtype, delta_chunk=chunk), **over))


@functools.lru_cache(maxsize=None)
def model(dtype="float32", chunk=8):
    return oh.OlmoHybridForGeneration(small(dtype, chunk), seed=3)


def reference(m, seq, last=None, ref=REF):
    import jax.numpy as jnp
    return np.asarray(ref.logits(
        m.weights, jnp.asarray(seq, jnp.int32),
        DRIVERS.reference_cfg(m.arch), last=last))


def row_errors(rows, z):
    """The driver's statistic: rms of (row - reference row) in standard
    deviations of the reference row, a position."""
    return np.sqrt(((rows - z) ** 2).mean(-1)) / z.std(-1)


def engine(m, *, dtype="float32", budget=16, slots=3, blocks=80, **kw):
    from paddle_tpu.serving.engine import ServingEngine
    return ServingEngine(m, max_slots=slots, block_size=4,
                         num_blocks=blocks, max_seq_len=128,
                         token_budget=budget, cache_dtype=dtype, **kw)


def serve(m, prompts, new_tokens, *, interpret=False, eng=None, **kw):
    """-> (engine, each request's tokens, each request's rows of logits
    as `engine.sample_logits` held them when a token was taken)."""
    with (interpret_mode() if interpret else contextlib.nullcontext()):
        eng = eng or engine(m, **kw)
        reqs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        rows, slots = [[] for _ in reqs], [-1] * len(reqs)
        while eng.scheduler.has_work:
            had = [len(r.output) for r in reqs]
            eng.step()
            for i, r in enumerate(reqs):
                slots[i] = r.slot if r.slot >= 0 else slots[i]
                if len(r.output) > had[i]:
                    rows[i].append(np.asarray(
                        eng.sample_logits[slots[i]]))
    return eng, [list(r.output) for r in reqs], \
        [np.stack(r) for r in rows]


PROMPTS = [np.random.default_rng(1).integers(0, VOCAB, n).tolist()
           for n in (40, 7, 23)]


# ------------------------------------------------- model and reference


def test_eager_forward_is_the_reference():
    m = model()
    ids = np.random.default_rng(0).integers(0, VOCAB, 50)
    want = reference(m, ids)
    got = np.asarray(m.forward(ids))
    assert row_errors(got, want).max() < 2e-4
    assert want.std() > 0.05


def test_arch_from_the_source_s_keys():
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmo_hybrid_7b_serve.json")) as f:
        cfg = json.load(f)
    arch = oh.arch_from_config(cfg["source_config"])
    assert len(arch.layer_kinds) == 32
    assert arch.layer_kinds[:4] == (oh.LINEAR,) * 3 + (oh.FULL,)
    assert arch.layer_kinds.count(oh.FULL) == 8
    assert (arch.hidden_size, arch.num_heads, arch.head_dim) == \
        (3840, 30, 128)
    assert (arch.linear_heads, arch.linear_key_dim,
            arch.linear_value_dim, arch.conv_width) == (30, 96, 192, 4)
    assert arch.conv_channels == 11520 and arch.allow_neg_eigval
    assert arch.vocab_rows == 100352 and arch.mlp_width == 11008
    cut = oh.arch_from_config(cfg)
    assert cut.layer_kinds == arch.layer_kinds[:8]
    # the weights of one period and of the vocabulary, as ISSUE 31
    # counts them
    shapes = oh.weight_shapes(arch)
    count = lambda g: sum(int(np.prod(s)) for s, _ in g.values())  # noqa
    assert round(count(shapes[oh.LINEAR]) / 1e6, 1) == 215.6
    assert round(count(shapes[oh.FULL]) / 1e6, 1) == 185.8
    assert round(count(shapes["top"]) / 1e6, 1) == 770.7


@pytest.mark.parametrize("interpret", (False, True),
                         ids=("fallback", "kernels"))
def test_engine_is_the_reference(interpret):
    """Prefill in chunks of 16 (two delta-rule chunks each, the state
    and the convolution tail carried from step to step), then decode
    through the cache: every greedy token is the float32 reference's
    largest logit, and the rows of logits the engine keeps are the
    reference's, through the jnp chunked form and through the
    interpreted kernels alike."""
    m = model()
    eng, outs, rows = serve(m, PROMPTS, 16, interpret=interpret)
    assert eng.step_compile_count() == 1
    assert eng.kv.blocks_in_use == 0
    assert eng.kv.state_slots_in_use == 0
    for p, out, r in zip(PROMPTS, outs, rows):
        assert len(out) == 16 and r.shape == (16, VOCAB)
        assert r.argmax(-1).tolist() == out
        z = reference(m, p + out[:-1], last=16)
        assert z.argmax(-1).tolist() == out
        assert row_errors(r, z).max() < 2e-4


def test_one_chunk_and_many_chunks_agree():
    m = model()
    _, a, ra = serve(m, PROMPTS[:1], 6, budget=16)
    _, b, rb = serve(m, PROMPTS[:1], 6, budget=64)
    # and delta-rule chunks of 64: a whole prefill chunk in one
    _, c, rc = serve(model(chunk=64), PROMPTS[:1], 6, budget=96)
    assert a == b == c
    assert row_errors(ra[0], rb[0]).max() < 2e-4
    assert row_errors(ra[0], rc[0]).max() < 2e-4


def test_engine_bf16_stays_inside_a_margin():
    """This architecture amplifies a rounding some 50 times at random
    weights (each head's output is normed whatever its size, and
    random queries meet no key they were trained for), and hidden 64 in
    bfloat16 is coarse: rows read 0.07-0.2 sigma from the float32
    reference (0.3-0.7 before the activations between two products
    stayed float32). The chip cell's limit is set at its own widths;
    here: no wrong computation, which reads 0.7-1.5 in bfloat16 too (a
    dropped carry)."""
    m = model("bfloat16")
    _, outs, rows = serve(m, PROMPTS, 12, dtype="bfloat16",
                          interpret=True)
    err = np.concatenate([
        row_errors(r, reference(m, p + o[:-1], last=12))
        for p, o, r in zip(PROMPTS, outs, rows)])
    print("bf16 engine, rows", np.round(np.sort(err), 3))
    assert LIMIT < np.median(err) < 0.2 and err.max() < 0.45


# ------------------------------------------------------ the delta rule


def ragged_case(with_state, H=2, dk=8, dv=16, S=8):
    """Runs of 1, 3, 63, 64, 65 and 200 tokens in one call, a hole of
    padding tokens between two of them and padding at the end."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    lens, firsts = [1, 3, 63, 64, 65, 200], [0, 5, 0, 7, 0, 11]
    T = sum(lens) + 20
    slot_ids, pos = np.full(T, -1, np.int32), np.zeros(T, np.int32)
    i = 0
    for s, (n, f) in enumerate(zip(lens, firsts)):
        slot_ids[i:i + n] = s + 1
        pos[i:i + n] = f + np.arange(n)
        i += n + (5 if s == 2 else 0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    f32 = lambda x: jnp.asarray(x, jnp.float32)                    # noqa
    args = (f32(unit(rng.normal(size=(T, H, dk))) / np.sqrt(dk)),
            f32(unit(rng.normal(size=(T, H, dk)))),
            f32(rng.normal(size=(T, H, dv))),
            f32(-np.exp(rng.uniform(-3, 1, size=(T, H)))),
            f32(2 / (1 + np.exp(-rng.normal(size=(T, H))))))
    state = rng.normal(size=(S, H, dk, dv)) if with_state \
        else np.zeros((S, H, dk, dv))
    runs = paged_runs(jnp.asarray(slot_ids), jnp.asarray(pos), None)
    return args, runs, f32(state), firsts


@pytest.mark.parametrize("with_state", (False, True),
                         ids=("zero_state", "incoming_state"))
@pytest.mark.parametrize("chunk", (8, 64))
def test_chunked_kernel_and_scan_agree_on_ragged_runs(with_state, chunk):
    import jax
    args, runs, state, firsts = ragged_case(with_state)
    assert int(runs[0][0]) == 6
    o0, s0 = jax.jit(gd.gated_delta_scan)(*args, runs, state)
    o1, s1 = jax.jit(functools.partial(
        gd.gated_delta_chunked, chunk=chunk))(*args, runs, state)
    with interpret_mode():
        o2, s2 = jax.jit(functools.partial(
            gd.gated_delta_ragged, chunk=chunk))(*args, runs, state)
    for o, s in ((o1, s1), (o2, s2)):
        assert float(abs(o - o0).max()) < 1e-5
        assert float(abs(s - s0).max()) < 2e-5
    # slots with no run keep their state bit for bit; padding tokens
    # give zeros
    for s in (s0, s1, s2):
        assert (np.asarray(s[0]) == np.asarray(state[0])).all()
        assert (np.asarray(s[7]) == np.asarray(state[7])).all()
    for o in (o0, o1, o2):      # the hole after the third run, the end
        assert float(abs(o[67:72]).max()) == float(abs(o[-15:]).max()) == 0
    if with_state:
        # a run from position 0 ignores what its slot held (its first
        # token's output is that of the zero state); another run reads it
        z = jax.jit(gd.gated_delta_scan)(*args, runs, state * 0)[0]
        for start, first in zip(np.asarray(runs[1])[:6], firsts):
            same = float(abs(z[start] - o0[start]).max()) < 1e-6
            assert same == (first == 0), (start, first)


def test_chunk_bookkeeping():
    import jax.numpy as jnp
    slot_ids = np.full(32, -1, np.int32)
    pos = np.zeros(32, np.int32)
    slot_ids[0], pos[0] = 2, 9                  # a decode token
    slot_ids[1:18], pos[1:18] = 0, np.arange(17)    # 17 from position 0
    runs = paged_runs(jnp.asarray(slot_ids), jnp.asarray(pos), None)
    ch = gd.delta_chunks(runs, 32, 4, chunk=8)
    assert gd.max_chunks(32, 4, 8) == 8 == ch["tok"].shape[0]
    assert int(ch["n"][0]) == 4
    F = gd
    assert ch["flags"].tolist()[:5] == [
        F._FIRST | F._LAST | F._SINGLE | F._REAL,       # the decode run
        F._FIRST | F._FRESH | F._REAL, F._REAL,         # 8 + 8
        F._LAST | F._SINGLE | F._REAL, 0]               # + 1
    assert ch["slot"].tolist() == [2, 0, 0, 0, 0, 0, 0, 0]
    assert ch["tok"][3].tolist() == [17] + [32] * 7
    # an empty step walks one all-padding chunk of slot 0: an identity
    none = paged_runs(jnp.full((32,), -1, jnp.int32),
                      jnp.zeros((32,), jnp.int32), None)
    ch = gd.delta_chunks(none, 32, 4, chunk=8)
    assert ch["flags"].tolist() == [F._FIRST | F._LAST | F._REAL] + [0] * 7


# ------------ what the comparison must catch, and what it must let pass


@pytest.mark.parametrize("kind", CONTROLS.PROGRAM)
def test_logits_catch_a_fault_of_the_program(kind):
    """Each planted fault of the controls (the chip cell runs the same
    ones), served in several prefill chunks and then decoded, against
    the reference of the model as published: every row at least 10
    times over the limit the sound engine stays 25 times under (the
    weakest, a bfloat16 state, reads 0.012-0.05; the others 0.3-1.4)."""
    m = model()
    faulty = m if kind != "beta_x1" else oh.OlmoHybridForGeneration(
        dataclasses.replace(m.arch, allow_neg_eigval=False),
        weights=m.weights)
    with CONTROLS.faulty_program(kind):
        _, (out,), (rows,) = serve(faulty, PROMPTS[:1], 8)
    err = row_errors(rows, reference(m, PROMPTS[0] + out[:-1], last=8))
    print(kind, err.min(), err.max())
    assert err.min() > 10 * LIMIT
    # and the patch is undone
    _, (out,), (rows,) = serve(m, PROMPTS[:1], 2)
    assert row_errors(rows, reference(
        m, PROMPTS[0] + out[:-1], last=2)).max() < LIMIT


@pytest.mark.parametrize("kind,lo,hi", (("bf16_operands", 0.01, 0.3),
                                        ("fp8_operands", 0.3, 3.0)))
def test_logits_catch_a_lower_precision(kind, lo, hi):
    m = model()
    seq = PROMPTS[0] + PROMPTS[2]
    z = reference(m, seq, last=16)
    low = reference(m, seq, last=16,
                    ref=CONTROLS.low_precision_reference(kind))
    err = np.median(row_errors(low, z))
    print(kind, err)
    assert lo < err < hi


def test_compare_is_the_driver_s():
    """The driver's `compare` on the engine's rows: the statistic of
    the cell, one pass of the reference."""
    import types
    m = model()
    _, (out,), (rows,) = serve(m, PROMPTS[:1], 6)
    d = DRIVERS.Driver.__new__(DRIVERS.Driver)
    d.env = types.SimpleNamespace(config_name="olmo_hybrid_7b_serve")
    d.model = m
    got = d.compare(PROMPTS[0], out, rows)
    assert got["err"].shape == (6,) and got["err"].max() < 2e-4
    assert got["margin"].max() == 0.0
    off = d.compare(PROMPTS[0], out, rows + 0.01 * rows.std())
    assert 0.008 < off["err"].min() < off["err"].max() < 0.012


# ------------------------------------------------- slots and the manager


def test_a_reused_slot_starts_from_zero():
    """Two requests through ONE slot, one after the other: the second
    finds the first's state and convolution tail in its slot and must
    not see them (a run from position 0 starts from zeros)."""
    m = model()
    eng = engine(m, slots=1)
    _, (a,), (ra,) = serve(m, PROMPTS[:1], 8, eng=eng)
    _, (b,), (rb,) = serve(m, PROMPTS[2:], 8, eng=eng)
    assert float(abs(eng.kv.states[0]).max()) > 0     # nothing was wiped
    _, (a1,), (ra1,) = serve(m, PROMPTS[:1], 8, slots=1)
    _, (b1,), (rb1,) = serve(m, PROMPTS[2:], 8, slots=1)
    assert (a, b) == (a1, b1)
    assert (ra == ra1).all() and (rb == rb1).all()


@pytest.mark.parametrize("interpret,dtype", (
    (False, "float32"), (True, "float32"), (False, "bfloat16")),
    ids=("fallback", "kernels", "fallback_bf16"))
def test_alone_and_in_company_bit_for_bit(interpret, dtype):
    """A prompt's prefill chunks end on multiples of the delta-rule
    chunk (`Scheduler.prefill_align`), so the chunked recurrence cuts
    it at the same positions whatever shares its steps: the rows of
    logits of a request served alone and among others are EQUAL. In
    bfloat16 too: what a step hands the next (state, convolution tail)
    is float32, as what a token hands the next inside a step is (on the
    chip a tail kept in bfloat16 moved the sentinel's tokens in company
    in 3 runs of 7)."""
    m = model(dtype)
    fed = []
    from paddle_tpu.serving.scheduler import Scheduler
    plan = Scheduler.plan

    def watch(self, *args):
        out = plan(self, *args)
        fed.extend((start, len(chunk), done)
                   for _, chunk, start, done in out.prefills)
        return out

    Scheduler.plan = watch
    try:
        _, (alone,), (ra,) = serve(m, PROMPTS[:1], 8, interpret=interpret,
                                   dtype=dtype)
        _, outs, rows = serve(m, [PROMPTS[2], PROMPTS[0], PROMPTS[1]], 8,
                              interpret=interpret, budget=24, dtype=dtype)
    finally:
        Scheduler.plan = plan
    assert outs[1] == alone
    assert (rows[1] == ra).all()
    assert all(start % 8 == 0 and (done or n % 8 == 0)
               for start, n, done in fed)
    assert {n for _, n, _ in fed} > {16}      # the cuts did differ


def test_preemption_frees_the_blocks_and_resumes_from_zero():
    m = model()
    _, want, _ = serve(m, PROMPTS, 12)
    # a full-layer pool too small for the three contexts together
    eng = engine(m, blocks=22)
    reqs = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
    eng.run()
    assert eng.scheduler.preemption_count > 0
    assert [list(r.output) for r in reqs] == want
    assert eng.kv.blocks_in_use == 0
    assert eng.kv.state_slots_in_use == 0


def test_the_manager_holds_state_beside_blocks():
    from paddle_tpu.serving.kv_cache import PagedKVCache
    kinds = ("linear", "linear", "full", "linear")
    kv = PagedKVCache(4, 4, 16, num_blocks=9, block_size=4, max_slots=3,
                      max_blocks_per_slot=8, layer_kinds=kinds,
                      linear_state=(4, 8, 16), conv_tail=(3, 128))
    assert kv.window_allocator is None and kv.window is None
    assert len(kv.k_pools) == 1 and len(kv.states) == 3
    assert kv.states[0].shape == (3, 4, 8, 16)
    assert str(kv.states[0].dtype) == "float32"
    assert kv.conv_tails[0].shape == (3, 3, 128)
    assert str(kv.conv_tails[0].dtype) == "float32"
    assert kv.state_bytes == 3 * (3 * 4 * 8 * 16 * 4 + 3 * 3 * 128 * 4)
    assert len(kv.tables()) == 1
    pools = kv._pools()
    assert [p.ndim for p in pools] == [4, 4, 4, 3, 4, 3, 4, 3]
    kv._set_pools(pools)
    assert len(kv.states) == len(kv.conv_tails) == 3
    # admission counts full-layer blocks only; the state is a slot's
    assert kv.ensure_capacity(0, 10) and kv.blocks_in_use == 3
    kv.slot_lens[0] = 10
    assert kv.state_slots_in_use == 1
    kv.release_slot(0)
    assert kv.blocks_in_use == 0 and kv.state_slots_in_use == 0
    assert kv.kv_bytes_per_token == 2 * 4 * 16 * 4
    with pytest.raises(ValueError, match="linear_state"):
        PagedKVCache(4, 4, 16, num_blocks=9, block_size=4, max_slots=3,
                     max_blocks_per_slot=8, layer_kinds=kinds)
    with pytest.raises(ValueError, match="needs a window"):
        PagedKVCache(2, 4, 16, num_blocks=9, block_size=4, max_slots=3,
                     max_blocks_per_slot=8,
                     layer_kinds=("sliding", "full"))


@pytest.mark.parametrize("what", ("prefix_caching", "draft_k",
                                  "truncate_slot", "cow_block",
                                  "export_blocks"))
def test_what_a_recurrent_state_forbids_is_refused(what):
    m = model()
    reason = "neither truncated nor shared by blocks"
    if what in ("prefix_caching", "draft_k"):
        with pytest.raises(ValueError, match=reason):
            engine(m, **{what: 2 if what == "draft_k" else True})
        return
    eng = engine(m)
    assert eng.kv.ensure_capacity(0, 8)
    args = {"truncate_slot": (0, 4), "cow_block": (0, 0),
            "export_blocks": ([1],)}[what]
    with pytest.raises(ValueError, match=reason):
        getattr(eng.kv, what)(*args)


def test_a_budget_no_aligned_chunk_fits_is_refused():
    """Prefill chunks are cut to multiples of the delta rule's chunk: a
    budget that leaves less than one beside the decoding slots would
    never feed a long prompt."""
    with pytest.raises(ValueError, match="could never be fed"):
        engine(model(chunk=64), budget=64)


def test_a_plan_that_feeds_a_slot_twice_is_refused():
    eng = engine(model())
    with pytest.raises(AssertionError, match="one run a slot"):
        eng._pack([(1, 5, 9)], [(1, np.arange(4), 10, False)])


# -------------------------------------------------- spans and counters


def test_flight_fields_of_a_hand_made_plan():
    from paddle_tpu.serving.engine import (_attention_work_by_kind,
                                           _linear_work)
    from paddle_tpu.serving.scheduler import Plan
    # a decode token at position 9, a verify-free chunk of 17 from 32
    # and a chunk of 8 from 0
    plan = Plan([(0, [5], 9)], [(1, np.arange(17), 32, False),
                              (2, np.arange(8), 0, True)], ())
    # the kernel loads the decode run's one row, two tiles of 8 + the 8
    # rows that align a tile's start and the 17th token's row, one tile
    assert _linear_work(plan, 8) == dict(
        lin_tokens=26, lin_runs=3, lin_single_runs=1,
        lin_chunks=1 + 3 + 1, lin_chunk_size=8,
        lin_rows_walked=1 + (2 * 16 + 1) + 16)
    assert _linear_work(plan, 64)["lin_chunks"] == 3
    work = _attention_work_by_kind(plan)
    assert work["kv_tokens_read_window"] == work["attn_pairs_window"] == 0
    assert work["kv_tokens_read_full"] == 10 + 49 + 8
    assert work["attn_pairs_full"] == 10 + (17 * 32 + 17 * 18 // 2) + 36


def test_flight_record_and_scopes():
    from paddle_tpu.serving import tracing
    m = model()
    eng = engine(m)
    tracing.enable()
    try:
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=8)
        eng.run()
    finally:
        tracing.disable()
    recs = list(eng.flight.records)
    fields = ("lin_tokens", "lin_runs", "lin_chunks", "lin_chunk_size",
              "lin_single_runs", "lin_rows_walked", "state_slots_in_use", "kv_tokens_read_full",
              "attn_pairs_full", "kv_blocks_in_use_full",
              "kv_tokens_read_window", "attn_pairs_window",
              "kv_blocks_in_use_window")
    for r in recs:
        assert set(fields) <= set(r)
        assert r["lin_tokens"] == r["prefill_tokens"] + r["decode_tokens"]
        assert r["lin_runs"] <= 3 and r["lin_chunk_size"] == 8
        assert r["lin_runs"] <= r["lin_chunks"] <= \
            r["lin_runs"] + r["lin_tokens"] // 8
        assert r["lin_single_runs"] <= r["lin_runs"]
        assert r["lin_tokens"] <= r["lin_rows_walked"] <= \
            r["lin_single_runs"] + 16 * r["lin_chunks"]
        assert r["kv_tokens_read_window"] == r["attn_pairs_window"] == \
            r["kv_blocks_in_use_window"] == 0
        assert r["kv_blocks_in_use"] == r["kv_blocks_in_use_full"]
        assert r["kv_blocks_total"] == 80
        # the paged kernel's logits over the full layers, the model's
        # heads kept of what its tiles (the padded heads too) compute
        full = eng._block.arch.layer_kinds.count("full")
        assert r["attn_logits_useful"] == \
            full * r["attn_pairs_full"] * eng._block.arch.num_heads
        assert r["attn_logits_issued"] >= r["attn_logits_useful"] > 0
    # the first step: one chunk of 16 from position 0
    assert (recs[0]["lin_tokens"], recs[0]["lin_runs"],
            recs[0]["lin_chunks"]) == (16, 1, 2)
    assert max(r["state_slots_in_use"] for r in recs) == 3
    # a record is noted by the call that DISPATCHED its step: the last
    # request's last token is still in flight then (its slot is live),
    # and the call that reads it back dispatches nothing
    assert recs[-1]["state_slots_in_use"] == 1
    assert eng.kv.state_slots_in_use == 0
    txt = eng._step_fn._jitted.trace(
        *eng.example_step_args()).lower().as_text(debug_info=True)
    for scope in ("lin_proj", "lin_conv", "gated_delta", "lin_gate_out",
                  "attn_full"):
        assert scope in txt, scope
    assert "attn_window" not in txt
    # with tracing off the step is the same program: nothing was added
    assert eng.step_compile_count() == 1
