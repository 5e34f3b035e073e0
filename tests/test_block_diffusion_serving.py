"""Decoding by blocks through the normal serving path, at a small size
on the CPU: a model that generates by diffusion over blocks
(`models/sdar_moe.py`) served by `ServingEngine` — chunked prefill under
the block-causal mask, then blocks of L rows through the paged cache —
against the model's eager `generate()` token for token and against the
plain reference's pass-by-pass rows (`benchmarks/configs/
sdar_30b_a3b_pp8_serve_reference.py`, through the cell's own driver)."""
import contextlib
import dataclasses
import functools
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.files import load_module  # noqa: E402

from paddle_tpu.models import sdar_moe  # noqa: E402
from paddle_tpu.models.serving_block import BlockDecoding  # noqa: E402
from paddle_tpu.ops.pallas import interpret_mode  # noqa: E402
from paddle_tpu.serving import batcher  # noqa: E402
from paddle_tpu.serving.engine import ServingEngine  # noqa: E402

CONTROLS = load_module("configs", "sdar_30b_a3b_pp8_serve_controls")
DRIVERS = load_module("drivers", "serve_frontend_sdar")
VOCAB, MASK, L = 97, 96, 4
#: sigma (rms of a row of logits against the reference's); the float32
#: engine reads under 1e-4, every planted fault over 0.01
LIMIT = 1e-3
LIMITS = dict(logit_err_sigmas=LIMIT, logit_search_sigmas=LIMIT,
              tie_gap=0.05, max_passes=4,
              confidence_tie_gap=0.05, margin_sigmas=0.01)


def small(rule="low_confidence_static", threshold=0.9, steps=4):
    return sdar_moe.SdarMoeArch(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        num_layers=2, num_experts=8, top_k=2, expert_width=32,
        vocab_rows=VOCAB, rope_theta=1e4, max_positions=256,
        compute_dtype="float32", block_decoding=BlockDecoding(
            block_length=L, mask_token_id=MASK, denoising_steps=steps,
            rule=rule, threshold=threshold))


@functools.lru_cache(maxsize=None)
def model(**kw):
    return sdar_moe.SdarMoeForGeneration(small(**kw), seed=3)


def engine(m, *, budget=32, slots=4, blocks=80, **kw):
    return ServingEngine(m, max_slots=slots, block_size=8,
                         num_blocks=blocks, max_seq_len=128,
                         token_budget=budget, cache_dtype="float32", **kw)


@functools.lru_cache(maxsize=None)
def shared_engine(**kw):
    """One engine (one compile) for the tests that only serve."""
    return engine(model(**kw))


def serve(eng, prompts, new_tokens, *, interpret=False, between=None):
    """-> (each request's tokens, each request's passes as the cell's
    driver keeps them: (block start, ids fed, decided before, positions
    decided, their tokens, the engine's rows [L, V])). `between(eng,
    reqs)` runs after every step."""
    passes = {}
    eng.on_block_pass = lambda req, start, fed, was, take, tokens: \
        passes.setdefault(req.req_id, []).append(
            (int(start), tuple(fed), tuple(was), tuple(take),
             tuple(tokens)))
    new = new_tokens if isinstance(new_tokens, (list, tuple)) \
        else [new_tokens] * len(prompts)
    with (interpret_mode() if interpret else contextlib.nullcontext()):
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, new)]
        out = [[] for _ in reqs]
        slots = [-1] * len(reqs)
        while eng.scheduler.has_work:
            had = [len(passes.get(r.req_id, ())) for r in reqs]
            for i, r in enumerate(reqs):
                slots[i] = r.slot if r.slot >= 0 else slots[i]
            eng.step()
            for i, r in enumerate(reqs):
                slots[i] = r.slot if r.slot >= 0 else slots[i]
                for p in passes.get(r.req_id, ())[had[i]:]:
                    out[i].append(p + (np.asarray(
                        eng.sample_logits[slots[i]]),))
            if between is not None:
                between(eng, reqs)
    eng.on_block_pass = None
    assert eng.kv.blocks_in_use == 0
    return [list(r.output) for r in reqs], out


def prompts_of(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB - 1, n).tolist() for n in lengths]


def compare(m, prompt, passes, **limits):
    """The cell's comparison (`drivers/serve_frontend_sdar.py`)."""
    d = DRIVERS.Driver.__new__(DRIVERS.Driver)
    d.env = types.SimpleNamespace(
        config_name="sdar_30b_a3b_pp8_serve",
        config={"reference": dict(LIMITS, **limits)})
    d.model = m
    return d.compare(prompt, passes)


# ---------------------------------------- tokens, rows, and the reference


@pytest.mark.parametrize("interpret", (False, True),
                         ids=("fallback", "kernels"))
def test_serving_is_generate_token_for_token(interpret):
    """Prompt lengths 0-3 mod 4 (a tail starts the first block as
    decided positions; a prompt shorter than a block has no prefill at
    all) and horizons that end inside a block, several to a step."""
    m = model()
    prompts = prompts_of((8, 9, 18, 23, 3))
    new = (6, 7, 5, 9, 4)
    want = [m.generate(p, n) for p, n in zip(prompts, new)]
    eng = engine(m) if interpret else shared_engine()
    got, passes = serve(eng, prompts, new, interpret=interpret)
    assert got == want
    assert eng.step_compile_count() == 1
    # a block of 4 at one position a pass: the rows fed start with the
    # prompt's tail and end with one mask
    first = passes[1][0]
    assert first[0] == 8 and first[1] == (prompts[1][8], MASK, MASK, MASK)
    assert first[2] == (True, False, False, False)


def test_rows_of_every_pass_are_the_reference_s():
    """The engine's float32 rows of every pass (denoise and commit)
    against the reference's `denoise_pass` fed the engine's block state,
    and every decision against the reference's rows."""
    m = model()
    prompts = prompts_of((21, 8), seed=2)
    tokens, passes = serve(shared_engine(), prompts, (10, 6))
    for prompt, ps in zip(prompts, passes):
        got = compare(m, prompt, ps)
        assert got["err"].shape == (len(ps), L)
        assert got["err"].max() < 2e-4, got["err"].max()
        assert got["margin"].max() == 0.0 and not got["faults"]
        assert not got["swaps"]
    # commits among them: 21 -> 10 fills the blocks at 20, 24 and 28
    commits = [p for p in passes[0] if all(p[2])]
    assert [p[0] for p in commits] == [20, 24]
    assert all(p[3] == () for p in commits)
    # the comparison sees what it should: shifted rows read their shift
    off = [p[:5] + (p[5] + 0.01 * p[5].std(),) for p in passes[0]]
    shifted = compare(m, prompts[0], off)
    assert 0.008 < shifted["err"].min() < shifted["err"].max() < 0.012


@pytest.mark.parametrize("kind", CONTROLS.PROGRAM)
def test_the_comparison_catches_a_fault_of_the_program(kind):
    """Each planted fault of the controls (the chip cell runs the same
    ones) over three blocks: rows over the limit the sound engine stays
    ten times under, and the patch is undone."""
    m = model()
    prompt = prompts_of((22,), seed=4)
    undo = CONTROLS.plant(kind, m.arch.num_layers)
    try:
        _, (ps,) = serve(engine(m), prompt, 10)
    finally:
        undo()
    got = compare(m, prompt[0], ps)
    print(kind, got["err"].max(), got["err"].mean())
    assert got["err"].max() > 10 * LIMIT
    _, (ps,) = serve(shared_engine(), prompt, 3)
    assert compare(m, prompt[0], ps)["err"].max() < LIMIT


def test_the_comparison_catches_a_wrong_position():
    m = model()
    prompt = prompts_of((12,), seed=6)
    _, (ps,) = serve(shared_engine(), prompt, 4)
    # the first pass decided ONE position: claim it decided another
    start, fed, was, take, tokens, rows = ps[0]
    other = next(i for i in range(L) if i not in take)
    ps[0] = (start, fed, was, (other,), (int(rows[other].argmax()),), rows)
    got = compare(m, prompt[0], ps[:1], confidence_tie_gap=1e-4)
    assert any("decided at log-confidence" in w for w in got["faults"])
    assert any("left masked" in w for w in got["faults"])


# ------------------------------- alone, in company, after a preemption


def test_alone_in_company_and_after_a_preemption_mid_block():
    """The same request alone, among others (other chunk cuts, other
    rows of the step) and preempted after two passes of its second
    block: the same tokens, the same block states pass by pass, and the
    same rows, bit for bit. The preempted request keeps its block's
    state and prefills again up to the block's first position."""
    m = model()
    eng = shared_engine()
    target, others = prompts_of((26,), seed=7)[0], prompts_of((19, 40, 9))
    (alone,), (pa,) = serve(eng, [target], 9)
    outs, pc = serve(eng, [others[0], target, others[1], others[2]], 9)
    assert outs[1] == alone == m.generate(target, 9)
    assert [p[:5] for p in pc[1]] == [p[:5] for p in pa]
    assert all((a[5] == b[5]).all() for a, b in zip(pa, pc[1]))

    hit = []

    def preempt(eng, reqs):
        r = reqs[0]
        if not hit and r.block_start == 28 and r.block_passes == 2:
            before = (r.block_start, list(r.block_tokens),
                      list(r.block_decided))
            assert eng.scheduler._preempt_victim(set()) is r
            assert eng.kv.blocks_in_use == 0 and r.state == "queued"
            assert (r.block_start, r.block_tokens, r.block_decided) \
                == before
            hit.append(len(r.output))

    (again,), (pp,) = serve(eng, [target], 9, between=preempt)
    assert hit and again == alone
    assert eng.scheduler.preemption_count >= 1
    assert [p[:5] for p in pp] == [p[:5] for p in pa]
    assert all((a[5] == b[5]).all() for a, b in zip(pa, pp))


def test_block_pressure_preempts_and_resumes():
    m = model()
    prompts = prompts_of((40, 23, 31), seed=8)
    want, _ = serve(shared_engine(), prompts, 12)
    eng = engine(m, blocks=13)
    got, _ = serve(eng, prompts, 12)
    assert eng.scheduler.preemption_count > 0
    assert got == want


# ------------------------------------------------------------- the rules


@pytest.mark.parametrize("rule,threshold,steps", (
    ("low_confidence_dynamic", 0.0145, 4), ("low_confidence_static", 0.9, 2),
    ("low_confidence_dynamic", 0.9, 4)))
def test_both_rules_decide_several_positions_a_pass(rule, threshold, steps):
    """A threshold low enough (the small model's confidences are 0.013
    to 0.017 over 97 ids) and a schedule of two positions a pass: passes that decide
    several positions, tokens delivered in position order, the engine
    the eager `generate()`."""
    m = model(rule=rule, threshold=threshold, steps=steps)
    prompts = prompts_of((10, 16, 5), seed=9)
    eng = shared_engine(rule=rule, threshold=threshold, steps=steps)
    got, passes = serve(eng, prompts, (9, 8, 7))
    assert got == [m.generate(p, n) for p, n in zip(prompts, (9, 8, 7))]
    most = max(len(p[3]) for ps in passes for p in ps)
    assert most > 1 if threshold < 0.9 or steps < 4 else most == 1
    for prompt, ps in zip(prompts, passes):
        got = compare(m, prompt, ps)
        assert got["err"].max() < 2e-4 and not got["faults"]


def test_a_mask_id_in_the_prompt_or_among_the_candidates_is_a_token():
    """Positions are told apart by position: a prompt full of the mask
    id, in its whole blocks and in its tail, is served as `generate()`
    serves it, and its tail's rows are fed decided."""
    m = model()
    prompt = [MASK] * 6 + prompts_of((3,))[0] + [MASK]
    (out,), (ps,) = serve(shared_engine(), [prompt], 6)
    assert out == m.generate(prompt, 6)
    assert ps[0][1][:2] == (prompt[8], MASK) and ps[0][2][:2] == (True, True)
    # a decided token equal to the mask id is not masked again: every
    # decided id of the block is overwritten with it after each step
    eng, seen = shared_engine(), []
    eng.on_block_pass = lambda req, start, fed, was, take, tokens: \
        seen.append((fed, was))
    req = eng.submit(prompts_of((8,))[0], max_new_tokens=4)
    while eng.scheduler.has_work:
        eng.step()
        for i, d in enumerate(req.block_decided or ()):
            if d:
                req.block_tokens[i] = MASK
    eng.on_block_pass = None
    assert len(seen) == 4 and len(req.output) == 4
    assert [sum(was) for _, was in seen] == [0, 1, 2, 3]
    assert all(list(fed) == [MASK] * L for fed, _ in seen)


# ------------------------------------------- scheduler, batcher, refusals


def test_a_slot_advances_at_a_commit_and_only_then():
    m = model()
    eng = shared_engine()
    req = eng.submit(prompts_of((13,), seed=3)[0], max_new_tokens=11)
    lens = []
    while eng.scheduler.has_work:
        slot = req.slot
        fed_all = req.block_decided is not None and all(req.block_decided) \
            and req.state == "decode"
        before = int(eng.kv.slot_lens[slot]) if slot >= 0 else None
        plan_before = req.state
        eng.step()
        if req.slot < 0:
            break
        after = int(eng.kv.slot_lens[req.slot])
        if plan_before == "decode":
            assert after == before + (L if fed_all else 0)
            assert after % L == 0 and after == req.block_start
        lens.append(after)
    assert lens[0] == 12 and max(lens) == 20
    assert req.output == m.generate(req.prompt, 11)


def test_plan_entries_are_lists_from_a_first_position():
    """One shape for every caller: (slot, [token ids], first position):
    a block's L ids here, one id for a plain decode."""
    from paddle_tpu.serving.scheduler import Plan
    eng = shared_engine()
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts_of((8, 5))]
    seen = []
    while eng.scheduler.has_work:
        plan = eng.scheduler.plan()
        seen += plan.decode
        # (drive the planned step by hand: what step() does)
        flight = eng._run_block_tick(plan, False)
        sp, got = flight.sp, flight.got
        for slot in sp.prefill_done:
            eng.scheduler.slots[slot].state = "decode"
        for slot, tokens, _ in got["groups"]:
            eng.emit(eng.scheduler.slots[slot], tokens, 0.0, False)
    assert seen and all(isinstance(t, list) and len(t) == L
                        and pos % L == 0 for _, t, pos in seen)
    assert all(len(r.output) == 5 for r in reqs)
    assert isinstance(Plan([], [], []).decode, list)
    # the packer: a block's rows are all sample rows; a prefill that
    # completes samples nothing
    layout = batcher.PlanLayout(16, 3, [("block_tables", (3, 4))],
                                sample_rows=L)
    buf = batcher.PlanBuffers(layout)
    sp = batcher.pack_step(16, 3, [(2, [5, 6, MASK, MASK], 8)],
                           [(0, np.arange(4, dtype=np.int32), 0, True)],
                           buffers=buf)
    assert buf.sample_index.shape == (3, L)
    assert buf.sample_index.tolist() == [[-1] * 4, [-1] * 4, [0, 1, 2, 3]]
    assert sp.decode_tokens == 4 and sp.prefill_done == [0]
    assert buf.positions[:8].tolist() == [8, 9, 10, 11, 0, 1, 2, 3]
    with pytest.raises(ValueError, match="exceeds the verify width"):
        batcher.pack_step(16, 3, [(2, [1] * 5, 8)], [], buffers=buf)
    # and the one-row layout takes a list of one as it took a bare int
    a = batcher.pack_step(8, 2, [(1, [7], 3)], [])
    b = batcher.pack_step(8, 2, [(1, 7, 3)], [])
    assert a.sample_index.tolist() == b.sample_index.tolist() == [-1, 0]
    assert a.token_ids.tolist() == b.token_ids.tolist()


def test_a_chunk_that_ends_inside_a_block_is_refused_where_it_is_packed():
    eng = shared_engine()
    with pytest.raises(AssertionError, match="ends inside a block"):
        eng._pack([], [(0, np.arange(6, dtype=np.int32), 8, False)])
    with pytest.raises(AssertionError, match="ends inside a block"):
        eng._pack([], [(0, np.arange(8, dtype=np.int32), 6, True)])
    eng._pack([], [(0, np.arange(6, dtype=np.int32), 8, True)])


@pytest.mark.parametrize("what,kw,match", (
    ("prefix_caching", dict(prefix_caching=True), "prefix_caching"),
    ("temperature", dict(sampling=batcher.SamplingConfig(
        strategy="sampling", temperature=0.7)), "temperature"),
    ("draft_k", dict(draft_k=2), "draft_k"),
    ("budget", dict(budget=16), "never be prefilled")))
def test_what_block_decoding_does_not_build_is_refused(what, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(model(), **kw)


def test_window_layers_beside_block_decoding_are_refused():
    m = model()

    @dataclasses.dataclass(frozen=True)
    class Windowed(sdar_moe.SdarMoeArch):
        @property
        def layer_kinds(self):
            return ("sliding", "full")

    arch = Windowed(**dataclasses.asdict(m.arch) | {
        "block_decoding": m.arch.block_decoding})
    with pytest.raises(ValueError, match="sliding"):
        engine(sdar_moe.SdarMoeForGeneration(arch, weights=m.weights))


def test_a_request_past_the_slot_s_blocks_is_refused():
    eng = shared_engine()
    with pytest.raises(ValueError, match="cached tokens"):
        eng.submit([1] * 100, max_new_tokens=27)    # 127 -> 128 rows: fits
        eng.submit([1] * 101, max_new_tokens=28)    # 129 -> 132 rows
    eng.scheduler.cancel(eng.scheduler.queue[0])
    assert not eng.scheduler.has_work


# --------------------------------------------------- spans and counters


def test_flight_record_spans_and_scopes():
    from paddle_tpu.serving import tracing
    m = model()
    eng = engine(m)
    prompts = prompts_of((21, 8, 14), seed=5)
    tracing.TRACER.reset()
    tracing.enable()
    try:
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        eng.run()
        traces = {t.trace_id: t for t in tracing.TRACER.traces()}
    finally:
        tracing.disable()
    recs = list(eng.flight.records)
    fields = ("diff_block_len", "diff_slot_passes", "diff_rows_masked",
              "diff_tokens_decided", "diff_commits",
              "diff_blocks_committed", "kv_tokens_read_full",
              "attn_pairs_full", "moe_pairs_total", "moe_pairs_local",
              "moe_experts_hit", "attn_logits_useful",
              "attn_logits_issued")
    heads, layers = m.arch.num_heads, m.arch.num_layers
    for r in recs:
        assert set(fields) <= set(r)
        assert r["diff_block_len"] == L
        # decode_tokens counts the ROWS fed
        assert r["decode_tokens"] == L * r["diff_slot_passes"]
        assert r["diff_commits"] == r["diff_blocks_committed"] \
            <= r["diff_slot_passes"]
        assert r["diff_tokens_decided"] == \
            r["diff_slot_passes"] - r["diff_commits"]   # one a pass
        assert r["diff_tokens_decided"] <= r["diff_rows_masked"]
        assert r["kv_tokens_read_window"] == r["attn_pairs_window"] == 0
        assert r["moe_pairs_total"] == r["moe_pairs_local"] == \
            layers * m.arch.top_k * (r["prefill_tokens"]
                                     + r["decode_tokens"])
        assert r["attn_logits_useful"] == \
            layers * heads * r["attn_pairs_full"]
        assert r["attn_logits_issued"] >= r["attn_logits_useful"] > 0
    assert sum(r["diff_tokens_decided"] for r in recs) >= 30
    assert sum(r["diff_commits"] for r in recs) == 2 + 2 + 2
    for req in reqs:
        t = traces[req.trace_id]
        names = [e.name for e in t.events]
        assert names.count("block_committed") == 2
        d = t.derive()
        # ten tokens in ten events or fewer; those handed over together
        # lie at gap 0, and the first is the first DELIVERED
        delivered = [e for e in t.events
                     if e.name in ("first_token", "decode_step")]
        inside = sum(e.attrs.get("inside", 0) for e in delivered)
        assert len(delivered) + inside == 10
        assert len(d["inter_token"]) == 9
        assert d["inter_token"].count(0.0) >= inside
        assert d["ttft"] > 0
    # the benchmark's reader on these records: tokens decided a slot
    # pass, a little over 4 / 5 (a request's last block has no commit)
    reader = load_module("layer_metrics", "diffusion.tokens_per_slot_pass")
    logged = []
    value = reader.read(types.SimpleNamespace(flight=recs,
                                              log=logged.append))
    assert value == pytest.approx(
        sum(r["diff_tokens_decided"] for r in recs)
        / sum(r["diff_slot_passes"] for r in recs))
    assert 0.8 <= value < 0.9 and "commits" in logged[-1]
    assert reader.read(types.SimpleNamespace(
        flight=[{"ts": 1.0, "decode_tokens": 3}], log=logged.append)) is None
    txt = eng._step_fn._jitted.trace(
        *eng.example_step_args()).lower().as_text(debug_info=True)
    for scope in ("diffusion_confidence", "moe_router", "moe_experts",
                  "attn_full"):
        assert scope in txt, scope
    assert eng.step_compile_count() == 1
