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


def held_to_the_device(eng):
    """Wrap `eng`'s readback: after every step read back, each live
    request's block (the host's replay of the decision) must be the
    state the step handed the next one on the device. -> the undo."""
    land = eng._land_tick

    def checked(fl):
        land(fl)
        ids, was, n = (np.asarray(a) for a in fl.got["block_state"])
        for slot, _, _ in fl.sp.decode_entries:
            req = fl.reqs[slot]
            if not req.done:
                assert (ids[slot].tolist(), was[slot].tolist(),
                        int(n[slot])) == (req.block_tokens,
                                          req.block_decided,
                                          req.block_passes), slot

    eng._land_tick = checked
    return lambda: vars(eng).pop("_land_tick")


def serve(eng, prompts, new_tokens, *, interpret=False, between=None,
          depth=1, check=True):
    """-> (each request's tokens, each request's passes as the cell's
    driver keeps them: (block start, ids fed, decided before, positions
    decided, their tokens, the engine's rows [L, V])), served at `depth`
    (`check`: every decision the host replays held against the
    device's). `between(eng, reqs)` runs after every step."""
    passes = {}
    eng.on_block_pass = lambda req, start, fed, was, take, tokens: \
        passes.setdefault(req.req_id, []).append(
            (int(start), tuple(fed), tuple(was), tuple(take),
             tuple(tokens)))
    new = new_tokens if isinstance(new_tokens, (list, tuple)) \
        else [new_tokens] * len(prompts)
    eng._depth = depth
    undo = held_to_the_device(eng) if check else lambda: None
    try:
        with (interpret_mode() if interpret else contextlib.nullcontext()):
            reqs = [eng.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, new)]
            out = [[] for _ in reqs]
            slots = [-1] * len(reqs)

            def landed():
                # the passes read back since, with the rows they read
                for i, r in enumerate(reqs):
                    slots[i] = r.slot if r.slot >= 0 else slots[i]
                    for p in passes.get(r.req_id, ())[len(out[i]):]:
                        out[i].append(p + (np.asarray(
                            eng.sample_logits[slots[i]]),))

            while eng.scheduler.has_work:
                landed()
                eng.step()
                landed()
                if between is not None:
                    between(eng, reqs)
    finally:
        undo()
        eng._depth = 1
        eng.on_block_pass = None
    assert eng.kv.blocks_in_use == 0 and eng._inflight is None
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
        # (a fault of the host's plan leaves the host's blocks and the
        # device's apart: nothing to hold the replay against)
        _, (ps,) = serve(engine(m), prompt, 10, check=False)
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
            # as a plan that preempts: the pass in flight is read first
            eng.drain()
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
    """A pool too small for three requests: the plan that has to preempt
    reads the pass in flight back first, then runs as the synchronous
    order does: the same victims, the same passes, the same tokens."""
    m = model()
    prompts = prompts_of((40, 23, 31), seed=8)
    want, _ = serve(shared_engine(), prompts, 12)
    eng = engine(m, blocks=13)
    got, passes = serve(eng, prompts, 12)
    assert eng.scheduler.preemption_count > 0
    assert got == want
    n = eng.scheduler.preemption_count
    sync, passes0 = serve(eng, prompts, 12, depth=0)
    assert sync == want
    assert eng.scheduler.preemption_count == 2 * n
    assert [[p[:5] for p in ps] for ps in passes] \
        == [[p[:5] for p in ps] for ps in passes0]


# ------------------------------------------------- depth 1 against depth 0


@pytest.mark.parametrize("rule,threshold,steps", (
    ("low_confidence_static", 0.9, 4), ("low_confidence_static", 0.9, 2),
    ("low_confidence_dynamic", 0.0145, 4),
    ("low_confidence_dynamic", 0.9, 4), ("low_confidence_dynamic", 0.1, 4)))
def test_the_device_decides_as_the_host_rule(rule, threshold, steps):
    """`BlockDecoding.decide_rows` (the step's) against `decide` (the
    host's replay) on random confidences and masks at every pass count,
    with exact ties and the float32 neighbours of the threshold (whose
    float32 lies below it at 0.9 and above it at 0.1)."""
    import jax.numpy as jnp
    bd = model(rule=rule, threshold=threshold, steps=steps) \
        .arch.block_decoding
    rng = np.random.default_rng(len(rule) + steps)
    S = 512
    near = np.float32(threshold)
    pool = np.asarray([np.nextafter(near, np.float32(0)), near,
                       np.nextafter(near, np.float32(1)),
                       np.float32(0.89999998), np.float32(0.90000004),
                       0.25, 0.5, 0.5, 0.015], np.float32)
    conf = np.where(rng.random((S, L)) < 0.5,
                    rng.choice(pool, (S, L)),
                    rng.random((S, L)).astype(np.float32))
    conf[:8] = conf[:8, :1]                     # every position tied
    masked = rng.random((S, L)) < 0.7
    masked[:, 0] |= ~masked.any(-1)             # a pass masks one at least
    n_pass = rng.integers(0, L + 2, S).astype(np.int32)
    got = np.asarray(bd.decide_rows(jnp.asarray(masked), jnp.asarray(conf),
                                    jnp.asarray(n_pass)))
    for s in range(S):
        want = bd.decide([i for i in range(L) if masked[s, i]], conf[s],
                         int(n_pass[s]))
        assert np.flatnonzero(got[s]).tolist() == want, (s, conf[s])
    if rule == "low_confidence_dynamic" and threshold == 0.9:
        # above the threshold in double precision: 0.90000004 is,
        # float32(0.9) = 0.89999998 is not
        row = jnp.asarray([[0.89999998, 0.90000004, 0.1, 0.2]], jnp.float32)
        one = jnp.ones((1, L), bool)
        assert np.asarray(bd.decide_rows(
            one, row, jnp.asarray([0]))).tolist() == [[False, True,
                                                       False, False]]


@pytest.mark.parametrize("rule,threshold,steps", (
    ("low_confidence_static", 0.9, 4), ("low_confidence_static", 0.9, 2),
    ("low_confidence_dynamic", 0.0145, 4)))
def test_depth_1_is_depth_0_pass_for_pass(rule, threshold, steps):
    """The same compiled step in both orders, prompts that end inside a
    block (and one shorter than a block): the same tokens, the same
    passes (block start, ids fed, decided before, decided, tokens) and
    the same rows bit for bit, steps that feed the same rows; every
    decision the host replays is the device's (`serve` holds them)."""
    from paddle_tpu.serving import tracing
    m = model(rule=rule, threshold=threshold, steps=steps)
    eng = shared_engine(rule=rule, threshold=threshold, steps=steps)
    prompts = prompts_of((9, 18, 23, 3), seed=10)
    new = (9, 6, 11, 7)
    got, recs = {}, {}
    for depth in (1, 0):
        had = len(eng.flight.records)
        tracing.enable()
        try:
            got[depth] = serve(eng, prompts, new, depth=depth)
        finally:
            tracing.disable()
        eng.flush_observability()
        recs[depth] = list(eng.flight.records)[had:]
    (a, pa), (b, pb) = got[1], got[0]
    assert a == b == [m.generate(p, n) for p, n in zip(prompts, new)]
    assert [[p[:5] for p in ps] for ps in pa] \
        == [[p[:5] for p in ps] for ps in pb]
    assert all((x[5] == y[5]).all() for ps, qs in zip(pa, pb)
               for x, y in zip(ps, qs))
    # every step but the first dispatched over an unread one; a request
    # that ended had one pass of L rows in flight, and nothing else
    # differs between the steps of the two orders
    assert [r["ahead"] for r in recs[1]] == [0] + [1] * (len(recs[1]) - 1)
    assert not any(r["ahead"] for r in recs[0])
    assert sum(r["diff_passes_wasted"] for r in recs[1]) == len(prompts)
    assert not any(r["diff_passes_wasted"] for r in recs[0])

    def fed(rs):
        return [(r["prefill_tokens"],
                 r["decode_tokens"] - L * r["diff_passes_wasted"],
                 r["diff_slot_passes"], r["diff_tokens_decided"],
                 r["diff_commits"]) for r in rs
                if r["prefill_tokens"] or r["diff_slot_passes"]]
    assert fed(recs[1]) == fed(recs[0])


@functools.lru_cache(maxsize=None)
def clocked_engine():
    clock = types.SimpleNamespace(now=0.0)
    return engine(model(), clock=lambda: clock.now), clock


@pytest.mark.parametrize("end", ("cancel", "expiry"))
def test_an_end_with_a_pass_in_flight_drains_first(end):
    """A cancel, and a plan that expires a request, read the pass in
    flight back first: at depth 1 the request ends with every pass that
    was dispatched for it, as at depth 0 one pass later, and the other
    request is served the same way in both orders."""
    eng, clock = clocked_engine()
    prompts = prompts_of((10, 17), seed=12)
    out = {}
    for depth in (1, 0):
        clock.now = 0.0
        eng._depth = depth
        passes = {}
        eng.on_block_pass = lambda req, start, fed, was, take, tokens: \
            passes.setdefault(req.req_id, []).append(
                (start, tuple(fed), tuple(was), tuple(take), tuple(tokens)))
        undo = held_to_the_device(eng)
        try:
            req = eng.submit(prompts[0], max_new_tokens=20, deadline=5.0)
            keep = eng.submit(prompts[1], max_new_tokens=12)
            # depth 1: the sixth pass is in flight when the fifth is read
            while len(passes.get(req.req_id, ())) < 5 + (1 - depth):
                eng.step()
            if depth:
                assert req.in_flight == 1 and eng._inflight is not None
            if end == "cancel":
                assert eng.cancel(req)
            else:
                clock.now = 6.0
                assert eng.step()
            assert req.state == ("cancelled" if end == "cancel"
                                 else "expired")
            assert req.in_flight == 0 and req.slot == -1
            assert len(passes[req.req_id]) == 6
            eng.run()
        finally:
            undo()
            eng._depth = 1
            eng.on_block_pass = None
        assert keep.state == "finished" and eng.kv.blocks_in_use == 0
        out[depth] = (list(req.output), passes[req.req_id],
                      list(keep.output), passes[keep.req_id])
    assert out[1] == out[0]
    assert out[1][2] == model().generate(prompts[1], 12)


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
    # a decided token equal to the mask id is not masked again: after
    # each step every decided id of a block is overwritten with it, in
    # the host's state and in the state the step in flight hands the
    # next one on the device; the device's decisions stay the host's
    import jax.numpy as jnp
    eng = shared_engine()
    for depth in (1, 0):
        seen = []
        eng._depth = depth
        undo = held_to_the_device(eng)
        eng.on_block_pass = lambda req, start, fed, was, take, tokens: \
            seen.append((fed, was))
        try:
            req = eng.submit(prompts_of((8,))[0], max_new_tokens=4)
            while eng.scheduler.has_work:
                eng.step()
                for i, d in enumerate(req.block_decided or ()):
                    if d:
                        req.block_tokens[i] = MASK
                ids, was, n = eng._prev_tokens
                eng._prev_tokens = (jnp.where(was, MASK, ids), was, n)
        finally:
            undo()
            eng._depth = 1
            eng.on_block_pass = None
        assert len(seen) == 4 and len(req.output) == 4
        assert [sum(was) for _, was in seen] == [0, 1, 2, 3]
        assert all(list(fed) == [MASK] * L for fed, _ in seen)


# ------------------------------------------- scheduler, batcher, refusals


def test_a_slot_advances_at_a_commit_and_only_then():
    """The slot holds what is COMMITTED: it grows by L when the host
    learns that a pass was its block's commit (the pass is read back),
    never at a denoise pass. At depth 1 the next block's first pass is
    dispatched before that, over the commit in flight, at the block's
    end; at depth 0 never."""
    m = model()
    eng = shared_engine()
    prompt = prompts_of((13,), seed=3)[0]
    for depth in (1, 0):
        eng._depth = depth
        starts, over = [], []

        def note(req, start, fed, was, take, tokens):
            assert int(eng.kv.slot_lens[req.slot]) == start \
                == req.block_start
            starts.append(start)

        launch = eng._launch_tick

        def launched(plan, trace_on):
            for slot, _, pos in plan.decode:
                held = int(eng.kv.slot_lens[slot])
                assert pos in (held, held + L)
                over.append(pos == held + L)
            return launch(plan, trace_on)

        eng.on_block_pass, eng._launch_tick = note, launched
        try:
            req = eng.submit(prompt, max_new_tokens=11)
            eng.run()
        finally:
            eng.on_block_pass = None
            vars(eng).pop("_launch_tick")
            eng._depth = 1
        # 13 -> 24: blocks at 12 (its first position the prompt's),
        # 16 and 20; the last one's commit is the pass too many
        assert sorted(set(starts)) == [12, 16, 20] and starts == \
            sorted(starts)
        assert sum(over) == (2 if depth else 0)
        assert req.output == m.generate(prompt, 11)
        assert eng.ahead_wasted_rows % L == 0


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
        # (drive the planned step by hand: what step() does at depth 0)
        flight = eng._launch_tick(plan, False)
        eng._land_tick(flight)
        for slot, tokens, _ in flight.got["groups"]:
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
    fields = ("diff_block_len", "diff_slot_passes", "diff_passes_wasted",
              "diff_rows_masked",
              "diff_tokens_decided", "diff_commits",
              "diff_blocks_committed", "kv_tokens_read_full",
              "attn_pairs_full", "moe_pairs_total", "moe_pairs_local",
              "moe_experts_hit", "attn_logits_useful",
              "attn_logits_issued")
    heads, layers = m.arch.num_heads, m.arch.num_layers
    for r in recs:
        assert set(fields) <= set(r)
        assert r["diff_block_len"] == L
        # decode_tokens counts the ROWS fed, a request's pass dispatched
        # after its end among them
        assert r["decode_tokens"] == \
            L * (r["diff_slot_passes"] + r["diff_passes_wasted"])
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
    # at depth 1 each request had one pass in flight when it ended
    assert eng._depth == 1
    assert sum(r["diff_passes_wasted"] for r in recs) == len(reqs)
    assert eng.ahead_wasted_rows == L * len(reqs) \
        == sum(r["ahead_wasted_rows"] for r in recs)
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
