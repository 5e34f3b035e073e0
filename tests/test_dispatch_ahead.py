"""The engine dispatches step k+1 before it reads step k back (ISSUE 36;
docs/SERVING.md "Dispatching ahead"): the pipelined order (`_depth` 1)
against the synchronous one (`_depth` 0, the same compiled step) on the
GPT engine, a tiny AFMoE (window tables), a tiny Olmo-Hybrid (recurrent
state) and a tiny SDAR (decoding by blocks, the decision on the device;
more in `tests/test_block_diffusion_serving.py`); a request that ends
on EOS one step before the host learns it; the rare paths that drain first (a preemption, a cancel, an
expiry); `sample_logits` as the drivers' sentinel loop reads it; the
drain at the end of `run()`; the one compile; the engines that stay at
depth 0. (The reader of `mixed_step.dispatch_ahead_pct` is tested beside
the other flight-field reader, `tests/test_packed_plan.py`.)"""
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_packed_plan as tp  # noqa: E402  (the tiny GPT and AFMoE)

VOCAB = tp.VOCAB


# ------------------------------------------------------------ engines
def gpt(kind="greedy", **kw):
    from paddle_tpu.serving.engine import ServingEngine
    kw.setdefault("sampling", tp._sampling(kind))
    return ServingEngine(tp._gpt(), max_slots=4, block_size=4,
                         max_seq_len=96, seed=7, cache_dtype="float32",
                         **kw)


def afmoe(kind="greedy"):
    return tp.build("afmoe_block", kind)


def olmo(kind="greedy"):
    from paddle_tpu.models import olmo_hybrid as oh
    from paddle_tpu.serving.engine import ServingEngine
    arch = oh.OlmoHybridArch(
        hidden_size=64, num_heads=4, head_dim=16, linear_heads=4,
        linear_key_dim=8, linear_value_dim=16, mlp_width=128,
        vocab_rows=96, layer_kinds=(oh.LINEAR,) * 3 + (oh.FULL,),
        max_positions=256, compute_dtype="float32", delta_chunk=8)
    return ServingEngine(oh.OlmoHybridForGeneration(arch, seed=3),
                         max_slots=3, block_size=4, num_blocks=80,
                         max_seq_len=128, token_budget=16, seed=7,
                         sampling=tp._sampling(kind),
                         cache_dtype="float32")


ENGINES = {"gpt": (gpt, VOCAB, 4), "afmoe": (afmoe, 96, 3),
           "olmo": (olmo, 96, 3)}


def prompts_of(lengths, vocab, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).tolist() for n in lengths]


def serve(eng, prompts, new_tokens, depth):
    """Serve `prompts` at `depth`, traced. -> (each request's tokens,
    the flight records)."""
    from paddle_tpu.serving import tracing
    eng._depth = depth
    reqs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, new_tokens)]
    tracing.enable()
    try:
        eng.run()
    finally:
        tracing.disable()
    assert all(r.state == "finished" for r in reqs)
    return [list(r.output) for r in reqs], list(eng.flight.records)


# --------------------------------------- the two orders, token for token
@pytest.mark.parametrize("kind", ["greedy", "sampling"])
@pytest.mark.parametrize("name", list(ENGINES))
def test_depth_1_is_depth_0_token_for_token(name, kind):
    """Mixed prefill and decode: a long prompt rides in chunks beside
    the decodes of the short ones. As many requests as slots, so that
    both orders make the same steps (a seeded sample depends on the
    step's key, and a request admitted into a slot that freed starts
    one step later when the end of the request before it is learned one
    step later: another key, the same distribution)."""
    build, vocab, slots = ENGINES[name]
    lengths = (41, 5, 19, 9)[:slots]
    new = (9, 14, 6, 11)[:slots]
    prompts = prompts_of(lengths, vocab)
    ahead, recs1 = serve(build(kind), prompts, new, 1)
    sync, recs0 = serve(build(kind), prompts, new, 0)
    assert ahead == sync
    assert [len(t) for t in ahead] == list(new)
    # every step but the first was dispatched over an unread one, and
    # fed the same tokens in the same order of steps
    assert [r["ahead"] for r in recs1] == [0] + [1] * (len(recs1) - 1)
    assert not any(r["ahead"] for r in recs0)
    fed = lambda recs: [(r["prefill_tokens"], r["decode_tokens"])  # noqa: E731
                        for r in recs]
    assert fed(recs1) == fed(recs0)
    assert any(p and d for p, d in fed(recs1))      # mixed steps


def test_more_requests_than_slots_greedy():
    """Greedy tokens do not depend on when the host learns them: with
    admissions that wait for a slot the steps differ, the tokens do
    not."""
    prompts = prompts_of((5, 17, 30, 3, 9, 12, 26), VOCAB)
    new = (9, 4, 12, 1, 7, 6, 10)
    a, b = gpt(), gpt()
    ahead, _ = serve(a, prompts, new, 1)
    sync, _ = serve(b, prompts, new, 0)
    assert ahead == sync
    assert a.steps_ahead > 20 and b.steps_ahead == 0
    assert a.ahead_wasted_rows == 0         # every end was by length
    assert a.step_compile_count() == b.step_compile_count() == 1
    assert a.kv.blocks_in_use == b.kv.blocks_in_use == 0


# ------------------------------------------------------- an end on EOS
def _eos_case(depth):
    """A request whose greedy answer holds its EOS mid-way, then a
    second one with the same prompt plus a tail."""
    probe = gpt()
    prompt = prompts_of((13,), VOCAB, seed=11)[0]
    answer = probe.generate_batch([prompt], max_new_tokens=12)[0]
    # an EOS whose wasted row FILLS a block: prompt + answer to the EOS
    # is a whole number of blocks, so a `finish` that went by the
    # slot's length would publish the block that holds the wasted row
    cut = next(n for n in range(3, 12) if (len(prompt) + n) % 4 == 0
               and answer[n - 1] not in answer[:n - 1])
    eos = answer[cut - 1]
    from paddle_tpu.serving import tracing
    eng = gpt(eos_token_id=eos, prefix_caching=True)
    eng._depth = depth
    tracing.enable()
    try:
        first = eng.submit(prompt, max_new_tokens=12)
        other = eng.submit(prompts_of((7,), VOCAB, seed=12)[0],
                           max_new_tokens=12)
        eng.run()
    finally:
        tracing.disable()
    return eng, prompt, answer[:cut], first, other


def test_eos_ends_one_step_late_and_the_row_is_dropped():
    eng, prompt, want, first, other = _eos_case(1)
    sync, _, _, first0, other0 = _eos_case(0)
    assert first.output == want == first0.output
    assert other.output == other0.output
    # the finishing slot was fed one row too many, and it was counted:
    # by the engine and in the record of the step that fed it
    ends = 1 + (len(other.output) < 12)
    assert eng.ahead_wasted_rows == ends and sync.ahead_wasted_rows == 0
    recs = list(eng.flight.records)
    assert sum(r["ahead_wasted_rows"] for r in recs) == ends
    assert not any(r["ahead_wasted_rows"] for r in sync.flight.records)
    assert eng.kv.blocks_in_use == sync.kv.blocks_in_use


def test_the_prefix_cache_holds_nothing_past_the_emitted_tokens():
    """`finish` publishes the tokens whose K/V was written AND emitted;
    a second request over the same prefix reads them and answers as an
    engine that never saw the first."""
    eng, prompt, want, first, _ = _eos_case(1)
    sync, *_ = _eos_case(0)
    seq = prompt + want
    for e in (eng, sync):
        # what the tree holds of the sequence: whole blocks of the
        # tokens before the last one emitted (which never fed a step
        # that was not wasted)
        nodes, _, got = e.prefix_cache._walk(seq, len(seq) // 4)
        assert got == (len(seq) - 1) // 4
    again = prompt + want[:-1] + prompts_of((6,), VOCAB, seed=13)[0]
    fresh = gpt(eos_token_id=eng.eos_token_id)
    expect = fresh.generate_batch([again], max_new_tokens=8)[0]
    for e in (eng, sync):
        req = e.submit(again, max_new_tokens=8)
        e.run()
        assert req.cache_hit_tokens >= (len(seq) - 1) // 4 * 4
        assert req.output == expect


# ------------------------------------------------ the paths that drain
def _step_until(eng, cond, limit=200):
    for _ in range(limit):
        if cond():
            return
        eng.step()
    raise AssertionError("the condition never held")


def test_a_pool_small_enough_to_preempt_drains_first():
    """The plan that has to preempt reads the step in flight back first,
    then runs as it always did: the same victims, the same tokens."""
    prompts = prompts_of((14, 15, 13, 12), VOCAB, seed=3)
    new = (20, 20, 20, 20)
    out = {}
    for depth in (1, 0):
        eng = gpt(num_blocks=19)
        out[depth] = serve(eng, prompts, new, depth)[0], \
            eng.scheduler.preemption_count, eng.steps_ahead
        assert eng.kv.blocks_in_use == 0
    assert out[1][0] == out[0][0]
    assert out[1][1] == out[0][1] > 0
    assert out[1][2] > 0


def test_a_cancel_with_a_token_in_flight_drains_first():
    out = {}
    for depth in (1, 0):
        eng = gpt()
        eng._depth = depth
        req, keep = (eng.submit(p, max_new_tokens=20)
                     for p in prompts_of((6, 9), VOCAB))
        _step_until(eng, lambda: len(req.output) >= 3)
        if depth:
            assert req.in_flight == 1 and eng._inflight is not None
        had = len(req.output)
        assert eng.cancel(req)
        # the token that was in flight was computed before the cancel
        assert len(req.output) == had + depth
        assert req.state == "cancelled" and req.slot == -1
        assert req.in_flight == 0 and eng._inflight is None
        eng.run()
        out[depth] = list(keep.output), list(req.output[:had])
        assert eng.kv.blocks_in_use == 0
    assert out[1] == out[0]


def test_an_expiry_with_a_token_in_flight_drains_first():
    clock = types.SimpleNamespace(now=0.0)
    out = {}
    for depth in (1, 0):
        clock.now = 0.0
        eng = gpt(clock=lambda: clock.now)
        eng._depth = depth
        req = eng.submit(prompts_of((6,), VOCAB)[0], max_new_tokens=20,
                         deadline=5.0)
        keep = eng.submit(prompts_of((9,), VOCAB)[0], max_new_tokens=9)
        _step_until(eng, lambda: len(req.output) >= 3)
        had = len(req.output)
        clock.now = 6.0
        assert eng.step()
        # drained, then expired as the synchronous engine expires it:
        # with every token that had been computed
        assert req.state == "expired" and req.in_flight == 0
        assert len(req.output) == had + depth
        eng.run()
        out[depth] = list(keep.output), list(req.output[:had])
        assert keep.state == "finished" and eng.kv.blocks_in_use == 0
    assert out[1] == out[0]


# -------------------------------- `sample_logits`, `has_work`, `run()`
@pytest.mark.parametrize("name", ["afmoe", "olmo"])
def test_sample_logits_is_the_row_of_the_token_just_emitted(name):
    """The drivers' sentinel loop, copied (`benchmarks/drivers/
    serve_frontend_afmoe.py` `sentinel_rows`): after a `step()` that
    grew the output, `sample_logits[slot]` is the row the new token is
    the largest of."""
    build, vocab, _ = ENGINES[name]
    e = build()
    assert e._depth == 1
    req = e.submit(prompts_of((21,), vocab)[0], max_new_tokens=9)
    rows, slot, calls = [], -1, 0
    while e.scheduler.has_work:
        n = len(req.output)
        e.step()
        calls += 1
        slot = req.slot if req.slot >= 0 else slot
        if len(req.output) > n:
            rows.append(np.asarray(e.sample_logits[slot]))
    assert len(rows) == len(req.output) == 9
    assert [int(r.argmax()) for r in rows] == req.output
    assert e._inflight is None and e.kv.blocks_in_use == 0
    # a token appears in the call AFTER the one that dispatched its step
    assert calls == e.steps_run + 1


def test_has_work_holds_while_a_step_is_in_flight_and_run_drains():
    eng = gpt()
    req = eng.submit(prompts_of((5,), VOCAB)[0], max_new_tokens=1)
    assert eng.step()                       # dispatched, not read
    assert eng._inflight is not None and not req.output
    assert req.in_flight == 1 and req.state == "decode"
    assert eng.scheduler.has_work
    assert eng.step()                       # nothing to plan: drains
    assert req.output and req.state == "finished"
    assert eng._inflight is None and not eng.scheduler.has_work
    assert not eng.step()                   # idle
    # `run(max_steps=)` hands back what the steps it ran computed
    req = eng.submit(prompts_of((5,), VOCAB)[0], max_new_tokens=8)
    assert eng.run(max_steps=3) == 3
    assert eng._inflight is None and len(req.output) == 3
    eng.run()
    assert len(req.output) == 8 and eng.step_compile_count() == 1


# --------------------------------------------------- decoding by blocks
def _diffusion():
    import test_block_diffusion_serving as bd
    return bd.engine(bd.model()), bd


def test_depth_1_is_depth_0_decoding_by_blocks():
    """A model that decodes by blocks runs at depth 1: a block's pass is
    planned one pass stale (its ids, decided positions and passes come
    from the device) and the device decides, so every step but the
    first is dispatched over an unread one. Mixed prefill and decode, as
    many requests as slots: the same tokens as the synchronous order,
    the same steps, and the rows a request's last pass fed after its end
    counted as wasted."""
    eng, bd = _diffusion()
    assert eng._ahead and eng._depth == 1
    prompts = prompts_of((41, 5, 19, 9), bd.VOCAB - 1)
    new = (9, 14, 6, 11)
    ahead, recs1 = serve(eng, prompts, new, 1)
    wasted = eng.ahead_wasted_rows
    sync, recs0 = serve(eng, prompts, new, 0)
    assert ahead == sync == [bd.model().generate(p, n)
                             for p, n in zip(prompts, new)]
    assert [r["ahead"] for r in recs1] == [0] + [1] * (len(recs1) - 1)
    recs0 = recs0[len(recs1):]
    assert not any(r["ahead"] for r in recs0)
    assert wasted == bd.L * len(prompts) == eng.ahead_wasted_rows
    fed = lambda recs: [  # noqa: E731
        (r["prefill_tokens"],
         r["decode_tokens"] - bd.L * r["diff_passes_wasted"])
        for r in recs if r["prefill_tokens"] or r["diff_slot_passes"]]
    assert fed(recs1) == fed(recs0)
    assert any(p and d for p, d in fed(recs1))      # mixed steps
    assert eng.step_compile_count() == 1


# ---------------------------------------- what stays at depth 0, and why
@pytest.mark.parametrize("case", [
    "draft_k", "repetition_penalty", "ticks_per_dispatch", "prefill_role"])
def test_engines_that_need_the_tokens_on_the_host_stay_at_depth_0(case):
    from paddle_tpu.serving import tracing
    from paddle_tpu.serving.batcher import SamplingConfig
    eng = gpt(**{
        "draft_k": dict(draft_k=2),
        "repetition_penalty": dict(
            sampling=SamplingConfig(repetition_penalty=1.3)),
        "ticks_per_dispatch": dict(ticks_per_dispatch=4),
        "prefill_role": dict(role="prefill")}[case])
    assert eng._depth == 0 and not eng._ahead
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in prompts_of((9, 20), VOCAB)]
    tracing.enable()
    try:
        for _ in range(60):
            if not eng.step():
                break
            # the synchronous order: nothing is ever left in flight
            assert eng._inflight is None
            assert not any(r.in_flight for r in reqs)
    finally:
        tracing.disable()
    eng.flush_observability()
    recs = list(eng.flight.records)
    assert recs and all(r["ahead"] == 0 for r in recs)
    assert all(r["ahead_wasted_rows"] == 0 for r in recs)
    assert eng.steps_ahead == 0


def test_the_depth_is_the_engine_s_own_business():
    """No keyword and no switch: what the engine can see of itself."""
    import inspect

    from paddle_tpu.serving.engine import ServingEngine
    params = inspect.signature(ServingEngine.__init__).parameters
    assert not [p for p in params if "ahead" in p or "depth" in p
                or "pipeline" in p]
    assert gpt()._depth == 1 and gpt(role="decode")._depth == 1
    assert gpt(kv_dtype="int8")._depth == 1
    assert gpt(sparse_blocks=4)._depth == 1
    assert _diffusion()[0]._depth == 1
