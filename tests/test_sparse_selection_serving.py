"""Attention through a learned selection on the normal serving path, at
a small size on the CPU: `models/keye_vl2.py` served by `ServingEngine`
— prefill in chunks, then decoding a token a step through the paged
cache and the indexer-key pool — against the plain reference's full
forward (`benchmarks/configs/keye_vl2_30b_a3b_pp8_serve_reference.py`):
the logits of every sample row and the members of its selection."""
import contextlib
import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_keye_sparse import (REF, TOPK, VOCAB, model, reference_cfg,
                              small)

from paddle_tpu import inference
from paddle_tpu.models import keye_vl2
from paddle_tpu.ops.pallas import interpret_mode
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving import tracing
from paddle_tpu.serving.engine import ServingEngine

#: sigma (rms of a row of logits against the reference's); the float32
#: engine reads under 1e-5
LIMIT = 1e-4


def engine(m, *, budget=32, slots=4, blocks=80, **kw):
    return ServingEngine(m, max_slots=slots, block_size=8,
                         num_blocks=blocks, max_seq_len=160,
                         token_budget=budget, cache_dtype="float32", **kw)


@functools.lru_cache(maxsize=None)
def shared_engine():
    """One engine (one compile) for the tests that only serve."""
    return engine(model())


def prompts_of(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).tolist() for n in lengths]


def serve(eng, prompts, new_tokens, *, interpret=False, between=None):
    """-> (each request's tokens; for each token the float32 row of
    logits it was the largest of; the selections of that row, bool
    [sparse layers, positions])."""
    new = new_tokens if isinstance(new_tokens, (list, tuple)) \
        else [new_tokens] * len(prompts)
    with (interpret_mode() if interpret else contextlib.nullcontext()):
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, new)]
        rows = [[] for _ in reqs]
        sels = [[] for _ in reqs]
        slots = [-1] * len(reqs)

        def collect():
            for i, r in enumerate(reqs):
                if len(r.output) > len(rows[i]):
                    rows[i].append(np.asarray(
                        eng.sample_logits[slots[i]]))
                    sels[i].append(np.asarray(
                        eng.sample_selection[:, slots[i]]))
                slots[i] = r.slot if r.slot >= 0 else slots[i]

        while eng.scheduler.has_work:
            for i, r in enumerate(reqs):
                slots[i] = r.slot if r.slot >= 0 else slots[i]
            eng.step()
            collect()
            if between is not None:
                between(eng, reqs)      # may read a step back (drain)
                collect()
    assert eng.kv.blocks_in_use == 0
    return [list(r.output) for r in reqs], rows, sels


def against_reference(m, prompt, tokens, rows, sels):
    """The rows of logits and their selections against the reference's
    full forward over prompt + tokens, teacher-forced. -> worst error
    in sigma."""
    N = len(tokens)
    ids = jnp.asarray(list(prompt) + list(tokens[:-1]), jnp.int32)
    cfg = reference_cfg(m.arch)
    # one compile a (length, rows), not one an operation
    z, _, _, keep = jax.jit(lambda w, i: REF.forward(w, i, cfg, last=N))(
        m.weights, ids)
    z, keep = np.asarray(z), np.asarray(keep)
    S = ids.shape[0]
    err = 0.0
    for p in range(N):
        err = max(err, float(np.sqrt(np.mean((rows[p] - z[p]) ** 2))
                             / z[p].std()))
        assert int(z[p].argmax()) == tokens[p]
        got = sels[p][:, :S]
        assert (got == keep[:, p]).all(), (p, np.flatnonzero(
            got[0] != keep[0, p]))
        assert not sels[p][:, S:].any()
    return err


# ------------------------------------------- tokens, rows, the selection


@pytest.mark.parametrize("interpret", (False, True),
                         ids=("fallback", "kernels"))
def test_prefill_in_chunks_then_decoding_is_the_reference(interpret):
    """Prompts of 3 to 6 x topk (chunks of 32 rows: the second chunk's
    rows lie past position topk and select), one under topk (dense),
    several to a step; every emitted token's row of logits and its
    selection in both layers are the reference's full forward's."""
    m = model()
    prompts = prompts_of((3 * TOPK + 5, 6 * TOPK, TOPK - 4, 70))
    new = (6, 5, 7, 4)
    eng = engine(m) if interpret else shared_engine()
    got, rows, sels = serve(eng, prompts, new, interpret=interpret)
    # (a token is the largest logit of the reference's teacher-forced
    # row: `against_reference`; the eager model's own greedy loop once)
    assert got[2][:3] == m.generate(prompts[2], 3)
    assert eng.step_compile_count() == 1
    assert eng._ahead and eng._depth == 1        # as Trinity and Olmo
    for p, toks, r, s in zip(prompts, got, rows, sels):
        assert against_reference(m, p, toks, r, s) < (
            1e-3 if interpret else LIMIT)


@pytest.mark.parametrize("budget", (8, 11, 24))
def test_chunk_boundaries_straddle_position_topk(budget):
    """Token budgets that cut the prompt so that chunks begin before
    and end after position topk = 16 (8: a boundary AT it; 11: the
    second chunk 11..21; 24: the first chunk holds it), and a cut that
    leaves an odd last token (a one-token run that is a prefill row)."""
    m = model()
    prompt = prompts_of((45,), seed=3)[0]
    eng = engine(m, budget=budget, slots=2)
    (toks,), (rows,), (sels,) = serve(eng, [prompt], 5)
    assert against_reference(m, prompt, toks, rows, sels) < LIMIT


def test_alone_in_company_and_after_a_preemption():
    """The same request alone, among others (other chunk cuts, other
    rows of the step, other pages) and preempted after three of its
    tokens (it prefills again from position 0 into other blocks): the
    same tokens, rows within rounding, the same selections."""
    m = model()
    eng = shared_engine()
    target, others = prompts_of((50,), seed=7)[0], prompts_of((19, 64, 9))
    (alone,), (ra,), (sa,) = serve(eng, [target], 8)
    outs, rc, sc = serve(eng, [others[0], target, others[1], others[2]],
                         8)
    assert outs[1] == alone
    for a, b in zip(ra, rc[1]):
        assert np.abs(a - b).max() < 1e-4
    assert all((a == b).all() for a, b in zip(sa, sc[1]))
    assert against_reference(m, target, outs[1], rc[1], sc[1]) < LIMIT

    hit = []

    def preempt(eng, reqs):
        r = reqs[0]
        if not hit and len(r.output) == 3:
            eng.drain()
            assert eng.scheduler._preempt_victim(set()) is r
            assert eng.kv.blocks_in_use == 0 and r.state == "queued"
            hit.append(len(r.output))

    (again,), (rp,), (sp,) = serve(eng, [target], 8, between=preempt)
    assert hit and again == alone
    assert eng.scheduler.preemption_count >= 1
    # the rows after the preemption are the rows without it
    assert len(rp) == len(ra) == 8
    assert against_reference(m, target, again, rp, sp) < LIMIT
    assert all((a == b).all() for a, b in zip(sa, sp))


def test_blocks_another_request_has_just_freed_leak_nothing():
    """A pool so small that the second request's blocks are the ones
    the first has just given back (LIFO), their K/V and indexer keys
    still in place: positions past a slot's length are no candidates,
    so the second request reads as it does on a fresh engine."""
    m = model()
    first, second = prompts_of((90, 60), seed=11)
    eng = engine(m, slots=1, blocks=14)
    (t1,), _, _ = serve(eng, [first], 6)
    used = np.asarray(eng.kv.idx_pools[0][1:13]).any()
    assert used                                     # keys were left
    (t2,), (r2,), (s2,) = serve(eng, [second], 6)
    assert against_reference(m, second, t2, r2, s2) < LIMIT


def test_block_pressure_preempts_and_resumes():
    m = model()
    prompts = prompts_of((60, 40, 50), seed=8)
    want, _, _ = serve(shared_engine(), prompts, 10)
    eng = engine(m, blocks=16)
    got, _, _ = serve(eng, prompts, 10)
    assert eng.scheduler.preemption_count > 0
    assert got == want


def test_the_two_orders_of_the_host_loop_give_the_same_tokens():
    """Depth 1 (dispatch ahead) and depth 0 on the same compiled step."""
    m = model()
    prompts = prompts_of((40, 23, 70), seed=5)
    eng = shared_engine()
    ahead, _, _ = serve(eng, prompts, 7)
    eng._depth = 0
    try:
        sync, _, _ = serve(eng, prompts, 7)
    finally:
        eng._depth = 1
    assert ahead == sync
    assert eng.step_compile_count() == 1


def test_the_selection_kernel_serves_what_the_jnp_form_does(monkeypatch):
    """The step with the exact top-k as the kernel `topk_select`
    (interpreted; every other operation as on the CPU) against the same
    step with its `jax.numpy` form: the same tokens, the same rows of
    logits and the same selections, bit for bit."""
    from paddle_tpu.ops.pallas import topk_select as ts
    prompts = prompts_of((3 * TOPK + 5, 70, TOPK - 4), seed=4)
    want = serve(shared_engine(), prompts, 6)
    traced, kernel = [], ts._topk_select
    monkeypatch.setattr(ts, "_INTERPRET", True)
    monkeypatch.setattr(ts, "_topk_select", lambda *a: traced.append(
        a[0].shape) or kernel(*a))
    got = serve(engine(model()), prompts, 6)
    # both selections of both layers, traced once
    assert len(traced) == 4
    assert got[0] == want[0]
    for a, b in zip(got[1:], want[1:]):
        for ra, rb in zip(a, b):
            assert all((x == y).all() for x, y in zip(ra, rb))


def test_a_patched_topk_mask_reaches_the_step(monkeypatch):
    """The step looks `topk_mask` up when it is traced (the benchmark's
    controls patch it): dense attention in its place keeps every
    candidate, and the selections show it."""
    from paddle_tpu.ops.pallas import topk_select as ts
    prompts = prompts_of((3 * TOPK + 5, 50), seed=6)
    _, _, want = serve(shared_engine(), prompts, 4)
    monkeypatch.setattr(ts, "topk_mask", lambda scores, k, cand: cand)
    _, _, got = serve(engine(model()), prompts, 4)
    members = [s.sum(-1).max() for sels in got for s in sels]
    assert max(members) > TOPK
    assert max(s.sum(-1).max() for sels in want for s in sels) == TOPK
    assert any((a != b).any() for x, y in zip(got, want)
               for a, b in zip(x, y))


def test_through_the_frontend_s_configuration():
    """`inference.create_serving_frontend` builds the same engine: no
    keyword of its own."""
    m = model()
    cfg = inference.Config().enable_continuous_batching(
        max_slots=2, block_size=8, num_blocks=40, max_seq_len=160,
        token_budget=32, cache_dtype="float32")
    fe = inference.create_serving_frontend(cfg, m, seed=0)
    prompt = prompts_of((50,), seed=2)[0]
    req = fe.engine.submit(prompt, max_new_tokens=2)
    fe.engine.run()
    assert list(req.output) == m.generate(prompt, 2)
    assert fe.engine.kv.sparse_layers == [0, 1]


# --------------------------------------------------------- what is refused


def test_what_is_refused_beside_a_selection():
    m = model()
    with pytest.raises(ValueError, match="prefix_caching.*cow_block"):
        engine(m, prefix_caching=True)
    for kw in (dict(draft_k=2), dict(sparse_blocks=4),
               dict(kv_dtype="int8"), dict(ticks_per_dispatch=4)):
        with pytest.raises(ValueError, match="GPT step only"):
            engine(m, **kw)

    class Mixed(keye_vl2.KeyeArch):
        @property
        def layer_kinds(self):
            return ("full", "sparse")

    arch = small()
    mixed = keye_vl2.KeyeModel(Mixed(**{
        f.name: getattr(arch, f.name)
        for f in arch.__dataclass_fields__.values()}), weights=m.weights)
    with pytest.raises(ValueError, match="beside full layers"):
        engine(mixed)


# --------------------------------------------------------- flight fields


def brute_work(groups, topk, max_run):
    dec = ctx = read = rows = scored = causal = kept = 0
    for start, n in groups:
        pieces = [(start + o, min(max_run, n - o))
                  for o in range(0, n, max_run)]
        for p0, m in pieces:
            for t in range(p0, p0 + m):
                scored += t + 1
                if m == 1:
                    dec, ctx = dec + 1, ctx + t + 1
                    read += min(topk, t + 1)
                else:
                    rows += 1
                    causal += t + 1
                    kept += min(topk, t + 1)
    return dict(sparse_rows_decode=dec, sparse_rows_chunk=rows,
                sparse_kv_tokens_context=ctx, sparse_kv_tokens_read=read,
                idx_keys_scored=scored, sparse_pairs_causal=causal,
                sparse_pairs_kept=kept)


def walked_by_the_step(plan, max_run, T, S, C):
    """Columns the kernel walks for the candidates the step itself makes
    of `plan` (`_SparseLayers.split` over its packed flat tokens): the
    chunk rows' and the one-token runs', each tile of rows as far as its
    largest candidate column + 1 (test_topk_select_kernel.brute_walk)."""
    from test_topk_select_kernel import brute_walk

    from paddle_tpu.ops.pallas import topk_select as ts
    from paddle_tpu.ops.pallas.paged_attention import paged_runs
    from paddle_tpu.serving.batcher import pack_step
    sp = pack_step(T, S, plan.decode, plan.prefills)
    valid = sp.slot_ids >= 0
    pos = jnp.asarray(np.where(valid, sp.positions, 0))
    layers = object.__new__(engine_mod._SparseLayers)
    layers.T, layers.S, layers.C = T, S, C
    split = layers.split(paged_runs(jnp.asarray(sp.slot_ids), pos, max_run),
                         jnp.zeros((S, 1), jnp.int32), pos,
                         jnp.asarray(valid), jnp.zeros((S,), jnp.int32))
    return sum(brute_walk(np.asarray(split[c]), ts.ROWS, ts.strip_of(C))
               for c in ("cand_c", "cand_d"))


@pytest.mark.parametrize("groups", (
    [(100, 1), (7, 1), (0, 32)], [(0, 33)], [(40, 65), (3, 2)],
    [(2047, 1), (2048, 1), (2040, 16)], [(10, 5), (500, 1)]))
def test_sparse_work_is_the_brute_count(groups):
    plan = engine_mod.Plan(
        decode=[(i, [1] * n, s) for i, (s, n) in enumerate(groups)
                if n == 1],
        prefills=[(8 + i, [1] * n, s, False)
                  for i, (s, n) in enumerate(groups) if n > 1],
        expired=[])
    T, S, C = 96, 16, 2560
    for topk, max_run in ((16, 32), (2048, 128)):
        got, walked = engine_mod._sparse_work(plan, topk, max_run, T, S, C)
        want = brute_work(engine_mod._plan_groups(plan), topk, max_run)
        want.update(idx_cols_walked=walked_by_the_step(plan, max_run, T,
                                                       S, C),
                    idx_cols_width=(T + S) * C)
        assert got == want, (topk, max_run)
        assert sum(n for _, n in walked) == want["sparse_rows_chunk"]


def test_a_decode_row_reads_at_most_topk_whatever_its_context():
    """The flight record of a traced run: a decode row reads
    min(topk, context) tokens of K/V, and the `_full` fields count what
    the run kernel walks (the chunk rows)."""
    m = model()
    eng = engine(m)
    tracing.TRACER.reset()
    tracing.enable()
    try:
        serve(eng, prompts_of((100, 5), seed=13), (6, 3))
        eng.flush_observability()
    finally:
        tracing.disable()
    recs = [r for r in eng.flight.records if "sparse_rows_decode" in r]
    assert recs
    dec = [r for r in recs if r["sparse_rows_decode"]]
    assert dec
    for r in recs:
        assert r["sparse_kv_tokens_read"] <= TOPK * r["sparse_rows_decode"]
        assert r["sparse_kv_tokens_read"] <= r["sparse_kv_tokens_context"]
        assert r["sparse_pairs_kept"] <= r["sparse_pairs_causal"]
        assert r["idx_pool_bytes"] == sum(
            int(p.size) * 4 for p in eng.kv.idx_pools)
        # only the chunk rows' groups are walked by the run kernel
        if not r["sparse_rows_chunk"]:
            assert r["kv_tokens_read_full"] == 0 == r["attn_pairs_full"]
        else:
            assert r["attn_pairs_full"] == r["sparse_pairs_causal"]
    late = max(dec, key=lambda r: r["sparse_kv_tokens_context"])
    assert late["sparse_kv_tokens_context"] > 100
    assert late["sparse_kv_tokens_read"] < late["sparse_kv_tokens_context"]
    tracing.TRACER.reset()


def test_every_operation_of_the_step_has_a_scope():
    """The four new scopes are in `DEVICE_SCOPES` and in the compiled
    step's own table; nothing of the step is left unnamed."""
    from test_device_scopes import COUNTED, INSTRUCTION
    with interpret_mode():
        eng = engine(model())
        eng.generate_batch(prompts_of((40, 9), seed=3), max_new_tokens=3)
        table = eng.step_op_scopes()
        text = eng._step_fn._jitted.lower(
            *eng.example_step_args()).compile().as_text()
    named = set(table.values())
    assert named <= set(tracing.DEVICE_SCOPES) | {tracing.NO_SCOPE}
    for scope in ("idx_proj", "idx_score", "idx_select", "attn_sparse",
                  "attn_full", "kv_write", "moe_router", "moe_experts"):
        assert scope in named, scope
    counted = [mt.group(1) for mt in map(INSTRUCTION.match,
                                         text.splitlines())
               if mt and mt.group(2) in COUNTED]
    unnamed = [n for n in counted if table[n] == tracing.NO_SCOPE]
    assert len(unnamed) <= 0.05 * len(counted), unnamed[:10]
    assert eng.step_compile_count() == 1
    del eng
    gc.collect()
