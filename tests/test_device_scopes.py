"""The serving step names its own device time (PR 35): every operation
of `serving_mixed_step` under a scope of `tracing.DEVICE_SCOPES`, the
engine's own table of instruction -> scope, the benchmark's readers of
it, and the tracer's own cost (`trace_self`). On the CPU, at the tiny
sizes of the existing serving tests; times here say nothing."""
import gc
import json
import os
import re
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness import device_scopes, trace_reduce  # noqa: E402
from harness.files import load_module  # noqa: E402

from paddle_tpu.analysis import guards  # noqa: E402
from paddle_tpu.ops.pallas import interpret_mode  # noqa: E402
from paddle_tpu.profiler.xplane import (  # noqa: E402
    hlo_op_names, hlo_op_scopes)
from paddle_tpu.serving import tracing  # noqa: E402
from paddle_tpu.serving.engine import ServingEngine  # noqa: E402

SERVE_CELLS = ["serve_gpt3_1p3b_closed", "serve_gpt3_1p3b_closed_b",
               "serve_trinity_ep8_mixed_len",
               "serve_olmo_hybrid_mixed_len",
               "serve_sdar_30b_a3b_mixed_len"]
EVERY_STEP = ("plan_unpack", "embed", "attn_qkv", "kv_write", "attn_full",
              "attn_out", "mlp", "head", "sample")
#: the scopes a step program must set, beside those of every step
MUST = {
    "gpt": (),
    "afmoe": ("attn_window", "moe_router", "moe_experts", "moe_shared"),
    "olmo_hybrid": ("lin_proj", "lin_conv", "gated_delta", "lin_gate_out"),
    "sdar": ("moe_router", "moe_experts", "diffusion_confidence"),
}
PROMPTS = [[3, 14, 15, 9, 2, 6, 5], [7, 8, 1]]


def build(which, **kw):
    """The engine of a step program at the tiny size its own serving
    tests build."""
    if which == "gpt":
        from test_serving import _model
        return ServingEngine(_model(), max_slots=4, block_size=8,
                             max_seq_len=64, cache_dtype="float32", **kw)
    if which == "afmoe":
        from paddle_tpu.models import afmoe
        from test_afmoe_serving import small
        return ServingEngine(
            afmoe.AfmoeForGeneration(small(), seed=3), max_slots=3,
            block_size=4, num_blocks=80, max_seq_len=128, token_budget=16,
            cache_dtype="float32", **kw)
    if which == "olmo_hybrid":
        from test_olmo_hybrid_serving import engine, model
        return engine(model(), **kw)
    from test_block_diffusion_serving import engine, model
    return engine(model(), **kw)


@pytest.fixture(autouse=True)
def _tracing_off():
    tracing.disable()
    tracing.TRACER.reset()
    yield
    tracing.disable()
    tracing.TRACER.reset()


# ------------------------------------------- the engine's own table


COUNTED = ("fusion", "dot", "convolution", "custom-call", "scatter",
           "gather", "dynamic-update-slice")
INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(')


@pytest.mark.parametrize("which", list(MUST))
def test_every_operation_of_the_step_has_a_scope(which):
    with interpret_mode(), guards.sanitize(
            transfer_guard=None,
            budgets={"serving_mixed_step": 1}) as watchdog:
        eng = build(which)
        eng.generate_batch(PROMPTS, max_new_tokens=3)
        built = eng.step_compile_count()
        assert built == 1
        table = eng.step_op_scopes()
        assert eng.step_op_scopes() is table            # kept
        assert eng.step_compile_count() == built
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=14)
        for _ in range(10):
            assert eng.step()
        eng.run()
        assert eng.step_compile_count() == built
        assert sum(n for (name, _), n in watchdog._counts.items()
                   if name == "serving_mixed_step") == 1
        assert not watchdog.violations
        text = eng._step_fn._jitted.lower(
            *eng.example_step_args()).compile().as_text()
    named = set(table.values())
    assert named <= set(tracing.DEVICE_SCOPES) | {tracing.NO_SCOPE}
    for scope in EVERY_STEP + MUST[which]:
        assert scope in named, (which, scope)
    # the table is the running executable's: the same text gives it
    assert table == hlo_op_scopes(text, tracing.scope_of,
                                  tracing.NO_SCOPE)
    counted = [m.group(1) for m in map(INSTRUCTION.match,
                                       text.splitlines())
               if m and m.group(2) in COUNTED]
    unnamed = [n for n in counted if table[n] == tracing.NO_SCOPE]
    assert len(counted) > 50
    assert len(unnamed) <= 0.05 * len(counted), (which, unnamed[:10])
    # the table is reached through `serving.tracing`, by engine name
    gc.collect()            # engines of earlier tests: no table of theirs
    assert tracing.step_op_scopes()[eng.name] is table


def test_the_device_loop_s_own_operations_read_tick_control():
    with interpret_mode():
        eng = build("gpt", ticks_per_dispatch=4)
        eng.generate_batch(PROMPTS, max_new_tokens=5)
        scopes = set(eng.step_op_scopes().values())
    assert "tick_control" in scopes and "attn_qkv" in scopes
    assert eng.step_compile_count() == 1


def test_step_never_reaches_the_table(monkeypatch):
    eng = build("gpt")
    monkeypatch.setattr(
        eng, "step_op_scopes",
        lambda: pytest.fail("step_op_scopes() inside step()"))
    for on in (False, True):
        (tracing.enable if on else tracing.disable)()
        eng.generate_batch(PROMPTS, max_new_tokens=3)
    assert eng._op_scopes is None


def test_scope_of_takes_the_innermost_name():
    pre = "jit(serving_mixed_step)/jit(main)/"
    assert tracing.scope_of(pre + "attn_qkv/dot_general") == "attn_qkv"
    assert tracing.scope_of(
        pre + "mlp/moe_experts/jit(_sort)/sort") == "moe_experts"
    assert tracing.scope_of(
        pre + "tick_control/while/body/head/dot_general") == "head"
    assert tracing.scope_of(pre + "multihead/add") == tracing.NO_SCOPE
    assert tracing.scope_of("") == tracing.NO_SCOPE
    assert len(set(tracing.DEVICE_SCOPES)) == len(tracing.DEVICE_SCOPES)
    assert tracing.SUMMED_PHASES == (
        "engine.plan", "engine.pack", "engine.dispatch", "engine.emit",
        "engine.note")


HLO = '''HloModule jit_serving_mixed_step

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(serving_mixed_step)/mlp/mul" source_file="x.py"}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %copy.1 = f32[8]{0} copy(%a)
  %fusion.2 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1
  %add.4 = f32[8]{0} add(%fusion.2, %copy.1), metadata={op_name="jit(serving_mixed_step)/head/add"}
  %neg.5 = f32[8]{0} negate(%add.4), metadata={op_name="jit(serving_mixed_step)/neg"}
  ROOT %copy.6 = f32[8]{0} copy(%neg.5)
}
'''


def test_hlo_tables_on_a_hand_made_module():
    assert hlo_op_names(HLO) == {
        "mul.3": "jit(serving_mixed_step)/mlp/mul",
        "add.4": "jit(serving_mixed_step)/head/add",
        "neg.5": "jit(serving_mixed_step)/neg"}
    scopes = hlo_op_scopes(HLO, tracing.scope_of, tracing.NO_SCOPE)
    assert scopes["mul.3"] == "mlp" and scopes["add.4"] == "head"
    # made by the compiler: the computation it calls, else its user
    assert scopes["fusion.2"] == "mlp"
    # (its first user that HAS a scope of its own: `fusion.2` has none)
    assert scopes["copy.1"] == "head"
    # emitted by JAX outside every scope: stays unnamed, and so does
    # what only it uses
    assert scopes["neg.5"] == tracing.NO_SCOPE
    assert scopes["copy.6"] == tracing.NO_SCOPE


# ------------------------------------------ the benchmark's readers


def reduced(ops, steps=10, chips=1):
    return trace_reduce.Reduced(
        chips=chips, window_s=1.0, busy_s=0.8,
        ops={k: [s, steps] for k, s in ops.items()},
        modules={"jit_serving_mixed_step": [0.9, steps * chips]}
        if steps else {}, gaps=[])


#: seconds over ten steps of one chip, and the table of the engine
OPS = {"fusion.1": 0.010, "copy.2": 0.005, "paged_ragged.6": 0.040,
       "fusion.3": 0.020, "fusion.4": 0.002, "fusion.5": 0.003,
       "fusion.6": 0.001, "fusion.7": 0.004, "moe_experts.14": 0.030,
       "fusion.8": 0.006, "fusion.9": 0.002, "fusion.10": 0.001,
       "fusion.99": 0.001, "fusion.11": 0.0005, "gated_delta.9": 0.020,
       "fusion.12": 0.003, "fusion.13": 0.002, "fusion.14": 0.001,
       "fusion.15": 0.0015}
TABLE = {"fusion.1": "attn_qkv", "copy.2": "attn_out",
         "paged_ragged.6": "attn_full", "fusion.3": "mlp",
         "fusion.4": "moe_shared", "fusion.5": "head",
         "fusion.6": "sample", "fusion.7": "kv_write",
         "moe_experts.14": "moe_experts", "fusion.8": "moe_experts",
         "fusion.9": "moe_router", "fusion.10": tracing.NO_SCOPE,
         "fusion.11": "diffusion_confidence",
         "gated_delta.9": "gated_delta", "fusion.12": "lin_proj",
         "fusion.13": "lin_conv", "fusion.14": "lin_gate_out",
         "fusion.15": "gated_delta", "never.ran": "embed"}
#: ms a step each reader gives of them
EXPECT = {
    "model.attn_proj_ms_per_step": 1.5,
    "model.kv_write_ms_per_step": 0.4,
    "model.mlp_ms_per_step": 2.2,
    "model.head_sample_ms_per_step": 0.45,
    "moe.route_ms_per_step": 0.8,
    "linear_attn.mixer_xla_ms_per_step": 0.75,
}


def context(ops=OPS, flight=(), **kw):
    logged = []
    return types.SimpleNamespace(
        trace=reduced(ops, **kw), flight=list(flight),
        log=logged.append), logged


def test_split_by_scope_on_a_hand_made_slice():
    found = device_scopes.split(reduced(OPS).ops, TABLE, 10)
    total = sum(OPS.values()) * 100
    assert found.total_ms == pytest.approx(total)
    assert found.known == pytest.approx(1 - 0.001 / sum(OPS.values()))
    # a kernel's operation goes to `kernel` whatever its scope
    assert found.ms(device_scopes.KERNEL) == pytest.approx(9.0)
    assert found.ms("attn_full") == 0 and not found.has("attn_full")
    assert found.ms("moe_experts") == pytest.approx(0.6)
    assert found.ms("gated_delta") == pytest.approx(0.15)
    # unknown to the table and known under no scope: both unnamed
    assert found.ms(device_scopes.NONE) == pytest.approx(0.2)
    assert found.rows()[0][:2] == (device_scopes.KERNEL,
                                   pytest.approx(9.0))
    assert found.rows()[0][2][0] == ("paged_ragged.6",
                                     pytest.approx(4.0))
    # four chips: seconds are summed over them, steps are a chip's
    four = device_scopes.split(reduced(OPS, chips=4).ops, TABLE, 10, 4)
    assert four.ms("mlp") == pytest.approx(2.0 / 4)
    # a table of another executable, no table, no step, no operation
    other = {f"fusion.{i}": "mlp" for i in range(3, 9)}
    assert device_scopes.split(reduced(OPS).ops, other, 10) is None
    assert device_scopes.split(reduced(OPS).ops, {}, 10) is None
    assert device_scopes.split(reduced(OPS).ops, TABLE, 0) is None
    assert device_scopes.split({}, TABLE, 10) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_scope_readers(name, monkeypatch):
    read = load_module("layer_metrics", name).read
    monkeypatch.setattr(device_scopes, "tables", lambda: {"e": TABLE})
    ctx, _ = context()
    assert read(ctx) == pytest.approx(EXPECT[name])
    # the step sets none of the reader's scopes: nothing
    ctx, _ = context({"fusion.10": 0.5, "never.ran": 0.0})
    assert read(ctx) is None
    # under 90% known; no mixed step in the slice
    ctx, _ = context(dict(OPS, **{"fusion.99": 0.05}))
    assert read(ctx) is None
    ctx, _ = context(steps=0)
    assert read(ctx) is None
    # a program that gives no table (the parent), or no engine alive
    monkeypatch.setattr(device_scopes, "tables", dict)
    ctx, _ = context()
    assert read(ctx) is None


def test_named_busy_reader(monkeypatch):
    read = load_module("layer_metrics", "device.named_busy_pct").read
    # of two live engines the table that knows the slice is taken
    monkeypatch.setattr(device_scopes, "tables", lambda: {
        "other": {"fusion.1": "mlp", "x": "head"}, "e": TABLE})
    ctx, logged = context()
    total = sum(OPS.values())
    assert read(ctx) == pytest.approx(100 * (1 - 0.002 / total))
    assert "knows 99.3% of it" in logged[0]
    assert logged[1].split()[:3] == ["9.000", "ms", "kernel:"]
    assert "paged_ragged.6 4.000, moe_experts.14 3.000, " \
        "gated_delta.9 2.000" in logged[1]
    assert len(logged) == 1 + 16        # kernel, (none), 14 scopes
    monkeypatch.setattr(device_scopes, "tables", dict)
    ctx, logged = context()
    assert read(ctx) is None and not logged


def test_tables_come_from_the_live_engines():
    gc.collect()
    eng = build("gpt", name="scoped")
    eng._op_scopes = {"fusion.1": "mlp"}        # as if built
    assert device_scopes.tables()["scoped"] == {"fusion.1": "mlp"}
    del eng
    gc.collect()
    assert "scoped" not in device_scopes.tables()


def test_tracer_reader():
    read = load_module("layer_metrics",
                       "mixed_step.tracer_ms_per_step").read
    flight = [dict(trace_self=s * 1e-3, ph_plan=1e-4, ph_pack=8e-4,
                   ph_dispatch=5e-4, ph_wait=9e-3, ph_emit=1e-4,
                   ph_note=1e-4) for s in (0.05, 0.07, 0.30)]
    ctx, logged = context(flight=flight)
    assert read(ctx) == pytest.approx(0.07)
    assert "0.070 of 1.600 (host_ms_per_step): 1.530" in logged[0]
    ctx, logged = context(flight=[dict(ph_plan=1e-4, ph_note=1e-4)])
    assert read(ctx) is None and not logged
    ctx, _ = context()
    assert read(ctx) is None


def test_manifest_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    # the cells of PR 35, and the cell PR 37 appended to the lists
    keye = "serve_keye_vl2_30b_a3b_longctx"
    serve = SERVE_CELLS + [keye]
    want = {
        "device.named_busy_pct": ("%", "higher", "device", serve),
        "model.attn_proj_ms_per_step": ("ms/step", "lower", "model",
                                        serve),
        "model.kv_write_ms_per_step": ("ms/step", "lower", "model",
                                       serve),
        "model.mlp_ms_per_step": ("ms/step", "lower", "model",
                                  SERVE_CELLS[:4]),
        "model.head_sample_ms_per_step": ("ms/step", "lower", "model",
                                          serve),
        "moe.route_ms_per_step": ("ms/step", "lower", "moe",
                                  [SERVE_CELLS[2], SERVE_CELLS[4], keye]),
        "linear_attn.mixer_xla_ms_per_step": (
            "ms/step", "lower", "linear_attn", [SERVE_CELLS[3]]),
    }
    for name, (unit, better, layer, cells) in want.items():
        assert entries[name] == dict(
            name=name, unit=unit, better=better, source="device_trace",
            layer=layer, moves="serve_tokens_per_s", workloads=cells)
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert entries["mixed_step.tracer_ms_per_step"] == dict(
        name="mixed_step.tracer_ms_per_step", unit="ms", better="lower",
        source="program_counter", layer="mixed_step",
        moves="serve_tokens_per_s", workloads=serve)


# --------------------------------------------- the tracer's own cost


SUMMED = ("ph_plan", "ph_pack", "ph_dispatch", "ph_emit", "ph_note")


def documented(name, rows=None):
    """The flight fields a `benchmarks/FLIGHT_FIELDS*.md` lists in the
    first column of its table (`_full` after `x_window`: `x_full`)."""
    with open(os.path.join(ROOT, "benchmarks", name)) as f:
        cells = [line.split("|")[1] for line in f
                 if line.startswith("| `")]
    fields = []
    for cell in cells[rows] if rows else cells:
        for token in re.findall(r"`([\w]+)`", cell):
            if token.startswith("_"):
                token = fields[-1].rsplit("_", 1)[0] + token
            fields.append(token)
    return fields


#: what each block model's record must still carry, by the benchmark's
#: own documents (`FLIGHT_FIELDS.md` rows 5-7 are the block step's)
BLOCK_FIELDS = {
    "afmoe": [("FLIGHT_FIELDS.md", slice(4, 7))],
    "olmo_hybrid": [("FLIGHT_FIELDS.md", slice(6, 7)),
                    ("FLIGHT_FIELDS_LINEAR.md", None)],
    "sdar": [("FLIGHT_FIELDS.md", slice(6, 7)),
             ("FLIGHT_FIELDS_DIFFUSION.md", None)],
}
COMMON = ("ts", "dur", "prefill_tokens", "decode_tokens", "active_slots",
          "queue_depth", "kv_blocks_in_use", "kv_blocks_total",
          "preemptions", "blocks_imported", "compile_cache_size",
          "sparse_skip_ratio", "h2d_arrays", "h2d_bytes",
          "attn_logits_useful", "attn_logits_issued", "ph_wait") + SUMMED


@pytest.mark.parametrize("which", list(MUST))
def test_trace_self_on_every_traced_record(which):
    with interpret_mode():
        eng = build(which)
        eng.generate_batch([[7, 7, 3]], max_new_tokens=2)     # warm
        assert eng.flight.steps == 0
        tracing.enable()
        steps0 = eng.steps_run
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=6)
        # a record is made after the NEXT dispatch has launched
        eng.step()
        eng.step()
        assert eng.flight.steps == 1 and eng._deferred is not None
        eng.flush_observability()
        assert eng.flight.steps == 2 and eng._deferred is None
        eng.run()
        traced = eng.steps_run - steps0
        tracing.disable()
        eng.generate_batch([[5, 6]], max_new_tokens=2)
    recs = list(eng.flight.records)
    assert len(recs) == eng.flight.steps == traced
    want = list(COMMON)
    if which == "gpt":
        want += documented("FLIGHT_FIELDS.md", slice(3, 4))
    for name, rows in BLOCK_FIELDS.get(which, ()):
        want += documented(name, rows)
    assert len(set(want)) > (20 if which == "gpt" else 30)
    for r in recs:
        assert set(want) <= set(r), sorted(set(want) - set(r))
        assert all(isinstance(v, (int, float)) for v in r.values())
        assert 0.0 < r["trace_self"] <= sum(r[f] for f in SUMMED)
        assert sum(r[f] for f in SUMMED + ("ph_wait",)) == \
            pytest.approx(r["dur"], abs=1e-6)
    assert all("gap_before" in r for r in recs[1:])
    # every request's span is whole, in order, after the queue's flush
    for t in tracing.TRACER.traces():
        names = [e.name for e in t.events]
        assert names[0] == "enqueued" and names[-1] == "finished"
        assert t.monotone() and t.outcome == "finished"
    assert not tracing.TRACER._pending


def test_tracing_off_records_no_trace_self():
    eng = build("gpt")
    eng.generate_batch(PROMPTS, max_new_tokens=4)
    assert eng.flight.steps == 0 and eng._deferred is None
    assert not tracing.TRACER._pending and not tracing.TRACER.traces()


def test_queued_events_keep_their_order():
    tr = tracing.RequestTracer(clock=iter(range(100)).__next__)
    tracing.enable()
    tid = tr.mint()
    tr.event(tid, "enqueued")
    tr.queue(tid, "first_token")
    tr.queue(tid, "decode_step", tokens=1)
    assert len(tr._pending) == 2
    held = tr._traces[tid]
    assert [e.name for e in held.events] == ["enqueued"]
    tr.finish(tid, "finished")          # flushes what is queued first
    assert [e.name for e in held.events] == [
        "enqueued", "first_token", "decode_step", "finished"]
    assert held.monotone() and held.outcome == "finished"
    assert type(held.outcome) is str
    tr.queue(tid, "decode_step")        # after the terminal: dropped
    assert [e.name for e in tr.get(tid).events][-1] == "finished"
    tr.queue(tid, "decode_step")
    tr.reset()
    assert not tr._pending and tr.traces() == []


def test_the_marker_times_itself():
    ticks = iter(range(1000))
    ph = tracing.PhaseMarker(clock=lambda: next(ticks) * 1.0)
    ph.mark("engine.plan", 0)           # boundary 0, ends at 1
    ph.mark("engine.wait")              # boundary 2, ends at 3
    ph.mark("engine.emit")              # boundary 4, ends at 5
    ph.close()                          # 6
    assert ph.take() == {"ph_plan": 2.0, "ph_wait": 2.0, "ph_emit": 2.0}
    assert ph.take_own(tracing.SUMMED_PHASES) == 2.0
    assert ph.take_own(tracing.SUMMED_PHASES) == 0.0


def test_queue_and_flush_from_many_threads_lose_and_reorder_nothing():
    import threading
    tr = tracing.RequestTracer(capacity=64, max_events=10_000)
    tracing.enable()
    workers, events = 16, 400
    ids = [tr.mint() for _ in range(workers)]

    def work(tid):
        for i in range(events):
            # hot-path events queue; every eighth is recorded at once,
            # which flushes whatever ANY thread has queued
            (tr.event if i % 8 == 7 else tr.queue)(tid, "decode_step", n=i)
        tr.finish(tid, "finished")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in ids]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    for tid in ids:
        got = tr.get(tid)
        assert got.outcome == "finished" and got.dropped_events == 0
        assert [e.attrs["n"] for e in got.events[:-1]] == list(range(events))
    assert not tr._pending
