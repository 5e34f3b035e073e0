"""The delta-rule kernel `gated_delta` on the run mixes of a serving
step (interpreted, on the CPU): what it computes against the recurrence
token by token, what it leaves alone, that a request's bits do not
depend on what rides with it, and the host's count of the rows it
loads with the benchmark's reader of that count."""
import functools
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.files import load_module  # noqa: E402

from paddle_tpu.ops.pallas import gated_delta as gd  # noqa: E402
from paddle_tpu.ops.pallas import interpret_mode  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import paged_runs  # noqa: E402

H, DK, DV, S, T, C = 2, 8, 16, 32, 288, 64


def step(rng, lens, firsts, slots=None, T=T):
    """q, k, v, g, beta, runs of one step: run i of `lens[i]` tokens
    from position `firsts[i]` in slot `slots[i]` (default i), packed
    from flat token 0 on; the rest is padding."""
    import jax.numpy as jnp
    slot_ids, pos = np.full(T, -1, np.int32), np.zeros(T, np.int32)
    at = 0
    for i, (n, f) in enumerate(zip(lens, firsts)):
        slot_ids[at:at + n] = i if slots is None else slots[i]
        pos[at:at + n] = f + np.arange(n)
        at += n
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    f32 = lambda x: jnp.asarray(x, jnp.float32)                    # noqa
    return (f32(unit(rng.normal(size=(T, H, DK))) / np.sqrt(DK)),
            f32(unit(rng.normal(size=(T, H, DK)))),
            f32(rng.normal(size=(T, H, DV))),
            f32(-np.exp(rng.uniform(-3, 1, size=(T, H)))),
            f32(2 / (1 + np.exp(-rng.normal(size=(T, H))))),
            paged_runs(jnp.asarray(slot_ids), jnp.asarray(pos), None))


def states(rng):
    import jax.numpy as jnp
    return jnp.asarray(rng.normal(size=(S, H, DK, DV)), jnp.float32)


@functools.lru_cache(maxsize=None)
def kernel():
    import jax
    return jax.jit(functools.partial(gd.gated_delta_ragged, chunk=C))


def ragged(*args):
    with interpret_mode():
        return kernel()(*args)


MIXES = {
    "one_token_a_slot": ([1] * S, [5 + i for i in range(S)]),
    # the static worst case: as many partial chunks as there are slots
    "two_tokens_a_slot": ([2] * S, [i % 2 * 7 for i in range(S)]),
    "odd_lengths_fresh": ([1, 3, 64, 65, 129], [0] * 5),
    "odd_lengths_continued": ([1, 3, 64, 65, 129], [9, 64, 128, 64, 192]),
    "no_run": ([], []),
    "a_run_to_the_last_token": ([3, T - 3], [4, 0]),
}


@pytest.mark.parametrize("mix", MIXES)
def test_kernel_is_the_recurrence(mix):
    import jax
    rng = np.random.default_rng(7)
    lens, firsts = MIXES[mix]
    *args, runs = step(rng, lens, firsts)
    state = states(rng)
    assert int(runs[0][0]) == len(lens)
    o0, s0 = jax.jit(gd.gated_delta_scan)(*args, runs, state)
    o1, s1 = ragged(*args, runs, state)
    assert float(abs(o1 - o0).max()) < 1e-5
    assert float(abs(s1 - s0).max()) < 2e-5
    # padding tokens give zeros; slots with no run keep their state
    fed = sum(lens)
    assert float(abs(o1[fed:]).max()) == 0 if fed < T else True
    idle = np.arange(len(lens), S)
    assert (np.asarray(s1)[idle] == np.asarray(state)[idle]).all()
    if lens:
        assert (np.asarray(s1)[0] != np.asarray(state)[0]).any()


def test_chunk_tables_of_the_mixes():
    """What the kernel reads from SMEM: a chunk's first flat token and
    its rows; a chunk slot past the last real one holds no row."""
    lens, firsts = MIXES["odd_lengths_continued"]
    runs = step(np.random.default_rng(0), lens, firsts)[-1]
    ch = gd.delta_chunks(runs, T, S, chunk=C)
    n = int(ch["n"][0])
    assert n == 1 + 1 + 1 + 2 + 3
    assert ch["t0"].tolist()[:n] == [0, 1, 4, 68, 132, 133, 197, 261]
    assert ch["rows"].tolist()[:n] == [1, 3, 64, 64, 1, 64, 64, 1]
    assert not ch["rows"][n:].any() and not ch["t0"][n:].any()
    single = (np.asarray(ch["flags"]) & gd._SINGLE) != 0
    assert single.tolist()[:n] == [True, False, False, False, True,
                                   False, False, True]
    # a token's row in its chunk and its chunk's last token
    assert ch["pos"].tolist()[:5] == [0, 0, 1, 2, 0]
    assert ch["last"].tolist()[:5] == [0, 3, 3, 3, 67]
    assert ch["pos"][132] == 0 and ch["last"][131] == 131
    assert ch["last"][261] == 261 and ch["pos"][260] == 63


def test_alone_in_company_and_after_a_preemption_bit_for_bit():
    """One prompt of two chunks and a token: as ONE run alone; chunk by
    chunk, each behind the decode tokens of 13 other slots (a start
    that is no multiple of 8); and once more from position 0 in another
    slot after its first chunks were thrown away (a preemption)."""
    rng = np.random.default_rng(11)
    N, d = 2 * C + 1, 13
    q, k, v, g, beta, runs = step(rng, [N], [0], slots=[1])
    o_alone, s_alone = ragged(q, k, v, g, beta, runs, states(rng))

    def in_company(slot, cuts, state):
        out = []
        for at, n in cuts:
            *other, runs = step(rng, [1] * d + [n], [3] * d + [at],
                                slots=[s for s in range(S)
                                       if s != slot][:d] + [slot])
            fed = [x.at[d:d + n].set(w[at:at + n])
                   for x, w in zip(other, (q, k, v, g, beta))]
            o, state = ragged(*fed, runs, state)
            out.append(np.asarray(o[d:d + n]))
        return np.concatenate(out), state

    cuts = ((0, C), (C, C), (2 * C, 1))
    o_chunks, state = in_company(1, cuts, states(rng))
    assert (o_chunks == np.asarray(o_alone[:N])).all()
    assert (np.asarray(state[1]) == np.asarray(s_alone[1])).all()
    # preempted after two chunks; resumed in slot 5 from position 0, the
    # prompt cut another way (two chunks at once, then the token)
    _, state = in_company(1, cuts[:2], states(rng))
    o_again, state = in_company(5, ((0, 2 * C), (2 * C, 1)), state)
    assert (o_again == np.asarray(o_alone[:N])).all()
    assert (np.asarray(state[5]) == np.asarray(s_alone[1])).all()


def test_rows_walked_and_the_new_flight_fields():
    from paddle_tpu.serving.engine import _linear_work
    from paddle_tpu.serving.scheduler import Plan
    tile = C + gd._ALIGN
    assert [gd.rows_walked(n, C) for n in (1, 2, 64, 65, 66, 129, 130)] \
        == [1, tile, tile, tile + 1, 2 * tile, 2 * tile + 1, 3 * tile]
    # three decode tokens, a chunk of 128 from 64, the last 65 tokens
    # of a prompt and a prompt of one token
    plan = Plan([(0, [5], 9), (3, [5], 70), (4, [6], 200)],
                [(1, np.arange(128), 64, False),
                 (2, np.arange(65), 128, True),
                 (5, np.arange(1), 0, True)], ())
    work = _linear_work(plan, C)
    assert work == dict(
        lin_tokens=3 + 128 + 65 + 1, lin_runs=6, lin_single_runs=4,
        lin_chunks=3 + 2 + 2 + 1, lin_chunk_size=C,
        lin_rows_walked=3 + 2 * tile + (tile + 1) + 1)


def test_reader_of_the_rows_walked():
    reader = load_module("layer_metrics", "linear_attn.rows_walked_fill_pct")
    logged = []
    ctx = lambda flight: types.SimpleNamespace(            # noqa: E731
        flight=flight, log=logged.append)
    flight = [dict(lin_tokens=30, lin_runs=30, lin_single_runs=30,
                   lin_chunks=30, lin_chunk_size=64, lin_rows_walked=30),
              dict(lin_tokens=28 + 300, lin_runs=30, lin_single_runs=28,
                   lin_chunks=33, lin_chunk_size=64,
                   lin_rows_walked=28 + 5 * 72),
              # a step that fed no linear layer a token is not counted
              dict(lin_tokens=0, lin_runs=0, lin_rows_walked=0)]
    assert reader.read(ctx(flight)) == pytest.approx(
        100.0 * 358 / (30 + 388))
    assert "29.0 of 30.0 runs hold one token (2 steps)" in logged[-1]
    # a program that does not record the field (the parent): nothing
    old = [dict(lin_tokens=328, lin_runs=30, lin_chunks=33,
                lin_chunk_size=64)]
    assert reader.read(ctx(old)) is None
    assert reader.read(ctx([])) is None
    import json
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "linear_attn.rows_walked_fill_pct")
    assert entry == dict(
        name="linear_attn.rows_walked_fill_pct", unit="%", better="higher",
        source="program_counter", layer="linear_attn",
        moves="serve_tokens_per_s",
        workloads=["serve_olmo_hybrid_mixed_len"])


# --------------------------- the chip's compiler, with no chip attached


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_mosaic_takes_the_kernel_at_the_cell_s_shapes(one_chip,
                                                      monkeypatch):
    """The Olmo cell's call (30 heads of key 96 / value 192, 32 slots,
    512 tokens) through the TPU's own compilers: what interpret mode
    cannot refuse (a load of several rows from a start that is no
    multiple of 8, a broadcast of a row loaded at a dynamic start, the
    VMEM a head group's rows take). Nothing runs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.core import place
    monkeypatch.setattr(place, "on_tpu_backend", lambda: True)
    H, T, dk, dv, slots = 30, 512, 96, 192, 32

    def call(q, k, v, g, beta, slot_ids, pos, state):
        return gd.gated_delta_ragged(
            q, k, v, g, beta, paged_runs(slot_ids, pos, None), state)

    f32, i32 = jnp.float32, jnp.int32
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                ((T, H, dk), f32), ((T, H, dk), f32), ((T, H, dv), f32),
                ((T, H), f32), ((T, H), f32), ((T,), i32), ((T,), i32),
                ((slots, H, dk, dv), f32))]
    # an executable for a chip that is not attached cannot be read back
    # from the persistent cache: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(call, donate_argnums=(7,)).trace(
            *args).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert "gated_delta" in text
    # no array in the 64-row layout of every chunk slot is made by XLA
    assert f"f32[{H},{gd.max_chunks(T, slots)},{gd.CHUNK}" not in text
