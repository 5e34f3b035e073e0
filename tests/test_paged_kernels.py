"""Block-table-native Pallas paged-attention kernels + int8 KV pools
(ISSUE 9).

The parity matrix: every serving attention shape (ragged prefill /
K-wide verify / K=1 decode) x pool dtype (fp32 / int8) runs the Pallas
kernel (interpret mode on the CPU mesh — the real scalar-prefetch +
block-table plumbing, not a shim) against the pure-XLA gather oracle;
the engine-level matrix covers (fp / int8) x (TP=1 / TP=2 CPU mesh)
including preemption, copy-on-write, prefix-cache adoption with
quantized scales, speculation, and the one-compile contract. The int8
path's bounded-divergence contract is enforced end-to-end by
tools/kv_smoke.py, wired in here.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForGeneration
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.profiler import metrics as pm
from paddle_tpu.serving.distributed import TPServingEngine
from paddle_tpu.serving.engine import STEP_FN_NAME, ServingEngine
from paddle_tpu.serving.kv_cache import PagedKVCache


@pytest.fixture
def _interpret_paged(monkeypatch):
    """Run the block-table-native kernels in interpret mode so the
    dispatch gate admits them on the CPU mesh."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    yield


@pytest.fixture
def _force_oracle(monkeypatch):
    """Pin the XLA gather path regardless of backend/interpret."""
    monkeypatch.setenv("PADDLE_TPU_PAGED_PALLAS", "0")
    yield


def _rand_pools(rng, NB, BS, H, Dh, quantized):
    if quantized:
        kp = rng.randint(-127, 128, (NB, BS, H, Dh)).astype(np.int8)
        vp = rng.randint(-127, 128, (NB, BS, H, Dh)).astype(np.int8)
        ks = (np.abs(rng.randn(NB, BS, H)) * 0.02 + 0.005).astype(
            np.float32)
        vs = (np.abs(rng.randn(NB, BS, H)) * 0.02 + 0.005).astype(
            np.float32)
        return kp, vp, ks, vs
    kp = rng.randn(NB, BS, H, Dh).astype(np.float32)
    vp = rng.randn(NB, BS, H, Dh).astype(np.float32)
    return kp, vp, None, None


# ------------------------------------------------- kernel-vs-oracle cells


class TestKernelOracleParity:
    NB, BS, H, Dh, S, MB = 11, 4, 3, 16, 4, 6

    def _setup(self, quantized, seed=0):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        kp, vp, ks, vs = _rand_pools(rng, self.NB, self.BS, self.H,
                                     self.Dh, quantized)
        bt = rng.randint(0, self.NB, (self.S, self.MB)).astype(np.int32)
        args = [jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt)]
        scales = [None if ks is None else jnp.asarray(ks),
                  None if vs is None else jnp.asarray(vs)]
        return rng, args, scales

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["fp32", "int8"])
    def test_ragged_matches_oracle(self, quantized, monkeypatch,
                                   _interpret_paged):
        import jax.numpy as jnp
        rng, (kp, vp, bt), (ks, vs) = self._setup(quantized)
        T = 9
        q = jnp.asarray(rng.randn(T, self.H, self.Dh).astype(np.float32))
        slots = jnp.asarray(rng.randint(-1, self.S, T).astype(np.int32))
        pos = jnp.asarray(rng.randint(
            0, self.MB * self.BS, T).astype(np.int32))
        got = fa.ragged_paged_attention(q, kp, vp, bt, slots, pos,
                                        ks, vs)
        monkeypatch.setenv("PADDLE_TPU_PAGED_PALLAS", "0")
        ref = fa.ragged_paged_attention(q, kp, vp, bt, slots, pos,
                                        ks, vs)
        valid = np.asarray(slots) >= 0        # padding rows are garbage
        np.testing.assert_allclose(np.asarray(got)[valid],
                                   np.asarray(ref)[valid],
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["fp32", "int8"])
    def test_verify_matches_oracle(self, quantized, monkeypatch,
                                   _interpret_paged):
        import jax.numpy as jnp
        rng, (kp, vp, bt), (ks, vs) = self._setup(quantized, seed=1)
        K = 3
        q = jnp.asarray(rng.randn(self.S, K, self.H,
                                  self.Dh).astype(np.float32))
        pos = jnp.asarray(np.sort(rng.randint(
            0, self.MB * self.BS, (self.S, K)), axis=1).astype(np.int32))
        slots = jnp.arange(self.S, dtype=jnp.int32)
        got = fa.verify_paged_attention(q, kp, vp, bt, slots, pos,
                                        ks, vs)
        monkeypatch.setenv("PADDLE_TPU_PAGED_PALLAS", "0")
        ref = fa.verify_paged_attention(q, kp, vp, bt, slots, pos,
                                        ks, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["fp32", "int8"])
    def test_decode_matches_oracle(self, quantized, monkeypatch,
                                   _interpret_paged):
        import jax.numpy as jnp
        rng, (kp, vp, bt), (ks, vs) = self._setup(quantized, seed=2)
        q = jnp.asarray(rng.randn(self.S, self.H,
                                  self.Dh).astype(np.float32))
        lens = jnp.asarray(rng.randint(
            1, self.MB * self.BS, self.S).astype(np.int32))
        got = fa.paged_attention(q, kp, vp, bt, lens, ks, vs)
        monkeypatch.setenv("PADDLE_TPU_PAGED_PALLAS", "0")
        ref = fa.paged_attention(q, kp, vp, bt, lens, ks, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_kill_switch_restores_oracle(self, _interpret_paged,
                                         _force_oracle):
        # with the env kill-switch the gate must refuse even under
        # interpret mode
        assert not pa.paged_pallas_enabled(128, 16)

    def test_gate_off_cpu_without_interpret(self):
        # plain CPU backend, no interpret: XLA oracle path
        assert not pa.paged_pallas_enabled(128, 16)


# ------------------------------------------------- the run-major kernel


class _RunShapes:
    """Flat-token layouts and pools of every dtype for the run kernel's
    tests (no tests of its own)."""
    NB, BS, H, Dh, S, MB, T = 41, 4, 2, 16, 6, 8, 24

    #: name -> [(slot, first position, tokens)] laid flat in order
    LAYOUTS = {
        "decodes_then_two_chunks": [(0, 17, 1), (1, 30, 1), (2, 9, 1),
                                    (3, 6, 9), (4, 2, 5)],
        "chunk_mid_block_across_blocks": [(1, 3, 14)],
        "chunk_from_zero": [(5, 0, 11), (0, 5, 1)],
        "padding_at_tail": [(2, 20, 1), (4, 0, 3)],
        "all_padding": [],
        "full_table": [(3, 8 * 4 - 6, 6), (0, 8 * 4 - 1, 1)],
        "same_slot_twice": [(1, 4, 3), (1, 12, 2), (2, 0, 1)],
        "whole_axis_one_run": [(0, 5, 24)],
    }

    def _layout(self, runs):
        slots = np.full(self.T, -1, np.int32)
        pos = np.zeros(self.T, np.int32)
        t = 0
        for slot, first, n in runs:
            slots[t:t + n] = slot
            pos[t:t + n] = np.arange(first, first + n)
            t += n
        return slots, pos

    def _pools(self, rng, dtype):
        import jax.numpy as jnp
        shape = (self.NB, self.BS, self.H, self.Dh)
        if dtype in ("int8", "fp8"):
            if dtype == "int8":
                kp, vp, ks, vs = _rand_pools(rng, *shape, True)
                kp, vp = jnp.asarray(kp), jnp.asarray(vp)
            else:
                kp, vp = (jnp.asarray(np.clip(
                    rng.randn(*shape) * 100, -440, 440).astype(
                        np.float32)).astype(jnp.float8_e4m3fn)
                    for _ in range(2))
                ks, vs = ((np.abs(rng.randn(*shape[:3])) * 0.02
                           + 0.005).astype(np.float32) for _ in range(2))
            return kp, vp, jnp.asarray(ks), jnp.asarray(vs), jnp.float32
        dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
        kp, vp, _, _ = _rand_pools(rng, *shape, False)
        return (jnp.asarray(kp).astype(dt), jnp.asarray(vp).astype(dt),
                None, None, dt)


class TestRunKernel(_RunShapes):
    """One walk per (slot, step): layouts the packer makes — decode
    runs of 1, prefill chunks, padding — against the gather oracle, and
    the work the walks do against what the contexts hold."""

    def _check(self, runs, dtype, kernel_name="paged_ragged", width=None,
               monkeypatch=None):
        import jax
        import jax.numpy as jnp
        rng = np.random.RandomState(3)
        kp, vp, ks, vs, qdt = self._pools(rng, dtype)
        bt = rng.randint(1, self.NB, (self.S, self.MB)).astype(np.int32)
        bt = jnp.asarray(bt[:, :width or self.MB])
        slots, pos = self._layout(runs)
        q = jnp.asarray(rng.randn(self.T, self.H, self.Dh).astype(
            np.float32)).astype(qdt)
        got = jax.jit(fa.ragged_paged_attention,
                      static_argnames=("kernel_name",))(
            q, kp, vp, bt, jnp.asarray(slots), jnp.asarray(pos), ks, vs,
            kernel_name=kernel_name)
        ref = fa.ragged_gather_reference(
            q, kp, vp, bt, jnp.asarray(slots), jnp.asarray(pos), ks, vs)
        got, ref = (np.asarray(x.astype(jnp.float32)) for x in (got, ref))
        valid = slots >= 0
        tol = {"bf16": 3e-2, "fp8": 1e-3}.get(dtype, 2e-5)
        np.testing.assert_allclose(got[valid], ref[valid], rtol=tol,
                                   atol=tol)
        # padding rows are never attended: finite, and zero
        assert not got[~valid].any()

    @pytest.mark.parametrize("layout", sorted(_RunShapes.LAYOUTS))
    def test_layouts_match_oracle(self, layout, _interpret_paged):
        self._check(self.LAYOUTS[layout], "fp32")

    @pytest.mark.parametrize("dtype", ["bf16", "int8", "fp8"])
    def test_pool_dtypes_match_oracle(self, dtype, _interpret_paged):
        self._check(self.LAYOUTS["decodes_then_two_chunks"], dtype)

    def test_sparse_name_with_shortened_table(self, _interpret_paged):
        # the sparse region's shape: runs of 1 over a table of 3 blocks,
        # positions compacted into its coordinates
        self._check([(0, 11, 1), (1, 4, 1), (2, 0, 1), (3, 9, 1)],
                    "fp32", kernel_name="paged_sparse", width=3)

    @pytest.mark.parametrize("layout", sorted(_RunShapes.LAYOUTS))
    def test_jitted_runs_are_the_layout(self, layout):
        import jax
        import jax.numpy as jnp
        runs = self.LAYOUTS[layout]
        slots, pos = self._layout(runs)
        n, start, length, slot, first = (np.asarray(x) for x in jax.jit(
            pa.paged_runs)(jnp.asarray(slots), jnp.asarray(pos)))
        starts = np.cumsum([0] + [c for _, _, c in runs])[:-1]
        want = [(t, c, s, p) for t, (s, p, c) in zip(starts, runs)]
        assert int(n[0]) == len(runs)
        assert list(zip(start, length, slot, first))[:len(runs)] == want
        assert not length[len(runs):].any()

    def test_work_follows_the_contexts(self, _interpret_paged):
        """`kv_blocks_walked == kv_blocks_needed` for a plan of unsplit
        runs (q tiles of a run share its walk), and nothing static in
        the kernel call grows with the table's width."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.serving.engine import _attention_work
        from paddle_tpu.serving.scheduler import Plan
        plan = Plan(
            decode=[(0, [7], 17), (1, [7], 30)],
            prefills=[(3, list(range(20)), 6, False),   # 3 q tiles
                      (4, list(range(5)), 0, True)],
            expired=[])
        work = _attention_work(plan, self.BS)
        assert work["kv_blocks_needed"] == 5 + 8 + 7 + 2
        assert work["kv_blocks_walked"] == work["kv_blocks_needed"]
        assert work["kv_tokens_read"] == 18 + 31 + 26 + 5

        def call(mb):
            rng = np.random.RandomState(0)
            kp = jnp.zeros((self.NB, self.BS, self.H, self.Dh))
            bt = jnp.asarray(rng.randint(1, self.NB, (self.S, mb)),
                             jnp.int32)
            q = jnp.zeros((self.T, self.H, self.Dh))
            z = jnp.zeros((self.T,), jnp.int32)
            jaxpr = jax.make_jaxpr(pa.ragged_attend)(q, kp, kp, bt, z, z)
            (eqn,) = [e for e in jaxpr.eqns
                      if e.primitive.name == "pallas_call"]
            gm = eqn.params["grid_mapping"]
            scratch = [str(v.aval) for v in
                       eqn.params["jaxpr"].invars[-gm.num_scratch_operands:]]
            return gm.grid, scratch, eqn.params["cost_estimate"]
        assert call(16) == call(256)


# ------------------------------------------------ stacked pools, in place


class TestStackedPools(_RunShapes):
    """`layer=l` on pools stacked over layers reads layer l's blocks
    where they lie (`pa.layer_blocks`: the flat view, the table offset)
    and must equal the call on the slice `pool[l]` — on the gather path
    and in the interpret-mode kernel, with the layer a traced scalar as
    a layer scan hands it."""
    L = 3
    RUNS = _RunShapes.LAYOUTS["decodes_then_two_chunks"]

    def _stacked(self, dtype, seed=5):
        """L layers of DIFFERENT contents, stacked (scales too)."""
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        layers = [self._pools(rng, dtype) for _ in range(self.L)]
        kp, vp, ks, vs = (
            None if layers[0][i] is None
            else jnp.stack([lay[i] for lay in layers]) for i in range(4))
        return rng, kp, vp, ks, vs, layers[0][4]

    @staticmethod
    def _path(kernel, monkeypatch):
        monkeypatch.setattr(pa, "_INTERPRET", kernel)

    @staticmethod
    def _at(pool, l):
        return None if pool is None else pool[l]

    def _ragged(self, kernel_name="paged_ragged"):
        import functools

        import jax
        return jax.jit(functools.partial(fa.ragged_paged_attention,
                                         kernel_name=kernel_name))

    @pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8", "fp8"])
    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["gather", "kernel"])
    def test_ragged_layer_equals_slice(self, kernel, dtype, monkeypatch):
        import jax.numpy as jnp
        self._path(kernel, monkeypatch)
        rng, kp, vp, ks, vs, qdt = self._stacked(dtype)
        bt = jnp.asarray(rng.randint(1, self.NB, (self.S, self.MB)),
                         jnp.int32)
        slots, pos = (jnp.asarray(x) for x in self._layout(self.RUNS))
        q = jnp.asarray(rng.randn(self.T, self.H, self.Dh).astype(
            np.float32)).astype(qdt)
        f = self._ragged()
        outs = []
        for l in range(self.L):
            got = f(q, kp, vp, bt, slots, pos, ks, vs,
                    layer=jnp.int32(l))
            ref = f(q, kp[l], vp[l], bt, slots, pos, self._at(ks, l),
                    self._at(vs, l))
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(ref.astype(jnp.float32)))
            outs.append(np.asarray(got.astype(jnp.float32)))
        # the layers do differ: a wrong offset could not pass
        assert not np.allclose(outs[0], outs[1])
        assert not np.allclose(outs[1], outs[2])

    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["gather", "kernel"])
    def test_sparse_name_shortened_table(self, kernel, monkeypatch):
        import jax.numpy as jnp
        self._path(kernel, monkeypatch)
        rng, kp, vp, ks, vs, qdt = self._stacked("fp32")
        bt = jnp.asarray(rng.randint(1, self.NB, (self.S, 3)), jnp.int32)
        slots, pos = (jnp.asarray(x) for x in self._layout(
            [(0, 11, 1), (1, 4, 1), (2, 0, 1), (3, 9, 1)]))
        q = jnp.asarray(rng.randn(self.T, self.H, self.Dh).astype(
            np.float32))
        f = self._ragged("paged_sparse")
        for l in range(self.L):
            np.testing.assert_array_equal(
                np.asarray(f(q, kp, vp, bt, slots, pos,
                             layer=jnp.int32(l))),
                np.asarray(f(q, kp[l], vp[l], bt, slots, pos)))

    @pytest.mark.parametrize("dtype", ["fp32", "int8"])
    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["gather", "kernel"])
    def test_verify_layer_equals_slice(self, kernel, dtype, monkeypatch):
        import jax
        import jax.numpy as jnp
        self._path(kernel, monkeypatch)
        rng, kp, vp, ks, vs, qdt = self._stacked(dtype, seed=6)
        K = 3
        bt = jnp.asarray(rng.randint(1, self.NB, (self.S, self.MB)),
                         jnp.int32)
        q = jnp.asarray(rng.randn(self.S, K, self.H, self.Dh).astype(
            np.float32))
        first = rng.randint(0, self.MB * self.BS - K, self.S)
        pos = jnp.asarray(first[:, None] + np.arange(K)[None],
                          jnp.int32)
        slots = jnp.arange(self.S, dtype=jnp.int32)
        f = jax.jit(fa.verify_paged_attention)
        for l in range(self.L):
            np.testing.assert_array_equal(
                np.asarray(f(q, kp, vp, bt, slots, pos, ks, vs,
                             layer=jnp.int32(l))),
                np.asarray(f(q, kp[l], vp[l], bt, slots, pos,
                             self._at(ks, l), self._at(vs, l))))

    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["gather", "kernel"])
    def test_null_block_is_the_layers_own(self, kernel, monkeypatch):
        """Table entry 0 of layer 1 is flat block NB, layer 1's own
        NULL block: garbage in layer 0's block 0 (flat block 0, where
        an offset left out would read) never reaches layer 1 — neither
        through the padding columns nor through a column that names
        block 0 inside the attended context."""
        import jax.numpy as jnp
        self._path(kernel, monkeypatch)
        rng, kp, vp, ks, vs, qdt = self._stacked("fp32", seed=7)
        kp = kp.at[0, 0].set(jnp.nan)
        vp = vp.at[0, 0].set(jnp.nan)
        bt = rng.randint(1, self.NB, (self.S, self.MB)).astype(np.int32)
        bt[:, 5:] = 0                   # NULL padding past the contexts
        bt[1, 2] = 0                    # and block 0 inside slot 1's
        slots, pos = (jnp.asarray(x) for x in self._layout(
            [(0, 17, 1), (1, 15, 1), (2, 9, 1), (3, 6, 9)]))
        q = jnp.asarray(rng.randn(self.T, self.H, self.Dh).astype(
            np.float32))
        f = self._ragged()
        got = np.asarray(f(q, kp, vp, jnp.asarray(bt), slots, pos,
                           layer=jnp.int32(1)))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(
            got, np.asarray(f(q, kp[1], vp[1], jnp.asarray(bt), slots,
                              pos)))
        # layer 0 does read its own block 0
        assert np.isnan(np.asarray(f(
            q, kp, vp, jnp.asarray(bt), slots, pos,
            layer=jnp.int32(0)))[1]).any()

    def test_kernel_call_is_one_layers(self):
        """The Mosaic call of a stacked pool is the call of one layer's
        pool: same grid, scratch and cost estimate (its `kv_tokens`
        count ONE layer's blocks, not L of them)."""
        import jax
        import jax.numpy as jnp
        kp = jnp.zeros((self.L, self.NB, self.BS, self.H, self.Dh))
        bt = jnp.ones((self.S, self.MB), jnp.int32)
        q = jnp.zeros((self.T, self.H, self.Dh))
        z = jnp.zeros((self.T,), jnp.int32)

        def call(pool, **kw):
            jaxpr = jax.make_jaxpr(
                lambda *a: pa.ragged_attend(*a, **kw))(q, pool, pool, bt,
                                                       z, z)
            (eqn,) = [e for e in jaxpr.eqns
                      if e.primitive.name == "pallas_call"]
            gm = eqn.params["grid_mapping"]
            scratch = [str(v.aval) for v in
                       eqn.params["jaxpr"].invars[-gm.num_scratch_operands:]]
            return gm.grid, scratch, eqn.params["cost_estimate"]
        assert call(kp, layer=2) == call(kp[2])


# ----------------------------------------------- a plane of heads a product


class TestPlanes:
    """The kernel reads a PLANE of P KV heads out of the fetched (key,
    head) rows at a time (`pa.plane_heads`: 1 for 32-bit pools, 2 for
    16-bit pools, all of them where neither read exists): the serve
    cells' head counts, every run length a step holds, against the
    gather oracle; and a row's bits whatever its run. Under
    `_INTERPRET` the CPU takes the same reads the chip does (the
    strided one, and the uint32 view bitcast back);
    `tools/tpu_tile_validate.py` holds them against the oracle on the
    chip at the cells' real shapes."""
    Dh, BS, MB, S, MAX_RUN = 16, 8, 23, 7, 128
    #: (slot, first position, tokens): every run length of the issue
    #: (one past `max_run` is cut into 128 + 1) and one of 20, so that
    #: every tile height (`pa.tile_tokens`: 1, 8, 32, 64, 128) is taken
    RUNS = [(0, 37, 1), (1, 20, 2), (2, 3, 7), (3, 10, 64),
            (4, 40, 128), (5, 5, 129), (6, 150, 20)]
    T = 352

    def _case(self, H, Gq, dtype, seed=0, L=None):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        NB = self.S * self.MB + 1
        dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
        shape = (NB, self.BS, H, self.Dh)
        if L is not None:
            shape = (L,) + shape
        kp, vp = (jnp.asarray(rng.randn(*shape).astype(np.float32))
                  .astype(dt) for _ in range(2))
        bt = 1 + rng.permutation(NB - 1).reshape(self.S, self.MB)
        slots = np.full(self.T, -1, np.int32)
        pos = np.zeros(self.T, np.int32)
        t = 0
        for slot, first, n in self.RUNS:
            slots[t:t + n] = slot
            pos[t:t + n] = np.arange(first, first + n)
            t += n
        q = jnp.asarray(rng.randn(self.T, H * Gq, self.Dh).astype(
            np.float32)).astype(dt)
        return (q, kp, vp, jnp.asarray(bt, jnp.int32),
                jnp.asarray(slots), jnp.asarray(pos))

    @staticmethod
    def _close(got, ref, slots, dtype):
        import jax.numpy as jnp
        got, ref = (np.asarray(x.astype(jnp.float32)) for x in (got, ref))
        valid = np.asarray(slots) >= 0
        tol = 3e-2 if dtype == "bf16" else 2e-5
        np.testing.assert_allclose(got[valid], ref[valid], rtol=tol,
                                   atol=tol)
        assert not got[~valid].any()

    @pytest.mark.parametrize("window", [None, 48], ids=["full", "window"])
    @pytest.mark.parametrize("dtype", ["fp32", "bf16"])
    @pytest.mark.parametrize("H,Gq", [(32, 1), (16, 1), (8, 6), (2, 1)])
    def test_cells_heads_and_run_lengths(self, H, Gq, dtype, window,
                                         _interpret_paged):
        import jax
        args = self._case(H, Gq, dtype)
        assert pa.plane_heads(H, args[1].dtype) == \
            (2 if dtype == "bf16" else 1)
        got = jax.jit(lambda *a: pa.ragged_attend(
            *a, window=window, max_run=self.MAX_RUN))(*args)
        ref = fa.ragged_gather_reference(*args, window=window)
        self._close(got, ref, args[4], dtype)

    @pytest.mark.parametrize("dtype", ["fp32", "bf16"])
    @pytest.mark.parametrize("H,Gq", [(16, 1), (8, 6)])
    def test_stacked_pools_traced_layer(self, H, Gq, dtype,
                                        _interpret_paged):
        import jax
        import jax.numpy as jnp
        q, kp, vp, bt, slots, pos = self._case(H, Gq, dtype, seed=1, L=3)
        f = jax.jit(lambda li: pa.ragged_attend(
            q, kp, vp, bt, slots, pos, max_run=self.MAX_RUN, layer=li))
        outs = []
        for li in range(3):
            got = f(jnp.int32(li))
            self._close(got, fa.ragged_gather_reference(
                q, kp[li], vp[li], bt, slots, pos), slots, dtype)
            outs.append(np.asarray(got.astype(jnp.float32)))
        assert not np.allclose(outs[0], outs[1])

    def test_odd_heads_in_16_bits_take_the_buffer_whole(
            self, _interpret_paged):
        import jax.numpy as jnp
        args = self._case(3, 2, "bf16", seed=2)
        assert pa.plane_heads(3, jnp.bfloat16) == 3
        assert pa.plane_heads(8, jnp.int8, quantized=True) == 8
        got = pa.ragged_attend(*args, max_run=self.MAX_RUN)
        self._close(got, fa.ragged_gather_reference(*args), args[4],
                    "bf16")

    @pytest.mark.parametrize("window", [None, 40], ids=["full", "window"])
    @pytest.mark.parametrize("dtype", ["fp32", "bf16"])
    def test_a_rows_bits_do_not_depend_on_its_run(self, dtype, window,
                                                  _interpret_paged):
        """One prompt of 100 tokens from position 30 of slot 2, its
        keys in the pool: fed as one run, as the power-of-two chunks a
        scheduler cuts (64, 32, 4; each in company of two decode runs),
        and token by token (100 runs of one: the flat order reversed,
        so no two tokens join), every (token, head) row leaves with the
        same bits."""
        import importlib.util
        import os

        import jax
        spec = importlib.util.spec_from_file_location(
            "tpu_tile_validate", os.path.join(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))), "tools",
                "tpu_tile_validate.py"))
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        H, Gq, N = 4, 2, 100
        q, kp, vp, bt, _, _ = self._case(H, Gq, dtype, seed=3)
        attend = jax.jit(lambda q, slots, pos: pa.ragged_attend(
            q, kp, vp, bt, slots, pos, window=window, max_run=self.MAX_RUN))
        whole, chunks, singles = tool.one_run_three_ways(
            attend, q[:N].astype("float32"), 2, 30, q.dtype,
            company=((0, 17), (5, 60)))
        assert np.abs(whole).max() > 0.1
        np.testing.assert_array_equal(whole, chunks)
        if dtype == "fp32":
            np.testing.assert_array_equal(whole, singles)
        else:
            # XLA:CPU rounds a bf16 product's float32 sums by its row
            # count (4 rows a token alone, 512 a tile; the kernel before
            # planes read the same two elements apart): on the chip the
            # MXU does not, and `tools/tpu_tile_validate.py` holds the
            # three feeds to the same bits there
            assert (whole != singles).mean() < 1e-3
            np.testing.assert_allclose(whole, singles, atol=1e-3, rtol=0)

    @pytest.mark.parametrize("window", [None, 48, 200])
    @pytest.mark.parametrize("H,Gq,P", [(32, 1, 2), (8, 6, 2), (16, 1, 1),
                                        (16, 1, 16)])
    def test_logits_issued_counts_the_kernels_tiles(self, H, Gq, P,
                                                    window):
        """`logits_issued` against the kernel's loops written out: a
        product of `tq * P * Gq` rows by `G * BS * P` columns a plane
        for every (q tile, fetched group) the causal and window rules
        let through; the useful pairs never exceed it, and are over a
        quarter of it at P = 2 on these runs."""
        BS, max_run = 16, 128
        G, TQ = pa.run_tiles(H, BS, 64, Gq, P)
        runs = [(1000, 1), (0, 1), (777, 300), (5, 64), (4095, 2)]
        want = pairs = 0
        for pos, n in runs:
            for cut in range(0, n, max_run):
                p0, m = pos + cut, min(max_run, n - cut)
                tq = next((t for t in (1, 8, 32, 64) if m <= t < TQ), TQ)
                nblk = (p0 + m - 1) // BS + 1
                lo = 0 if window is None \
                    else max(p0 - (window - 1), 0) // BS
                for g in range(lo // G, -(-nblk // G)):
                    base = g * G * BS
                    for off in range(0, m, tq):
                        live = base <= p0 + off + tq - 1
                        if window is not None:
                            live &= base + G * BS - 1 > p0 + off - window
                        want += live * (H // P) * (tq * P * Gq) * (
                            G * BS * P)
                pairs += sum(min(p + 1, window or p + 1)
                             for p in range(p0, p0 + m))
        got = pa.logits_issued(runs, (P, G, TQ), H, Gq, BS,
                               window=window, max_run=max_run)
        assert got == want
        assert pairs * H * Gq <= got
        if window is None and P == 2:
            assert pairs * H * Gq > 0.25 * got


def test_useful_logits_reader():
    """`kernels.paged_ragged_useful_logits_pct` on hand-made records,
    and on the parent's (neither field): nothing, and no exception."""
    import json
    import os
    import sys
    import types
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    from harness.files import load_module
    name = "kernels.paged_ragged_useful_logits_pct"
    read = load_module("layer_metrics", name).read
    logged = []

    def ctx(flight):
        return types.SimpleNamespace(flight=flight, log=logged.append)
    assert read(ctx([
        {"attn_logits_useful": 300, "attn_logits_issued": 1000},
        {"attn_logits_useful": 100, "attn_logits_issued": 600},
        {"kv_tokens_read": 7}])) == pytest.approx(25.0)
    assert "over 2 steps" in logged[-1]
    for flight in ([], [{"kv_blocks_walked": 4, "attn_pairs": 9}]):
        assert read(ctx(flight)) is None
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    serve = [w["name"] for w in manifest["workloads"]
             if w["name"].startswith("serve_")]
    assert entry["workloads"][:4] == serve[:4] and dict(
        entry, workloads=None) == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": None}


# --------------------------------------------------------- engine matrix


def _model(vocab=211):
    paddle.seed(1234)
    m = GPTForGeneration(vocab_size=vocab, hidden_size=32, num_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128,
                         compute_dtype="float32")
    m.eval()
    return m


def _prompts(lens, vocab=211, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, n).tolist() for n in lens]


def _engine(cls, m, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("cache_dtype", "float32")
    kw.setdefault("seed", 0)
    return cls(m, **kw)


class TestEnginePallasPath:
    """End-to-end: the compiled mixed step running through the
    interpret-mode Pallas kernels must be TOKEN-IDENTICAL to the XLA
    oracle path — fp32 exactly, int8 vs its own oracle-path twin."""

    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["fp32", "int8"])
    def test_engine_token_identical(self, kv_dtype, _interpret_paged):
        m = _model(vocab=97)
        prompts = _prompts((4, 7, 11), vocab=97)
        got = _engine(ServingEngine, m, max_slots=2, block_size=4,
                      max_seq_len=32, kv_dtype=kv_dtype).generate_batch(
            prompts, max_new_tokens=4)
        pa._INTERPRET = False
        try:
            ref = _engine(ServingEngine, m, max_slots=2, block_size=4,
                          max_seq_len=32,
                          kv_dtype=kv_dtype).generate_batch(
                prompts, max_new_tokens=4)
        finally:
            pa._INTERPRET = True
        assert got == ref

    def test_engine_speculative_pallas_identical(self, _interpret_paged):
        """The verify-shaped kernel carries the speculative region:
        draft_k>0 through Pallas must equal the non-speculative Pallas
        engine (greedy identity) — exercising the G=K grouped cell."""
        m = _model(vocab=97)
        prompts = _prompts((4, 9), vocab=97)
        base = _engine(ServingEngine, m, max_slots=2, block_size=4,
                       max_seq_len=32).generate_batch(
            prompts, max_new_tokens=5)
        spec = _engine(ServingEngine, m, max_slots=2, block_size=4,
                       max_seq_len=32, draft_k=2).generate_batch(
            prompts, max_new_tokens=5)
        assert spec == base

    @pytest.mark.parametrize("options", [
        {}, {"kv_dtype": "int8"}, {"draft_k": 2},
        {"sparse_blocks": 3, "kv_dtype": "fp8_e4m3"}],
        ids=["plain", "int8", "speculative", "sparse_fp8"])
    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["gather", "kernel"])
    def test_step_slices_no_layer_pool(self, kernel, options,
                                       monkeypatch):
        """The scanned step reads each layer's K/V blocks IN PLACE: no
        `dynamic_slice` / `slice` of the lowered step yields one
        layer's pool, scale pool or summary pool out of the stacked
        array (on a chip that slice is a copy of the whole layer's
        pool, every layer, every step)."""
        import re
        monkeypatch.setattr(pa, "_INTERPRET", kernel)
        eng = _engine(ServingEngine, _model(vocab=97), max_slots=2,
                      block_size=4, max_seq_len=32, **options)
        text = eng._step_fn._jitted.lower(
            *eng.example_step_args()).as_text()
        NB = eng.kv.num_blocks
        assert "stablehlo.while" in text and f"x{NB}x" in text
        sliced = [
            line.strip() for line in text.splitlines()
            if re.search(r"stablehlo\.(dynamic_)?slice", line)
            and re.search(rf"-> tensor<(1x)?{NB}x", line)]
        assert not sliced, sliced


class TestEngineInt8:
    """int8 pools on the XLA oracle path: deterministic quantization
    invariants the per-entry scales buy (see kv_cache.PagedKVCache)."""

    def test_single_compile_and_agreement(self):
        pm.enable()
        pm.REGISTRY.reset()
        try:
            # the kv_smoke workload (model seed 0): the >=99% greedy
            # agreement bound is a property of the real divergence
            # scale, but WHICH argmaxes sit close enough to flip is
            # seed-dependent on a random-init model — pin the seed the
            # documented contract was measured on
            paddle.seed(0)
            m = GPTForGeneration(vocab_size=211, hidden_size=32,
                                 num_layers=2, num_attention_heads=4,
                                 max_position_embeddings=128,
                                 compute_dtype="float32")
            m.eval()
            prompts = _prompts((3, 9, 17, 5, 12, 7, 21, 4))
            fp = _engine(ServingEngine, m).generate_batch(
                prompts, max_new_tokens=6)
            c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
            q8 = _engine(ServingEngine, m, kv_dtype="int8")
            out = q8.generate_batch(prompts, max_new_tokens=6)
            assert pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0 == 1
            total = sum(len(o) for o in fp)
            agree = sum(a == b for x, y in zip(fp, out)
                        for a, b in zip(x, y))
            assert agree / total >= 0.99
            assert q8.kv.blocks_in_use == 0
        finally:
            pm.REGISTRY.reset()
            pm.disable()

    def test_preemption_is_int8_deterministic(self):
        """Per-token quantization is append-order independent: a
        preempted + re-prefilled int8 request must emit exactly the
        tokens of an unpressured int8 run."""
        m = _model()
        prompts = _prompts((3, 9, 17, 5, 12, 7, 21, 4))
        calm = _engine(ServingEngine, m, kv_dtype="int8").generate_batch(
            prompts, max_new_tokens=6)
        tight = _engine(ServingEngine, m, kv_dtype="int8",
                        num_blocks=10)
        out = tight.generate_batch(prompts, max_new_tokens=6)
        assert tight.scheduler.preemption_count > 0
        assert out == calm

    def test_prefix_adoption_cow_carries_scales(self):
        """Prefix-cache adoption + CoW on int8 pools: shared-head
        requests must match the uncached int8 engine token for token
        (the CoW copy includes the scale columns), and the pool must
        drain clean."""
        m = _model()
        rng = np.random.RandomState(3)
        common = rng.randint(1, 211, 24).tolist()
        shared = [common + rng.randint(1, 211, 4).tolist()
                  for _ in range(4)]
        # fully-cached prompts (== common): the hit ends mid-block, so
        # admission must CoW the last shared block before re-feeding
        # its final token — the cell that exercises scale-carrying CoW
        shared.insert(2, list(common))
        shared.append(list(common))
        plain = _engine(ServingEngine, m, max_slots=2,
                        kv_dtype="int8").generate_batch(
            shared, max_new_tokens=6)
        cached = _engine(ServingEngine, m, max_slots=2,
                         kv_dtype="int8", prefix_caching=True)
        out = cached.generate_batch(shared, max_new_tokens=6)
        assert out == plain
        assert cached.prefix_cache.hit_tokens > 0
        assert cached.prefix_cache.cow_copies > 0
        cached.prefix_cache.evict_all()
        assert cached.kv.blocks_in_use == 0
        assert cached.kv.allocator.invariant_ok

    def test_speculative_int8_identity(self):
        m = _model()
        prompts = _prompts((3, 9, 17, 5))
        base = _engine(ServingEngine, m, kv_dtype="int8").generate_batch(
            prompts, max_new_tokens=8)
        spec = _engine(ServingEngine, m, kv_dtype="int8",
                       draft_k=3)
        out = spec.generate_batch(prompts, max_new_tokens=8)
        assert out == base
        assert spec.kv.blocks_in_use == 0

    def test_kv_dtype_validation(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedKVCache(2, 4, 8, num_blocks=4, block_size=4,
                         max_slots=2, max_blocks_per_slot=2,
                         kv_dtype="int4")

    def test_kv_bytes_per_token(self):
        fp = PagedKVCache(2, 4, 8, num_blocks=4, block_size=4,
                          max_slots=2, max_blocks_per_slot=2)
        q8 = PagedKVCache(2, 4, 8, num_blocks=4, block_size=4,
                          max_slots=2, max_blocks_per_slot=2,
                          kv_dtype="int8")
        # 2 (K,V) * L=2 * H=4 * (Dh=8 * itemsize [+ 4B scale/head])
        assert fp.kv_bytes_per_token == 2 * 2 * 4 * 8 * 4
        assert q8.kv_bytes_per_token == 2 * 2 * 4 * (8 + 4)
        assert q8.block_bytes == q8.kv_bytes_per_token * 4
        assert not fp.quantized and q8.quantized

    def test_cow_copies_scale_columns(self):
        import jax.numpy as jnp
        kv = PagedKVCache(1, 2, 4, num_blocks=6, block_size=2,
                          max_slots=2, max_blocks_per_slot=2,
                          kv_dtype="int8")
        kv.ensure_capacity(0, 2)
        src = kv.slot_blocks(0)[0]
        kv.k_pool = kv.k_pool.at[:, src].set(7)
        kv.k_scale = kv.k_scale.at[:, src].set(0.25)
        kv.v_scale = kv.v_scale.at[:, src].set(0.5)
        assert kv.cow_block(0, 0)
        dst = kv.slot_blocks(0)[0]
        assert dst != src
        np.testing.assert_array_equal(np.asarray(kv.k_pool[:, dst]), 7)
        np.testing.assert_array_equal(
            np.asarray(kv.k_scale[:, dst]), 0.25)
        np.testing.assert_array_equal(
            np.asarray(kv.v_scale[:, dst]), 0.5)
        assert kv.allocator.invariant_ok


class TestTPMatrix:
    """(fp / int8) x TP=2 vs TP=1 on the CPU virtual-device mesh:
    token identity, one compile, sharded scale pools."""

    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["fp32", "int8"])
    def test_tp2_matches_tp1(self, kv_dtype):
        pm.enable()
        pm.REGISTRY.reset()
        try:
            m = _model()
            prompts = _prompts((3, 9, 17, 5))
            ref = _engine(ServingEngine, m,
                          kv_dtype=kv_dtype).generate_batch(
                prompts, max_new_tokens=8)
            c0 = pm.JIT_COMPILES.labels(STEP_FN_NAME).value
            tp = _engine(TPServingEngine, m, tensor_parallel=2,
                         kv_dtype=kv_dtype)
            out = tp.generate_batch(prompts, max_new_tokens=8)
            assert out == ref
            assert pm.JIT_COMPILES.labels(STEP_FN_NAME).value - c0 == 1
            assert tp.kv.blocks_in_use == 0
            if kv_dtype == "int8":
                assert "mp" in str(tp.kv.k_scale.sharding.spec)
                assert "mp" in str(tp.kv.v_scale.sharding.spec)
        finally:
            pm.REGISTRY.reset()
            pm.disable()

    def test_tp2_int8_prefix_and_preemption(self):
        """The pressure cells: int8 TP=2 under preemption and under
        prefix adoption + CoW must match int8 TP=1."""
        m = _model()
        prompts = _prompts((3, 9, 17, 5, 12, 7, 21, 4))
        ref = _engine(ServingEngine, m, kv_dtype="int8",
                      num_blocks=10).generate_batch(
            prompts, max_new_tokens=6)
        tp = _engine(TPServingEngine, m, tensor_parallel=2,
                     kv_dtype="int8", num_blocks=10)
        assert tp.generate_batch(prompts, max_new_tokens=6) == ref
        assert tp.scheduler.preemption_count > 0

        rng = np.random.RandomState(3)
        common = rng.randint(1, 211, 24).tolist()
        shared = [common + rng.randint(1, 211, 4).tolist()
                  for _ in range(6)]
        plain = _engine(ServingEngine, m, max_slots=2,
                        kv_dtype="int8").generate_batch(
            shared, max_new_tokens=6)
        tpc = _engine(TPServingEngine, m, tensor_parallel=2,
                      max_slots=2, kv_dtype="int8",
                      prefix_caching=True)
        assert tpc.generate_batch(shared, max_new_tokens=6) == plain
        assert tpc.prefix_cache.hit_tokens > 0
        tpc.prefix_cache.evict_all()
        assert tpc.kv.blocks_in_use == 0
        assert tpc.kv.allocator.invariant_ok

    def test_tp2_penalties_match_tp1(self):
        """Logit processors under the TP mesh: the penalty history is
        a replicated extra step input (n_data grows by one), so the
        shard_map spec ordering is load-bearing — pin it with a
        TP=2-vs-TP=1 token-identity cell, penalties on, both dtypes."""
        from paddle_tpu.serving.batcher import SamplingConfig
        m = _model()
        prompts = _prompts((3, 9, 17, 5))
        sc = dict(repetition_penalty=1.5, presence_penalty=0.3,
                  penalty_window=32)
        for kv_dtype in (None, "int8"):
            ref = _engine(ServingEngine, m, kv_dtype=kv_dtype,
                          sampling=SamplingConfig(**sc)).generate_batch(
                prompts, max_new_tokens=8)
            tp = _engine(TPServingEngine, m, tensor_parallel=2,
                         kv_dtype=kv_dtype,
                         sampling=SamplingConfig(**sc))
            assert tp.generate_batch(prompts, max_new_tokens=8) == ref
            # penalties must actually bite vs the plain greedy run
            assert ref != _engine(ServingEngine, m,
                                  kv_dtype=kv_dtype).generate_batch(
                prompts, max_new_tokens=8)


# --------------------------------------------------------- smoke wiring


def test_kv_smoke_tool(capsys):
    """tools/kv_smoke.py is the tier-1 CI contract for the int8 pools:
    >= 1.9x capacity at equal HBM budget, >= 99% greedy agreement,
    zero leaked blocks/scales after evict_all, and the metric names
    (incl. paddle_tpu_serving_kv_bytes_per_token) in the dump."""
    import importlib.util
    import os

    pm.REGISTRY.reset()
    was = pm._enabled
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "kv_smoke.py")
    spec = importlib.util.spec_from_file_location("kv_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        rc = mod.main()
        out = capsys.readouterr().out
        assert rc == 0
        assert "paddle_tpu_serving_kv_bytes_per_token" in out
    finally:
        pm.REGISTRY.reset()
        if not was:
            pm.disable()
