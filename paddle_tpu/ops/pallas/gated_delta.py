"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) over the runs
of one ragged serving step.

A run is a slot's consecutive tokens of this step (`paged_attention.
paged_runs` WITHOUT a `max_run` cut: one run a slot); every slot owns a
float32 state `S [dk, dv]` a head. Per token, head by head:

    S_t = alpha_t S_{t-1} + k_t u_t^T,   alpha_t = exp(g_t)
    u_t = beta_t (v_t - (alpha_t S_{t-1})^T k_t),   o_t = S_t^T q_t

A run whose first position is 0 starts from the zero state, any other
from its slot's stored state; slots with no run keep theirs.

Three forms of the same function:

- `gated_delta_scan`: the recurrence, token by token (the oracle);
- `gated_delta_chunked`: the exact chunkwise form in `jax.numpy`, the
  CPU fallback. For a chunk of C tokens with incoming state S_0,
  gamma_r = sum_{i<=r} g_i, A_ri = beta_r e^{gamma_r - gamma_i} k_r.k_i
  (i < r), Tm = (I + A)^{-1}:
      U   = Tm diag(beta) (V - e^gamma * (K S_0))
      O   = e^gamma * (Q S_0) + (tril(Q K^T) * e^{gamma_r - gamma_i}) U
      S_C = e^{gamma_C} S_0 + K^T (e^{gamma_C - gamma} * U)
  every exponent <= 0; padding rows take beta = 0, g = 0, k = 0;
- `gated_delta_ragged`: the same with the pass that carries S through a
  run's chunks as the Mosaic kernel `gated_delta`: grid (head group,
  chunk), a run's state read from HBM once, held in VMEM across its
  chunks and written once. The products that do not depend on S_0 (`A`,
  the masked decayed `Q K^T`, the decays) are made by XLA for all
  chunks at once (`_prologue`); the kernel solves `(I + A) Tm = I` by
  forward substitution, only for the chunks that hold more than one
  token; a chunk of ONE token (a decode run, or a run's last odd token)
  takes the recurrence itself, as vector operations on the state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_INTERPRET = False

# a chunk's flags, as the kernel reads them from SMEM
_FIRST, _FRESH, _LAST, _SINGLE, _REAL = 1, 2, 4, 8, 16

_HI = jax.lax.Precision.HIGHEST


def gated_delta_enabled() -> bool:
    """The Mosaic kernel on a TPU backend (or interpreted, in tests);
    the chunked `jax.numpy` form elsewhere."""
    if _INTERPRET:
        return True
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


def max_chunks(T, max_slots, chunk=CHUNK):
    """Most chunks the runs of one step can make: a run of n tokens
    makes ceil(n / chunk), and a slot has at most one run."""
    return -(-T // chunk) + min(int(max_slots), T)


def token_runs(runs, T):
    """Per flat token of a step: (run index, valid, offset in its run,
    whether its run starts at position 0)."""
    _, start, length, _, first = runs
    t = jnp.arange(T, dtype=jnp.int32)
    r = jnp.clip(jnp.searchsorted(start, t, side="right") - 1, 0, T - 1)
    off = t - start[r]
    valid = (off >= 0) & (off < length[r])
    return r, valid, off, first[r] == 0


def delta_chunks(runs, T, max_slots, chunk=CHUNK):
    """The chunks of a step's runs, fixed shapes. -> dict: `tok [NC, C]`
    the flat token a chunk row holds (T = padding), `slot [NC]`, `flags
    [NC]` (first / fresh / last / single token / real), `n [1]`, and the
    way back: `at [T]` the row of `[NC * C]` a flat token lies at."""
    C = int(chunk)
    NC = max_chunks(T, max_slots, C)
    n_runs, start, length, slot, first = runs
    nch = -(-length // C)
    cend = jnp.cumsum(nch)
    n = cend[-1]
    c = jnp.arange(NC, dtype=jnp.int32)
    r = jnp.clip(jnp.searchsorted(cend, c, side="right"), 0, T - 1)
    j = c - (cend[r] - nch[r])
    real = c < n
    rows = jnp.where(real, jnp.clip(length[r] - j * C, 0, C), 0)
    i = jnp.arange(C, dtype=jnp.int32)
    tok = jnp.where(i[None, :] < rows[:, None],
                    (start[r] + j * C)[:, None] + i[None, :], T)
    is_first = real & (j == 0)
    flags = (jnp.where(is_first, _FIRST, 0)
             | jnp.where(is_first & (first[r] == 0), _FRESH, 0)
             | jnp.where(real & (j == nch[r] - 1), _LAST, 0)
             | jnp.where(rows == 1, _SINGLE, 0)
             | jnp.where(real, _REAL, 0))
    # chunks past the last real one stay on its slot (the kernel's
    # state block then never moves); a step with no run at all walks
    # chunk 0 as an all-padding run of slot 0: an identity
    last = slot[jnp.maximum(n_runs[0] - 1, 0)]
    cslot = jnp.clip(jnp.where(real, slot[r], last), 0, max_slots - 1)
    empty = n == 0
    flags = jnp.where(empty & (c == 0), _FIRST | _LAST | _REAL, flags)
    cslot = jnp.where(empty, 0, cslot)
    tr, valid, off, _ = token_runs(runs, T)
    at = jnp.where(valid,
                   (cend[tr] - nch[tr] + off // C) * C + off % C, 0)
    return dict(tok=tok, slot=cslot.astype(jnp.int32),
                flags=flags.astype(jnp.int32), n=n.reshape(1), at=at,
                valid=valid)


def _prologue(q, k, v, g, beta, chunks, solve):
    """Everything of the chunkwise form that does not depend on the
    incoming state, for all chunks and heads at once, float32, head
    major: K, Q `[H, NC, C, dk]`, their transposes, V `[H, NC, C, dv]`,
    the masked decayed `Q K^T` `[H, NC, C, C]`, `scal [H, NC, C, 3]` =
    (e^gamma, e^{gamma_C - gamma}, beta), `dec [H, NC]` = e^{gamma_C},
    and the triangular system: `Tm = (I + A)^{-1}` where `solve` (XLA's
    batched solver: the fallback), else `AT = A^T` for the kernel, which
    solves it only for the chunks that hold more than one token (on the
    chip XLA's solver took 2.6 ms a layer for 1200 systems of 64 rows,
    a third of the step)."""
    tok = chunks["tok"]                                     # [NC, C]
    f32 = jnp.float32
    exact = _HI if q.dtype == f32 else None

    def rows(x):                       # [T, H, d] -> [H, NC, C, d]
        x = jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])
        return jnp.moveaxis(x[tok], 2, 0)

    K, Q, V = rows(k), rows(q), rows(v)
    G = rows(g.astype(f32)[..., None])[..., 0]              # [H, NC, C]
    B = rows(beta.astype(f32)[..., None])[..., 0]
    gam = jnp.cumsum(G, axis=-1)
    C = tok.shape[1]
    low = jnp.tril(jnp.ones((C, C), bool), -1)
    diff = gam[..., :, None] - gam[..., None, :]            # r, i
    dec_lo = jnp.exp(jnp.where(low, diff, -jnp.inf))        # i < r
    kk = jnp.einsum("hnrd,hnid->hnri", K, K, precision=exact,
                    preferred_element_type=f32)
    A = B[..., :, None] * dec_lo * kk
    eye = jnp.eye(C, dtype=f32)
    if solve:
        system = dict(Tm=jax.lax.linalg.triangular_solve(
            A + eye, jnp.broadcast_to(eye, A.shape), left_side=True,
            lower=True, unit_diagonal=True))
    else:
        system = dict(AT=jnp.swapaxes(A, -1, -2))
    qk = jnp.einsum("hnrd,hnid->hnri", Q, K, precision=exact,
                    preferred_element_type=f32)
    P = (dec_lo + eye) * qk
    scal = jnp.stack([jnp.exp(gam),
                      jnp.exp(gam[..., -1:] - gam), B], axis=-1)
    dec = jnp.exp(gam[..., -1])                             # [H, NC]
    K, Q, V = K.astype(f32), Q.astype(f32), V.astype(f32)
    return dict(K=K, Q=Q, KT=jnp.swapaxes(K, -1, -2),
                QT=jnp.swapaxes(Q, -1, -2), V=V, P=P, scal=scal, dec=dec,
                **system)


def _chunk_math(K, Q, KT, V, Tm, P, scal, dec, S0):
    """One chunk of one head: -> (O [C, dv], S_C [dk, dv]). `dec` is
    e^{gamma_C}, a scalar."""
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
    eg, d, bt = scal[:, 0:1], scal[:, 1:2], scal[:, 2:3]
    U = dot(Tm, bt * (V - eg * dot(K, S0)))
    O = eg * dot(Q, S0) + dot(P, U)
    return O, dec * S0 + dot(KT, d * U)


def _back(O, chunks, dtype):
    """O [H, NC, C, dv] -> o [T, H, dv] at the flat tokens."""
    H, NC, C, dv = O.shape
    o = jnp.moveaxis(O.reshape(H, NC * C, dv)[:, chunks["at"]], 0, 1)
    return jnp.where(chunks["valid"][:, None, None], o, 0).astype(dtype)


def gated_delta_chunked(q, k, v, g, beta, runs, state, *, chunk=CHUNK,
                        chunks=None):
    """The chunkwise form in `jax.numpy`: the fallback off the TPU and
    the kernel's oracle. Arguments and result as `gated_delta_ragged`."""
    T = q.shape[0]
    chunks = chunks or delta_chunks(runs, T, state.shape[0], chunk)
    pre = _prologue(q, k, v, g, beta, chunks, solve=True)
    heads = jax.vmap(_chunk_math)

    def walk(carry, x):
        state, cur = carry
        slot, fl, *a = x
        on = lambda bit: (fl & bit) != 0                    # noqa: E731
        cur = jnp.where(on(_FIRST), jnp.where(on(_FRESH), 0.0,
                                              state[slot]), cur)
        O, nxt = heads(*a, cur)
        cur = jnp.where(on(_REAL), nxt, cur)
        state = jnp.where(on(_LAST), state.at[slot].set(cur), state)
        return (state, cur), O

    per_chunk = [jnp.moveaxis(pre[n], 1, 0) for n in
                 ("K", "Q", "KT", "V", "Tm", "P", "scal", "dec")]
    (state, _), O = jax.lax.scan(
        walk, (state, jnp.zeros_like(state[0])),
        (chunks["slot"], chunks["flags"], *per_chunk))
    return _back(jnp.moveaxis(O, 0, 1), chunks, v.dtype), state


def gated_delta_scan(q, k, v, g, beta, runs, state):
    """The recurrence, token by token over the flat tokens of the step:
    the definition the other two forms are held against."""
    T = q.shape[0]
    f32 = jnp.float32
    _, _, _, slot, _ = runs
    r, valid, off, zero_start = token_runs(runs, T)

    def step(state, x):
        qt, kt, vt, gt, bt, s, ok, fresh = x
        S0 = jnp.where(fresh, 0.0, state[s])
        Sd = jnp.exp(gt)[:, None, None] * S0
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", Sd, kt,
                                           precision=_HI))
        S1 = Sd + kt[:, :, None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", S1, qt, precision=_HI)
        return jnp.where(ok, state.at[s].set(S1), state), \
            jnp.where(ok, o, 0.0)

    state, o = jax.lax.scan(step, state, (
        q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
        beta.astype(f32), jnp.clip(slot[r], 0, state.shape[0] - 1),
        valid, valid & (off == 0) & zero_start))
    return o.astype(v.dtype), state


def _head_group(H):
    return next(n for n in (6, 5, 4, 3, 2, 1) if H % n == 0)


def _kernel(slot_ref, flags_ref, dec_ref, k_ref, q_ref, kT_ref, qT_ref,
            v_ref, aT_ref, p_ref, scal_ref, s_in, o_ref, s_out, s_scr,
            tm_scr, *, HG, NC, C):
    g0, c = pl.program_id(0) * HG, pl.program_id(1)
    fl = flags_ref[c]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
           ).astype(jnp.float32)

    @pl.when(((fl & _FIRST) != 0) & ((fl & _FRESH) == 0))
    def _():
        s_scr[...] = s_in[0]

    @pl.when((fl & _FRESH) != 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    @pl.when(((fl & _REAL) != 0) & ((fl & _SINGLE) == 0))
    def _():
        def head(h, _):
            # Tm = (I + A)^{-1} by forward substitution, a row a step:
            # row r = e_r - sum_{i<r} A[r, i] row i. A is strictly
            # lower, so the rows not yet made (still e_i) add nothing
            tm_scr[...] = eye
            for r in range(1, C):
                tm_scr[r:r + 1, :] = eye[r:r + 1, :] - jnp.sum(
                    aT_ref[h, 0, :, r:r + 1] * tm_scr[...], axis=0,
                    keepdims=True)
            O, S = _chunk_math(
                k_ref[h, 0], q_ref[h, 0], kT_ref[h, 0], v_ref[h, 0],
                tm_scr[...], p_ref[h, 0], scal_ref[h, 0],
                dec_ref[(g0 + h) * NC + c], s_scr[h])
            o_ref[h, 0] = O
            s_scr[h] = S
            return 0

        jax.lax.fori_loop(0, HG, head, 0)

    @pl.when((fl & _SINGLE) != 0)
    def _():
        # one token at row 0: the recurrence itself, on the vector unit
        for h in range(HG):
            S0 = s_scr[h]
            kc, qc = kT_ref[h, 0, :, 0:1], qT_ref[h, 0, :, 0:1]
            Sd = dec_ref[(g0 + h) * NC + c] * S0
            u = scal_ref[h, 0, 0:1, 2:3] * (
                v_ref[h, 0, 0:1, :]
                - jnp.sum(Sd * kc, axis=0, keepdims=True))
            S1 = Sd + kc * u
            s_scr[h] = S1
            o_ref[h, 0, 0:1, :] = jnp.sum(S1 * qc, axis=0, keepdims=True)

    @pl.when((fl & _LAST) != 0)
    def _():
        s_out[0] = s_scr[...]


def gated_delta_ragged(q, k, v, g, beta, runs, state, *, chunk=CHUNK,
                       chunks=None):
    """q, k `[T, H, dk]`, v `[T, H, dv]` (float32, or the compute
    dtype), g, beta `[T, H]` float32, `runs` as `paged_runs` gives them with no
    `max_run` (distinct slots), state `[slots, H, dk, dv]` float32
    -> (o `[T, H, dv]` in v's dtype, zero at padding tokens; the state
    with the runs' slots advanced, donated in place). `chunks`:
    `delta_chunks` of the same runs, where a caller made them once for
    several layers."""
    if not gated_delta_enabled():
        return gated_delta_chunked(q, k, v, g, beta, runs, state,
                                   chunk=chunk, chunks=chunks)
    T, H, dk = q.shape
    dv = v.shape[-1]
    S = state.shape[0]
    C = int(chunk)
    chunks = chunks or delta_chunks(runs, T, S, C)
    pre = _prologue(q, k, v, g, beta, chunks, solve=False)
    NC = chunks["tok"].shape[0]
    HG = _head_group(H)

    def rows(d0, d1):
        return pl.BlockSpec((HG, 1, d0, d1),
                            lambda h, c, sl, fl, dec: (h, c, 0, 0))

    st = pl.BlockSpec((1, HG, dk, dv),
                      lambda h, c, sl, fl, dec: (sl[c], h, 0, 0))
    pad = lambda n, m: -(-n // m) * m                       # noqa: E731
    vmem = 4 * HG * (2 * (2 * C * pad(dk, 128) + 2 * pad(dk, 8) * 128
                          + 2 * C * pad(dv, 128) + 3 * C * 128)
                     + 5 * pad(dk, 8) * pad(dv, 128))
    O, state = pl.pallas_call(
        functools.partial(_kernel, HG=HG, NC=NC, C=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(H // HG, NC),
            in_specs=[rows(C, dk), rows(C, dk), rows(dk, C), rows(dk, C),
                      rows(C, dv), rows(C, C), rows(C, C), rows(C, 3),
                      st],
            out_specs=[rows(C, dv), st],
            scratch_shapes=[pltpu.VMEM((HG, dk, dv), jnp.float32),
                            pltpu.VMEM((C, C), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((H, NC, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(min(100 * 2 ** 20,
                                     max(32 * 2 ** 20, 2 * vmem)))),
        interpret=_INTERPRET, name="gated_delta",
    )(chunks["slot"], chunks["flags"], pre["dec"].reshape(-1), pre["K"], pre["Q"], pre["KT"],
      pre["QT"], pre["V"], pre["AT"], pre["P"], pre["scal"], state)
    return _back(O, chunks, v.dtype), state
