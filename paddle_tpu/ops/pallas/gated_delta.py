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
- `gated_delta_ragged`: the same as the Mosaic kernel `gated_delta`,
  which does work, and moves bytes, for the chunks that hold tokens.
  Grid (head group, chunk slot); a run's state is read from HBM once,
  held in VMEM across its chunks and written once. XLA makes only what
  is a scalar a token (`_token_scalars`: gamma, its two decays, beta,
  from the token's OWN chunk alone) and packs q | k | v | scalars head
  major into one array (`_pack`); a head group's rows of the whole step
  stay in VMEM and a chunk takes its rows at the flat token the chunk
  tables name. A chunk of two or more tokens makes `A`, the masked
  decayed `Q K^T` and `K^T` in VMEM (float32, `HIGHEST`), solves
  `(I + A) Tm = I` by a sweep over the columns and takes the chunkwise
  form; a chunk of ONE token (a decode run, or a run's last odd token)
  never enters the row layout: it takes the recurrence itself on its
  one row, as vector operations on the state; a chunk slot that holds
  nothing costs a grid step and no bytes (its block indices stay where
  the last real chunk left them).
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

# a tile of rows is loaded from a multiple of 8 rows (a sublane tile:
# Mosaic takes several rows at a dynamic start from nowhere else) and
# rolled to its first token: it loads this many rows beside its own
_ALIGN = 8


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
    """The chunks of a step's runs, fixed shapes. -> dict: `slot [NC]`,
    `flags [NC]` (first / fresh / last / single token / real), `t0 [NC]`
    the flat token a chunk starts at and `rows [NC]` the tokens it
    holds (a run is a contiguous range of flat tokens), `n [1]`; per
    flat token `pos [T]` its row in its chunk, `last [T]` its chunk's
    last token, `valid [T]`; and, for the `jax.numpy` form's row layout,
    `tok [NC, C]` the flat token a chunk row holds (T = padding) and
    the way back, `at [T]` the row of `[NC * C]` a flat token lies at."""
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
    last_slot = slot[jnp.maximum(n_runs[0] - 1, 0)]
    cslot = jnp.clip(jnp.where(real, slot[r], last_slot), 0,
                     max_slots - 1)
    empty = n == 0
    flags = jnp.where(empty & (c == 0), _FIRST | _LAST | _REAL, flags)
    cslot = jnp.where(empty, 0, cslot)
    tr, valid, off, _ = token_runs(runs, T)
    at = jnp.where(valid,
                   (cend[tr] - nch[tr] + off // C) * C + off % C, 0)
    pos = jnp.where(valid, off % C, 0)
    t = jnp.arange(T, dtype=jnp.int32)
    last = jnp.where(
        valid, t - pos + jnp.clip(length[tr] - off // C * C, 1, C) - 1, t)
    return dict(tok=tok, slot=cslot.astype(jnp.int32),
                flags=flags.astype(jnp.int32), n=n.reshape(1), at=at,
                valid=valid, rows=rows.astype(jnp.int32),
                t0=jnp.where(rows > 0, start[r] + j * C, 0).astype(
                    jnp.int32), pos=pos, last=last)


def _prologue(q, k, v, g, beta, chunks):
    """The `jax.numpy` form's row layout: everything of the chunkwise
    form that does not depend on the incoming state, for all chunk
    slots and heads at once, float32, head major: K, Q `[H, NC, C, dk]`,
    `KT`, V `[H, NC, C, dv]`, the masked decayed `Q K^T` `[H, NC, C,
    C]`, the columns `eg` = e^gamma, `d` = e^{gamma_C - gamma} and `bt`
    = beta `[H, NC, C, 1]`, `dec [H, NC]` = e^{gamma_C}, and `Tm = (I +
    A)^{-1}` by XLA's batched triangular solver. The kernel makes none
    of it: every array here has a row for each of the NC chunk slots."""
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
    Tm = jax.lax.linalg.triangular_solve(
        A + eye, jnp.broadcast_to(eye, A.shape), left_side=True,
        lower=True, unit_diagonal=True)
    qk = jnp.einsum("hnrd,hnid->hnri", Q, K, precision=exact,
                    preferred_element_type=f32)
    K, Q, V = K.astype(f32), Q.astype(f32), V.astype(f32)
    return dict(K=K, Q=Q, KT=jnp.swapaxes(K, -1, -2), V=V, Tm=Tm,
                P=(dec_lo + eye) * qk, eg=jnp.exp(gam)[..., None],
                d=jnp.exp(gam[..., -1:] - gam)[..., None],
                bt=B[..., None], dec=jnp.exp(gam[..., -1]))


def _chunk_math(K, Q, KT, V, Tm, P, eg, d, bt, dec, S0):
    """One chunk of one head: -> (O [C, dv], S_C [dk, dv]). `eg`, `d`,
    `bt` are columns `[C, 1]`; `dec` is e^{gamma_C}, a scalar."""
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
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
    pre = _prologue(q, k, v, g, beta, chunks)
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
                 ("K", "Q", "KT", "V", "Tm", "P", "eg", "d", "bt", "dec")]
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


def _head_group(H, head_bytes):
    """Heads a grid step holds: the most that divide H and whose rows
    of the whole step (`head_bytes` a head, in and out, each buffered
    twice) leave VMEM room for the states and the chunk's arithmetic."""
    return next((n for n in (6, 5, 4, 3, 2) if H % n == 0
                 and n * head_bytes <= 48 * 2 ** 20), 1)


def _lanes(n):
    return -(-n // 128) * 128


def _row_layout(dk, dv):
    """One token's packed row of a head: q at lane 0, k, v, and behind
    v its four scalars (gamma, e^gamma, e^{gamma_C - gamma}, beta), q,
    k and v each from a multiple of 128 lanes. -> (k at, v at, scalars
    at, width). At dk 96, dv 192: 128, 256, 448, 512, the lanes the
    three arrays would fill apart."""
    vat = 2 * _lanes(dk)
    return _lanes(dk), vat, vat + dv, _lanes(vat + dv + 4)


def rows_walked(length, chunk=CHUNK):
    """Rows of q, k, v the kernel loads for a run of `length` tokens
    (host arithmetic, for the engine's counters): a chunk of one token
    its one row, a chunk of more its tile of `chunk` rows and the
    `_ALIGN` that let the load start on a sublane tile; a chunk slot
    that holds nothing loads none."""
    full, rest = divmod(int(length), int(chunk))
    return (full + (rest > 1)) * (int(chunk) + _ALIGN) + (rest == 1)


def _token_scalars(g, beta, chunks, C):
    """-> (`[T, H, 4]` float32: gamma, e^gamma, e^{gamma_C - gamma},
    beta a token and head; `dec [H * NC]`: e^{gamma_C} a head and chunk
    slot). gamma is the sum of g over the token's chunk up to it, by
    doubling steps over the chunk's own rows: a token's scalars depend
    on its chunk alone, whatever rides in the step."""
    f32 = jnp.float32
    gam, pos = g.astype(f32), chunks["pos"][:, None]
    step = 1
    while step < C:
        gam = gam + jnp.where(pos >= step, jnp.roll(gam, step, axis=0), 0.0)
        step *= 2
    scal = jnp.stack([gam, jnp.exp(gam), jnp.exp(gam[chunks["last"]] - gam),
                      beta.astype(f32)], axis=-1)
    rows, end = chunks["rows"], chunks["t0"] + chunks["rows"] - 1
    dec = jnp.where((rows > 0)[:, None],
                    jnp.exp(gam[jnp.clip(end, 0, gam.shape[0] - 1)]), 1.0)
    return scal, dec.T.reshape(-1)


def _pack(q, k, v, scal, C):
    """-> `[H, TP, width]` float32: every token's row (`_row_layout`)
    head major, behind the step's T tokens the zero rows a tile that
    starts at the last token still loads."""
    T, H, dk = q.shape
    kat, _, sat, width = _row_layout(dk, v.shape[-1])
    f32 = jnp.float32

    def to(x, lanes):
        return jnp.pad(x.astype(f32),
                       ((0, 0), (0, 0), (0, lanes - x.shape[-1])))

    row = jnp.concatenate([to(q, kat), to(k, kat), v.astype(f32),
                           to(scal, width - sat)], axis=-1)
    more = -(-(T + C + _ALIGN) // _ALIGN) * _ALIGN - T
    return jnp.moveaxis(jnp.pad(row, ((0, more), (0, 0), (0, 0))), 1, 0)


def _kernel(slot_ref, flags_ref, t0_ref, rows_ref, dec_ref, x_ref, s_in,
            o_ref, s_out, s_scr, row_scr, *, HG, NC, C, dk, dv):
    g0, c = pl.program_id(0) * HG, pl.program_id(1)
    fl, t0 = flags_ref[c], t0_ref[c]
    kat, vat, _, _ = _row_layout(dk, dv)
    f32 = jnp.float32
    iota = lambda shape, dim: jax.lax.broadcasted_iota(     # noqa: E731
        jnp.int32, shape, dim)

    @pl.when(((fl & _FIRST) != 0) & ((fl & _FRESH) == 0))
    def _():
        s_scr[...] = s_in[0]

    @pl.when((fl & _FRESH) != 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    @pl.when(((fl & _REAL) != 0) & ((fl & _SINGLE) == 0))
    def _():
        # the chunk's rows lie at t0 .. t0 + n: load the C + _ALIGN rows
        # from the sublane tile t0 is in, roll t0 to row 0
        n = rows_ref[c]
        base = pl.multiple_of(t0 // _ALIGN * _ALIGN, _ALIGN)
        lead = t0 - base
        back = jnp.where(lead == 0, 0, C + _ALIGN - lead)
        r, i = iota((C, C), 0), iota((C, C), 1)
        eye = (r == i).astype(f32)
        low = (i < r) & (r < n)
        live = iota((C, 1), 0) < n
        tall = iota((C + _ALIGN, 1), 0)
        mine = (tall >= lead) & (tall < lead + n)
        nt = functools.partial(
            jax.lax.dot_general, precision=_HI, preferred_element_type=f32,
            dimension_numbers=(((1,), (1,)), ((), ())))

        def tile(h, at, lanes):
            rows = x_ref[h, pl.ds(base, C + _ALIGN), at:at + lanes]
            return jnp.where(live, pltpu.roll(rows, back, 0)[:C], 0.0)

        def head(h, _):
            Q, K = tile(h, 0, dk), tile(h, kat, dk)
            vs = tile(h, vat, dv + 4)
            V = vs[:, :dv]
            gam, eg, d, bt = (vs[:, dv + j:dv + j + 1] for j in range(4))
            # gamma along the lanes, then what `_prologue` makes: rows
            # past the chunk's n tokens are zeros, their beta 0
            dec_lo = jnp.exp(jnp.where(
                low, gam - jnp.sum(eye * gam, axis=0, keepdims=True),
                -jnp.inf))
            A = bt * dec_lo * nt(K, K)
            P = (dec_lo + eye) * nt(Q, K)
            # Tm = (I + A)^{-1}, a column a step: once row j is final,
            # every later row r takes off A[r, j] row j. A is strictly
            # lower: a tile of 8 rows above row j has nothing to take
            tm = [eye[b:b + 8] for b in range(0, C, 8)]
            for j in range(C - 1):
                row = tm[j // 8][j % 8:j % 8 + 1]
                for b in range(j // 8, C // 8):
                    tm[b] = tm[b] - A[8 * b:8 * b + 8, j:j + 1] * row
            O, S = _chunk_math(K, Q, K.T, V, jnp.concatenate(tm), P, eg, d,
                               bt, dec_ref[(g0 + h) * NC + c], s_scr[h])
            s_scr[h] = S
            # o's rows the same way back: only this chunk's are written
            out = pltpu.roll(jnp.concatenate(
                [O, jnp.zeros((_ALIGN, dv), f32)]), lead, 0)
            at = (h, pl.ds(base, C + _ALIGN))
            o_ref[at] = jnp.where(mine, out, o_ref[at])
            return 0

        jax.lax.fori_loop(0, HG, head, 0)

    @pl.when((fl & _SINGLE) != 0)
    def _():
        # one token, one row at its flat token: the recurrence itself,
        # on the vector unit. The row goes through `row_scr` (Mosaic
        # loads ONE row at any dynamic start, and broadcasts only from
        # a static one); k and q as columns: the row against the
        # diagonal, summed along the lanes
        diag = iota((dk, dk), 0) == iota((dk, dk), 1)
        col = lambda x: jnp.sum(jnp.where(diag, x, 0.0),    # noqa: E731
                                axis=1, keepdims=True)
        for h in range(HG):
            row_scr[0:1] = x_ref[h, pl.ds(t0, 1)]
            vs = row_scr[0:1, vat:vat + dv + 4]
            kc, qc = col(row_scr[0:1, kat:kat + dk]), col(row_scr[0:1, :dk])
            Sd = dec_ref[(g0 + h) * NC + c] * s_scr[h]
            u = vs[:, dv + 3:dv + 4] * (
                vs[:, :dv] - jnp.sum(Sd * kc, axis=0, keepdims=True))
            S1 = Sd + kc * u
            s_scr[h] = S1
            o_ref[h, pl.ds(t0, 1)] = jnp.sum(S1 * qc, axis=0, keepdims=True)

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
    C = int(chunk)
    chunks = chunks or delta_chunks(runs, T, state.shape[0], C)
    scal, dec = _token_scalars(g, beta, chunks, C)
    x = _pack(q, k, v, scal, C)
    NC = chunks["slot"].shape[0]
    TP, width = x.shape[1:]
    head_bytes = 4 * 2 * TP * (width + _lanes(dv))
    HG = _head_group(H, head_bytes)

    def whole(lanes):           # a head group's rows of the whole step
        return pl.BlockSpec((HG, TP, lanes), lambda h, c, *_: (h, 0, 0))

    st = pl.BlockSpec((1, HG, dk, dv),
                      lambda h, c, sl, *_: (sl[c], h, 0, 0))
    vmem = HG * (head_bytes + 4 * 5 * -(-dk // 8) * 8 * _lanes(dv)) \
        + 8 * 2 ** 20
    O, state = pl.pallas_call(
        functools.partial(_kernel, HG=HG, NC=NC, C=C, dk=dk, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(H // HG, NC),
            in_specs=[whole(width), st],
            out_specs=[whole(dv), st],
            scratch_shapes=[pltpu.VMEM((HG, dk, dv), jnp.float32),
                            pltpu.VMEM((8, width), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((H, TP, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(min(100 * 2 ** 20,
                                     max(32 * 2 ** 20, vmem)))),
        interpret=_INTERPRET, name="gated_delta",
    )(chunks["slot"], chunks["flags"], chunks["t0"], chunks["rows"], dec,
      x, state)
    o = jnp.moveaxis(O[:, :T], 0, 1)
    return jnp.where(chunks["valid"][:, None, None], o, 0).astype(
        v.dtype), state
