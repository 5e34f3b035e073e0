"""Grouped-expert matmul Pallas kernel for the MoE capacity buffers.

The MoE serving hot path (ISSUE 10) runs every expert's FFN on its
fixed `[C, d]` capacity buffer. The XLA path expresses the whole block
as one-hot einsums (`moe_utils.dispatch_tokens` / `combine_tokens` +
`einsum("ecd,edf->ecf")`), which materializes `[T, k, C]`/`[T, k, E]`
masks and leaves the per-expert matmuls to the compiler's batching.
This kernel grids DIRECTLY over (expert, C-tile, F-tile) with a
sequential d-reduction axis, so each expert's capacity buffer hits the
MXU as dense tiles:

* grid `(E, C/bc, F/bf, D/bd)` — the leading three axes are
  embarrassingly parallel (`dimension_semantics`), the trailing
  reduction axis carries a VMEM fp32 accumulator;
* int8 weight-only experts dequantize INSIDE the kernel: the
  per-(expert, out-channel) scale tile rides the same (e, f) index
  map as the weight tile and multiplies it right after the load —
  the weight is read from HBM as int8, exactly like `_mm`'s fused
  dequant on the dense path;
* int4 weight-only experts (ISSUE 14) store TWO nibbles per byte
  along the contraction axis (`pack_int4`/`unpack_int4`: low nibble =
  even row, high nibble = odd row, sign-extended by arithmetic
  shifts) with per-(expert, out-channel) fp16 scales; the kernel
  loads the packed `[bd/2, bf]` tile and unpacks + dequantizes it in
  registers right before the dot — the weight is read from HBM at
  0.5 bytes/element, and the autotune cache keys these winners by
  the `int4` weight dtype (the PR 11 int8 keying rule);
* tile sizes `(block_c, block_f, block_d)` are TUNABLE
  (`ops.pallas.autotune`, kernel name ``grouped_matmul``) — the
  einsum path stays the CPU oracle and the fallback for shapes the
  gate refuses.

The companion index-based dispatch/combine (no one-hot
materialization) lives in `parallel.moe_utils`
(`dispatch_tokens_indexed` / `combine_tokens_indexed`); together they
form the grouped MoE path `incubate.nn.fused_transformer` dispatches
to on TPU (or under kernel-test interpret mode).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune

# Set by tests to run the kernel in interpret mode on the CPU mesh.
_INTERPRET = False


def _on_tpu_backend() -> bool:
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


def grouped_matmul_killed() -> bool:
    """`PADDLE_TPU_GROUPED_MATMUL=0`: the operator asked for the
    one-hot einsum reference on every MoE expert matmul."""
    return os.environ.get("PADDLE_TPU_GROUPED_MATMUL", "1") == "0"


def grouped_matmul_enabled(d_in, d_out) -> bool:
    """Dispatch gate: env kill-switch first, then backend/shape — on a
    TPU backend the contraction and output feature axes must be
    lane-aligned so weight tiles fill (sublane x 128) registers; under
    `_INTERPRET` (tests) any shape runs. Alignment comes from the same
    source of truth as the paged gate (`autotune.LANE_ALIGN`)."""
    if grouped_matmul_killed():
        return False
    if _INTERPRET:
        return True
    if d_in % autotune.LANE_ALIGN == 0 and d_out % autotune.LANE_ALIGN == 0:
        return _on_tpu_backend()
    from . import xla_fallback
    xla_fallback("grouped_expert_matmul",
                 f"the gate refuses d_in={d_in}, d_out={d_out} (both "
                 f"must be multiples of {autotune.LANE_ALIGN})")
    return False


# ---------------------------------------------------------------------
# int4 packing (two nibbles per byte along the contraction axis)
# ---------------------------------------------------------------------

INT4_QMAX = 7.0


def pack_int4(q, axis=-2):
    """Pack int4-valued int8 (`[-8, 7]`) pairs along `axis` into one
    int8 byte each: low nibble = even index, high nibble = odd index.
    The axis length must be even (expert contraction axes always are —
    they are MXU-lane-aligned in practice)."""
    q = jnp.asarray(q)
    axis = axis % q.ndim
    if q.shape[axis] % 2:
        raise ValueError(
            f"pack_int4 needs an even axis length, got {q.shape[axis]}")
    even = jnp.take(q, jnp.arange(0, q.shape[axis], 2), axis=axis)
    odd = jnp.take(q, jnp.arange(1, q.shape[axis], 2), axis=axis)
    return ((odd.astype(jnp.int8) << 4)
            | (even.astype(jnp.int8) & 0x0F)).astype(jnp.int8)


def unpack_int4(packed, axis=-2):
    """Inverse of `pack_int4`: int8 bytes -> int4 values, interleaved
    back to the original order (arithmetic shifts sign-extend, so the
    round trip is exact over [-8, 7]). Pure vector ops, so the grouped
    kernel unpacks its weight tile with the same function."""
    axis = axis % packed.ndim
    # shift in int32: Mosaic has no 8-bit vector shifts on v5e ("failed
    # to legalize operation 'arith.shli' ... vector<..xi8>")
    p32 = packed.astype(jnp.int32)
    low = (p32 << 28) >> 28
    high = p32 >> 4
    out = jnp.stack([low, high], axis=axis + 1).astype(jnp.int8)
    shape = list(packed.shape)
    shape[axis] *= 2
    return out.reshape(shape)


def is_packed_int4(w, d_in):
    """True when `w` is an int4-packed weight for a logical `[...,
    d_in, d_out]` matmul: int8 storage with HALF the contraction rows.
    The shape test is unambiguous — an int8 weight always matches its
    activation's contraction axis exactly."""
    return (w.dtype == jnp.int8 or str(w.dtype) == "int8") \
        and w.shape[-2] * 2 == int(d_in)


def quantize_int4_experts(w):
    """[..., In, Out] float -> (packed int8 [..., In/2, Out], fp16
    scales [..., Out]): symmetric per-out-channel amax scaling at
    qmax=7, then nibble-packed along the contraction axis. The fp16
    scales halve the (already small) scale overhead vs the int8
    path's fp32 — int4's point is bytes. Same scale convention as
    `fused_transformer._quantize_expert_stack`: dequant is
    `q * scale / qmax`."""
    wf = jnp.asarray(w).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2), 1e-9)
    q = jnp.clip(jnp.round(wf / scale[..., None, :] * INT4_QMAX),
                 -INT4_QMAX, INT4_QMAX).astype(jnp.int8)
    return pack_int4(q, axis=-2), scale.astype(jnp.float16)


def expert_weight_bytes(E, d_in, d_out, weight_dtype, num_layers=1):
    """HBM bytes one expert-weight stack `[L, E, d_in, d_out]` costs,
    scales included — the analytic side of the int4 capacity contract
    (bf16 2 B/elem; int8 0.5 B... no: 1 B + fp32 scale/out-chan; int4
    0.5 B + fp16 scale/out-chan). Pure host arithmetic."""
    n = num_layers * E * d_in * d_out
    per_scale = num_layers * E * d_out
    if weight_dtype in ("float32",):
        return 4 * n
    if weight_dtype in ("bfloat16", "float16"):
        return 2 * n
    if weight_dtype == "int8":
        return n + 4 * per_scale
    if weight_dtype == "int4":
        return n // 2 + 2 * per_scale
    raise ValueError(f"unknown expert weight dtype {weight_dtype!r}")


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd, qmax):
    """One (expert, c-tile, f-tile, d-tile) grid cell.

    x tile [1, bc, bd]; w tile [1, bd, bf] (int8 when quantized);
    optional scale tile [1, 1, bf] fp32; out tile [1, bc, bf]; fp32
    accumulator scratch [bc, bf] carried across the d axis."""
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[0].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _gmm_kernel_quant(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nd, qmax):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # weight-only dequant fused at the tile load: int8 tile * per-
    # out-channel scale/qmax (same formula as fused_transformer._deq)
    w = w_ref[0].astype(jnp.float32) \
        * (s_ref[0].astype(jnp.float32) / qmax)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _gmm_kernel_quant4(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nd,
                       qmax):
    """int4 variant: the weight tile arrives PACKED `[bd/2, bf]` int8
    and is unpacked + dequantized in registers right before the dot —
    the HBM fetch is half the int8 path's. Same grid/accumulator
    discipline as the other kernels; the d-reduction axis indexes
    packed rows (bd/2 per tile), the x tile the matching bd rows."""
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w4 = unpack_int4(w_ref[0], axis=0)               # [bd, bf] int4
    w = w4.astype(jnp.float32) \
        * (s_ref[0].astype(jnp.float32) / qmax)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _pick_block(n, target, multiple=1):
    """Largest divisor of n that is <= target (tiles must be exact —
    a remainder tile would read past the buffer). `multiple` further
    constrains the divisor (the int4 d-tile must cover whole packed
    bytes, so it must be even)."""
    b = min(int(target), int(n))
    b -= b % multiple
    while b > multiple and (n % b or b % multiple):
        b -= 1
    if b <= 0 or n % b:
        b = multiple
    return b


def _gmm_call(x, w, scale, qmax, bc, bf, bd, out_dtype):
    """The raw pallas_call with resolved tile sizes. An int4-packed
    weight (`is_packed_int4`) rides the quant4 kernel: its BlockSpec
    tiles packed rows (`bd // 2` per d-step) while x tiles the
    matching `bd` activation rows — the index maps line up because
    both advance one block per d grid step."""
    E, C, D = x.shape
    F = w.shape[2]
    int4 = is_packed_int4(w, D)
    nd = D // bd
    grid = (E, C // bc, F // bf, nd)
    in_specs = [
        pl.BlockSpec((1, bc, bd), lambda e, c, f, d: (e, c, d)),
        pl.BlockSpec((1, bd // 2 if int4 else bd, bf),
                     lambda e, c, f, d: (e, d, f)),
    ]
    args = [x, w]
    if scale is not None:
        # [E, F] -> [E, 1, F]: Mosaic wants a block's second-minor dim
        # to be a multiple of 8 or the whole axis, and a one-expert row
        # of a 2-D [E, F] array is neither
        in_specs.append(pl.BlockSpec((1, 1, bf),
                                     lambda e, c, f, d: (e, 0, f)))
        # ... and fp32: the int4 path stores fp16 scales, which v5e
        # cannot load as a vector ("Invalid vector type for load ...
        # vector<..xf16>"); widening [E, F] here costs nothing next to
        # the weight read
        args.append(scale.astype(jnp.float32).reshape(E, 1, F))
        kernel = functools.partial(
            _gmm_kernel_quant4 if int4 else _gmm_kernel_quant, nd=nd,
            qmax=float(qmax))
    else:
        kernel = functools.partial(_gmm_kernel, nd=nd, qmax=float(qmax))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, c, f, d: (e, c, f)),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((E, C, F), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * E * C * D * F,
            bytes_accessed=(E * C * D * x.dtype.itemsize
                            + w.size * w.dtype.itemsize
                            + E * C * F * jnp.dtype(out_dtype).itemsize),
            transcendentals=0),
        interpret=_INTERPRET, name="grouped_matmul",
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _gmm_core(x, w, bc, bf, bd, out_dtype):
    """Differentiable (unquantized) grouped matmul: Pallas forward,
    XLA einsum backward — the `_flash_core` discipline (the compiler
    fuses the two grouped backward contractions well, and training
    never runs int8 experts)."""
    return _gmm_call(x, w, None, 127.0, bc, bf, bd, out_dtype)


def _gmm_core_fwd(x, w, bc, bf, bd, out_dtype):
    return _gmm_call(x, w, None, 127.0, bc, bf, bd, out_dtype), (x, w)


def _gmm_core_bwd(bc, bf, bd, out_dtype, res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    dx = jnp.einsum("ecf,edf->ecd", gf, w.astype(jnp.float32))
    dw = jnp.einsum("ecd,ecf->edf", x.astype(jnp.float32), gf)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_gmm_core.defvjp(_gmm_core_fwd, _gmm_core_bwd)


def grouped_expert_matmul(x, w, scale=None, *, qmax=None,
                          block_c=None, block_f=None, block_d=None,
                          out_dtype=None):
    """x [E, C, D] @ w [E, D, F] -> [E, C, F], one expert per leading
    grid axis. `scale` [E, F] fp32 dequantizes int8 weight-only
    experts inside the kernel (`w * scale / qmax` per out-channel);
    the quantized variant is inference-only (no VJP — int8 experts
    are never trained), the fp variant differentiates via a custom
    VJP whose backward runs the XLA grouped contractions.

    int4-packed weights (`is_packed_int4`: int8 storage at half the
    contraction rows, the `pack_int4` layout) dispatch the quant4
    kernel with the `[E, F]` fp16 scales; tile lookups then key by
    the `int4` dtype. `qmax` defaults by detected weight format
    (INT4_QMAX packed, 127 int8) so a call site that forgets to
    thread it can never silently mis-scale the dequant.

    Tile sizes default to the tuned winner for this shape bucket
    (`autotune.kernel_config("grouped_matmul", ...)`) and fall back to
    MXU-shaped 128/512 targets; explicit arguments pin them (the
    tuner's candidate builder does exactly that)."""
    E, C, D = x.shape
    int4 = scale is not None and is_packed_int4(w, D)
    if qmax is None:
        qmax = INT4_QMAX if int4 else 127.0
    F = w.shape[2]
    if block_c is None or block_f is None or block_d is None:
        # quantized experts key by the WEIGHT dtype (int8 / int4):
        # tiles measured on 1-byte or packed-nibble loads are a
        # different cache entry than the fp variant's
        if int4:
            key_dt = jnp.dtype(jnp.int4)
        elif scale is not None:
            key_dt = w.dtype
        else:
            key_dt = x.dtype
        cfg = autotune.kernel_config(
            "grouped_matmul", autotune.shape_bucket(E, C, D, F),
            key_dt, default=None) or {}
        block_c = block_c or cfg.get("block_c", 128)
        block_f = block_f or cfg.get("block_f", 128)
        block_d = block_d or cfg.get("block_d", 512)
    bc = _pick_block(C, block_c)
    bf = _pick_block(F, block_f)
    bd = _pick_block(D, block_d, multiple=2 if int4 else 1)
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if scale is None:
        return _gmm_core(x, w, bc, bf, bd, out_dtype)
    return _gmm_call(x, w, scale, qmax, bc, bf, bd, out_dtype)


def grouped_matmul_oracle(x, w, scale=None, *, qmax=None,
                          out_dtype=None):
    """The einsum reference (CPU oracle + fallback): dequant in the
    compute dtype, then `ecd,edf->ecf` — numerically the
    `fused_transformer._expert_ffn` formulation. int4-packed weights
    unpack first (same nibble layout as the kernel); `qmax` defaults
    by detected format like `grouped_expert_matmul`."""
    cd = out_dtype or x.dtype
    if scale is not None and is_packed_int4(w, x.shape[2]):
        if qmax is None:
            qmax = INT4_QMAX
        w = unpack_int4(w, axis=-2)
    if qmax is None:
        qmax = 127.0
    wf = w.astype(cd)
    if scale is not None:
        wf = wf * (scale[:, None, :].astype(cd) / float(qmax))
    return jnp.einsum("ecd,edf->ecf", x.astype(cd), wf).astype(cd)


# ---------------------------------------------------------------------
# ragged groups: the dropless expert layer (models/afmoe.py)
# ---------------------------------------------------------------------

#: rows of one m-tile of the ragged kernel. Every tile belongs to ONE
#: expert (each group is padded up to whole tiles), so an expert with at
#: most this many pairs reads its weights once; the padding is bounded
#: by one tile an expert, whatever the routing.
RAGGED_BLOCK_M = 64


def ragged_num_tiles(num_rows, num_groups, block_m=RAGGED_BLOCK_M):
    """m-tiles that hold `num_rows` rows in `num_groups` groups however
    they are divided: the rows' own tiles plus one part-filled tile a
    group. Static, from shapes alone."""
    return -(-int(num_rows) // block_m) + int(num_groups)


def ragged_layout(group_sizes, num_tiles, block_m=RAGGED_BLOCK_M):
    """Where each group's rows live in the tile-padded row axis.

    group_sizes [E] int32 -> (row_start [E], tile_expert [num_tiles],
    n_used [1]): group e's rows start at `row_start[e]` (a multiple of
    `block_m`), tile i belongs to expert `tile_expert[i]`, and tiles
    from `n_used` on hold no row (they repeat the last used expert, so
    that the kernel fetches nothing new for them)."""
    sizes = group_sizes.astype(jnp.int32)
    tiles = (sizes + block_m - 1) // block_m
    tile_end = jnp.cumsum(tiles)
    n_used = tile_end[-1]
    ids = jnp.arange(num_tiles, dtype=jnp.int32)
    ids = jnp.minimum(ids, jnp.maximum(n_used - 1, 0))
    tile_expert = jnp.searchsorted(tile_end, ids, side="right")
    tile_expert = jnp.minimum(tile_expert, sizes.shape[0] - 1)
    return ((tile_end - tiles) * block_m, tile_expert.astype(jnp.int32),
            n_used.reshape(1).astype(jnp.int32))


def _rgmm_kernel(te_ref, nu_ref, x_ref, w_ref, *rest, nd, swiglu):
    """One (m-tile, d-tile) grid cell of the ragged grouped matmul:
    x tile [bm, bd] times its expert's weight tile [1, bd, F] (whole
    rows of the weight: one contiguous read), accumulated in fp32
    across the d axis. With `swiglu` a second weight rides the same
    tiles and the last d step writes `silu(x Wg) * (x Wu)`. Tiles past
    `n_used` compute nothing and fetch nothing."""
    if swiglu:
        w2_ref, o_ref, acc_ref, acc2_ref = rest
    else:
        o_ref, acc_ref = rest
        w2_ref = acc2_ref = None
    i, d = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nu_ref[0])
    def _live():
        @pl.when(d == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if swiglu:
                acc2_ref[...] = jnp.zeros_like(acc2_ref)

        x = x_ref[...]
        dims = (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            x, w_ref[0], dims, preferred_element_type=jnp.float32)
        if swiglu:
            acc2_ref[...] += jax.lax.dot_general(
                x, w2_ref[0], dims, preferred_element_type=jnp.float32)

        @pl.when(d == nd - 1)
        def _finalize():
            y = acc_ref[...]
            if swiglu:
                y = y * jax.nn.sigmoid(y) * acc2_ref[...]
            o_ref[...] = y.astype(o_ref.dtype)


def ragged_expert_matmul(x, w, tile_expert, n_used, w_up=None, *,
                         block_m=RAGGED_BLOCK_M, block_d=512,
                         out_dtype=None, name="moe_experts"):
    """Rows grouped by expert times each group's own weight:
    `x [M, D] @ w[tile_expert[i]] [D, F]` for every m-tile i of
    `block_m` rows, `M = len(tile_expert) * block_m` (`ragged_layout`
    places the rows). With `w_up` the result is the SwiGLU hidden
    `silu(x w) * (x w_up)`. Operands keep their dtype (bf16 on a bf16
    deployment), accumulation is fp32. Each expert's weights are read
    once a tile it owns; tiles from `n_used` on cost nothing, and their
    output rows are left unwritten: read only rows `ragged_layout` gave
    to a group.

    On a TPU backend (or in kernel-test interpret mode) this is the
    Pallas kernel, named `name` in the device trace; elsewhere
    `jax.lax.ragged_dot` over the tile-padded groups."""
    M, D = x.shape
    E, _, F = w.shape
    NT = tile_expert.shape[0]
    if M != NT * block_m:
        raise ValueError(f"{M} rows are not {NT} tiles of {block_m}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    swiglu = w_up is not None
    if not grouped_matmul_enabled(D, F):
        live = jnp.arange(NT) < n_used[0]
        sizes = jnp.zeros((E,), jnp.int32).at[tile_expert].add(
            jnp.where(live, block_m, 0).astype(jnp.int32))
        y = jax.lax.ragged_dot(x, w, sizes,
                               preferred_element_type=jnp.float32)
        if swiglu:
            y = jax.nn.silu(y) * jax.lax.ragged_dot(
                x, w_up, sizes, preferred_element_type=jnp.float32)
        return y.astype(out_dtype)
    bd = _pick_block(D, block_d)
    nd = D // bd

    def live_tile(i, nu):
        return jnp.where(i < nu[0], i, jnp.maximum(nu[0] - 1, 0))

    def live_d(i, d, nu):
        return jnp.where(i < nu[0], d, nd - 1)

    x_spec = pl.BlockSpec(
        (block_m, bd), lambda i, d, te, nu: (live_tile(i, nu),
                                             live_d(i, d, nu)))
    w_spec = pl.BlockSpec(
        (1, bd, F), lambda i, d, te, nu: (te[i], live_d(i, d, nu), 0))
    o_spec = pl.BlockSpec(
        (block_m, F), lambda i, d, te, nu: (live_tile(i, nu), 0))
    scratch = [pltpu.VMEM((block_m, F), jnp.float32)]
    args = [x, w]
    in_specs = [x_spec, w_spec]
    if swiglu:
        args.append(w_up)
        in_specs.append(w_spec)
        scratch.append(pltpu.VMEM((block_m, F), jnp.float32))
    n_w = 2 if swiglu else 1
    vmem = (2 * n_w * bd * F * w.dtype.itemsize
            + 2 * block_m * (bd + F) * 4 + n_w * block_m * F * 4)
    return pl.pallas_call(
        functools.partial(_rgmm_kernel, nd=nd, swiglu=swiglu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(NT, nd), in_specs=in_specs,
            out_specs=o_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((M, F), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(100 * 2 ** 20,
                                     max(32 * 2 ** 20, 2 * vmem)))),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_w * M * D * F,
            bytes_accessed=(n_w * min(E, NT) * D * F * w.dtype.itemsize
                            + M * D * x.dtype.itemsize
                            + M * F * out_dtype.itemsize),
            transcendentals=M * F if swiglu else 0),
        interpret=_INTERPRET, name=name,
    )(tile_expert, n_used, *args)


def tune_grouped_matmul(E, C, D, F, *, dtype="float32",
                        quantized=False, seed=0, budget_s=None,
                        timer=None, persist=True):
    """Search the (block_c, block_f, block_d) tile space of one
    grouped-matmul shape bucket against the einsum oracle. Runs the
    real kernel (interpret mode off-TPU); the winner lands in the
    persistent cache so `grouped_expert_matmul`'s next trace resolves
    it for free."""
    import numpy as np

    global _INTERPRET
    dtype = np.dtype(dtype)
    int4 = dtype == np.dtype(jnp.int4)
    if int4 or dtype == np.int8:
        # an int8/int4 KEY dtype means the weight-quantized variant:
        # activations stay fp32 (the serving compute dtype), weights
        # quantized + scales (int4: nibble-packed, fp16 scales)
        quantized, dtype = True, np.dtype(np.float32)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(E, C, D).astype(dtype))
    qmax = INT4_QMAX if int4 else 127.0
    if int4:
        q = rng.randint(-7, 8, (E, D, F)).astype(np.int8)
        w = pack_int4(jnp.asarray(q), axis=-2)
        s = jnp.asarray((np.abs(rng.randn(E, F)) * 0.05 + 0.01).astype(
            np.float16))
        args = (x, w, s)
    elif quantized:
        w = jnp.asarray(rng.randint(-127, 128, (E, D, F)).astype(
            np.int8))
        s = jnp.asarray((np.abs(rng.randn(E, F)) * 0.05 + 0.01).astype(
            np.float32))
        args = (x, w, s)
    else:
        w = jnp.asarray((rng.randn(E, D, F) * 0.1).astype(dtype))
        args = (x, w, None)

    def oracle(x, w, s):
        return grouped_matmul_oracle(x, w, s, qmax=qmax, out_dtype=dtype)

    def build(cfg):
        def run(x, w, s):
            return grouped_expert_matmul(
                x, w, s, qmax=qmax, block_c=cfg["block_c"],
                block_f=cfg["block_f"], block_d=cfg["block_d"],
                out_dtype=dtype)
        return run

    was = _INTERPRET
    if not _on_tpu_backend():
        _INTERPRET = True
    try:
        # quantized winners cache under the weight dtype the runtime
        # lookup keys by (int8 / int4), never clobbering the fp entry
        if int4:
            key_dt = np.dtype(jnp.int4)
        elif quantized:
            key_dt = np.dtype(np.int8)
        else:
            key_dt = dtype
        return autotune.search(
            "grouped_matmul", autotune.shape_bucket(E, C, D, F),
            key_dt, autotune.grouped_matmul_candidates(E, C, D, F),
            build, args, oracle, rtol=2e-2, atol=2e-2,
            budget_s=budget_s, timer=timer, persist=persist,
            meta={"quantized": bool(quantized), "int4": bool(int4),
                  "seed": seed})
    finally:
        _INTERPRET = was
