"""Pallas TPU kernels — the hand-written device kernels for ops where XLA
fusion isn't enough (the reference's CUDA `paddle/phi/kernels/fusion/` +
external flashattn equivalents)."""

import contextlib as _contextlib
import warnings as _warnings


def xla_fallback(kernel: str, why: str) -> None:
    """Say that `kernel` is taking its XLA form because of `why`.

    Off-TPU that is the normal path and nothing is said. On a TPU
    backend a kernel gate that refuses a shape is a performance cliff
    the operator did not ask for, so it warns — once per message,
    through the `warnings` registry — instead of degrading in silence.
    Kill-switch fallbacks do not come here: the operator set those."""
    from ...core.place import on_tpu_backend
    if on_tpu_backend():
        _warnings.warn(
            f"{kernel}: running the XLA path on a TPU backend — {why}",
            RuntimeWarning, stacklevel=2)


@_contextlib.contextmanager
def interpret_mode():
    """Run every kernel of this package in Pallas interpret mode: how a
    CPU rehearsal (`chip_smoke.py --rehearse`, `tools/
    tpu_tile_validate.py --rehearse`) drives the real kernel bodies and
    their block-table plumbing with no chip."""
    from . import (flash_attention, gated_delta, grouped_matmul,
                   layer_norm, mla_attention, paged_attention, topk_select)
    mods = (flash_attention, gated_delta, grouped_matmul, layer_norm,
            mla_attention, paged_attention, topk_select)
    old = [m._INTERPRET for m in mods]
    for m in mods:
        m._INTERPRET = True
    try:
        yield
    finally:
        for m, was in zip(mods, old):
            m._INTERPRET = was
