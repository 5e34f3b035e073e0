"""Layer `model`: what turns hidden states into tokens, per mixed step:
under `head` the sample rows' gather, the final norm, the head product
and the float32 logits; under `sample` the key's split and
`select_token`; under `diffusion_confidence` a block-decoding model's
confidences. Device self time of the profiled slice's operations that
the live engine's own table of instruction -> scope
(`tracing.step_op_scopes()`) puts under these scopes, over the mixed
steps of the slice (`harness/device_scopes.py`); an operation named
after a Pallas kernel is left out, the `kernels.*` metrics hold it. None
where the program gives no table (before PR 35), where the table is not
the running executable's, or where the step sets none of these
scopes."""
from harness import device_scopes

SCOPES = ("head", "sample", "diffusion_confidence")


def read(ctx):
    return device_scopes.ms_per_step(ctx, *SCOPES)
