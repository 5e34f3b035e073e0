"""Layer `model`: the projections either side of attention, per mixed
step: under `attn_qkv` the input norm, the q, k, v (and gate) products,
q/k norm, rope and, in the GPT step, the slice and transpose of the
scanned qkv matrix; under `attn_out` the output product with its bias or
norm and the residual. Device self time of the profiled slice's
operations that the live engine's own table of instruction -> scope
(`tracing.step_op_scopes()`) puts under these scopes, over the mixed
steps of the slice (`harness/device_scopes.py`); an operation named
after a Pallas kernel is left out, the `kernels.*` metrics hold it. None
where the program gives no table (before PR 35), where the table is not
the running executable's, or where the step sets none of these
scopes."""
from harness import device_scopes

SCOPES = ("attn_qkv", "attn_out")


def read(ctx):
    return device_scopes.ms_per_step(ctx, *SCOPES)
