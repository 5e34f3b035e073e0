"""Layer `linear_attn`: the linear mixer AROUND its kernel, per mixed
step: the projections (`lin_proj`), the short convolution with its
windows and tails (`lin_conv`), the gated output norm and product
(`lin_gate_out`), and what of the scope `gated_delta` is not the kernel
`gated_delta`. Device self time of the profiled slice's operations that
the live engine's own table of instruction -> scope
(`tracing.step_op_scopes()`) puts under these scopes, over the mixed
steps of the slice (`harness/device_scopes.py`); an operation named
after a Pallas kernel is left out, the `kernels.*` metrics hold it. None
where the program gives no table (before PR 35), where the table is not
the running executable's, or where the step sets none of these
scopes."""
from harness import device_scopes

SCOPES = ("lin_proj", "lin_conv", "lin_gate_out", "gated_delta")


def read(ctx):
    return device_scopes.ms_per_step(ctx, *SCOPES)
