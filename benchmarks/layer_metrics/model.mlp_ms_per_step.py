"""Layer `model`: the dense feed-forward part, per mixed step: under
`mlp` a GPT FFN or a SwiGLU MLP with its norm and residual, AFMoE's
dense layer, an expert layer's norms and residual; under `moe_shared`
AFMoE's shared expert. Device self time of the profiled slice's
operations that the live engine's own table of instruction -> scope
(`tracing.step_op_scopes()`) puts under these scopes, over the mixed
steps of the slice (`harness/device_scopes.py`); an operation named
after a Pallas kernel is left out, the `kernels.*` metrics hold it. None
where the program gives no table (before PR 35), where the table is not
the running executable's, or where the step sets none of these
scopes."""
from harness import device_scopes

SCOPES = ("mlp", "moe_shared")


def read(ctx):
    return device_scopes.ms_per_step(ctx, *SCOPES)
