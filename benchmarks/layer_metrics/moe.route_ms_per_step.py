"""Layer `moe`: the expert layer AROUND its kernel, per mixed step: the
router (`moe_router`: scores, top-k, weights) and what of the scope
`moe_experts` is not the kernel `moe_experts` (the sort by expert,
gathers, scatters, the weighted combine, the counters). Device self time
of the profiled slice's operations that the live engine's own table of
instruction -> scope (`tracing.step_op_scopes()`) puts under these
scopes, over the mixed steps of the slice (`harness/device_scopes.py`);
an operation named after a Pallas kernel is left out, the `kernels.*`
metrics hold it. None where the program gives no table (before PR 35),
where the table is not the running executable's, or where the step sets
none of these scopes."""
from harness import device_scopes

SCOPES = ("moe_router", "moe_experts")


def read(ctx):
    return device_scopes.ms_per_step(ctx, *SCOPES)
