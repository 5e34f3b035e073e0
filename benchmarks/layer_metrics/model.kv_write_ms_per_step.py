"""Layer `model`: the appends of a step's keys and values into the paged
pools, per mixed step: the scope `kv_write` (head padding, quantization
on append and block summaries included). Device self time of the
profiled slice's operations that the live engine's own table of
instruction -> scope (`tracing.step_op_scopes()`) puts under these
scopes, over the mixed steps of the slice (`harness/device_scopes.py`);
an operation named after a Pallas kernel is left out, the `kernels.*`
metrics hold it. None where the program gives no table (before PR 35),
where the table is not the running executable's, or where the step sets
none of these scopes."""
from harness import device_scopes

SCOPES = ("kv_write",)


def read(ctx):
    return device_scopes.ms_per_step(ctx, *SCOPES)
