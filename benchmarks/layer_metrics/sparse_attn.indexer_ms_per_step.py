"""Layer `sparse_attn`: the learned selection itself, per mixed step:
the indexer's three projections (`idx_proj`), its scores of every
candidate key (`idx_score`) and the exact top-k (`idx_select`: the
k-th largest score by bisection, the ties, the decode rows' index
lists). Device self time of the profiled slice's operations that the
live engine's own table of instruction -> scope
(`tracing.step_op_scopes()`) puts under these scopes, over the mixed
steps of the slice (`harness/device_scopes.py`); logs the three. None
where the program gives no table, where the table is not the running
executable's, or where the step sets none of these scopes (a program
without sparse layers, or before PR 37)."""
from harness import device_scopes

SCOPES = ("idx_proj", "idx_score", "idx_select")


def read(ctx):
    total = device_scopes.ms_per_step(ctx, *SCOPES)
    if total is not None:
        ctx.log("indexer, ms a step: " + ", ".join(
            f"{s} {device_scopes.of(ctx).ms(s):.3f}" for s in SCOPES))
    return total
