"""Device time of a mixed step by what the step was doing: the profiled
slice's operations (`ctx.trace.ops`: instruction name -> self seconds)
laid over the live engine's own table of instruction -> scope
(`paddle_tpu.serving.tracing.step_op_scopes()`: the compiled step's HLO
metadata searched for the names of `tracing.DEVICE_SCOPES`, the
`jax.named_scope`s every operation of the step is traced under). A v5e
trace's events carry an instruction's name and no `op_name`, so the
table is the program's to give.

An operation named after a Pallas kernel (`KERNELS`) goes to `KERNEL`
whatever scope it was traced under: the `kernels.*` metrics hold those,
and a scope's sum here is what is left AROUND its kernel. What the
table does not know, or knows under no scope, is `NONE`.

Nothing is returned (every reader then returns None and the result line
leaves its metric out) where the program has no such table (a program
before PR 35), where no mixed step ran in the slice, or where under
`KNOWN_SHARE` of the slice's operation time is of instructions the
table knows: a table of another executable than the one that ran.
"""
from __future__ import annotations

import dataclasses
import time

KERNELS = ("paged_ragged", "moe_experts", "gated_delta")
KERNEL, NONE = "kernel", "(none)"
PROGRAM = "serving_mixed_step"
KNOWN_SHARE = 0.9


@dataclasses.dataclass
class Split:
    steps: float            # mixed steps of the slice, a chip
    known: float            # share of the op time the table knows
    ops: dict               # scope -> {instruction: ms a step}
    table_s: float = 0.0    # what getting the engines' tables took

    def ms(self, *scopes):
        """ms a step under `scopes` (kernel operations left out)."""
        return sum(sum(self.ops.get(s, {}).values()) for s in scopes)

    @property
    def total_ms(self):
        return self.ms(*self.ops)

    def has(self, *scopes):
        return any(s in self.ops for s in scopes)

    def rows(self, top=3):
        """[(scope, ms a step, its `top` largest instructions)],
        largest scope first."""
        out = [(s, sum(o.values()),
                sorted(o.items(), key=lambda kv: -kv[1])[:top])
               for s, o in self.ops.items()]
        return sorted(out, key=lambda r: -r[1])


def split(ops, table, steps, chips=1):
    """`ops`: {instruction: [self seconds, calls]} over `chips` chips;
    `table`: {instruction: scope}; `steps`: mixed steps a chip. -> a
    `Split`, or None (see the module's docstring)."""
    total = sum(v[0] for v in ops.values())
    if not table or not steps or total <= 0:
        return None
    known = sum(v[0] for k, v in ops.items() if k in table) / total
    if known < KNOWN_SHARE:
        return None
    by = {}
    for name, (seconds, _) in ops.items():
        scope = KERNEL if any(k in name for k in KERNELS) \
            else table.get(name, NONE)
        by.setdefault(scope, {})[name] = \
            seconds * 1e3 / steps / max(chips, 1)
    return Split(steps=steps, known=known, ops=by)


def tables():
    """{engine name: its table} of the live engines; {} on a program
    that cannot give one."""
    try:
        from paddle_tpu.serving import tracing
    except ImportError:
        return {}
    give = getattr(tracing, "step_op_scopes", None)
    return give() if give else {}


def of(ctx):
    """The `Split` of a run's context (made once, kept on it), by the
    live engine whose table knows most of the slice; or None."""
    if not hasattr(ctx, "_device_scopes"):
        steps = ctx.trace.calls_of(PROGRAM, "modules")
        t0 = time.monotonic()
        found = [split(ctx.trace.ops, table, steps, ctx.trace.chips)
                 for table in tables().values()] if steps else []
        found = [s for s in found if s is not None]
        best = max(found, key=lambda s: s.known, default=None)
        if best is not None:
            best.table_s = time.monotonic() - t0
        ctx._device_scopes = best
    return ctx._device_scopes


def ms_per_step(ctx, *scopes):
    """What a reader of scope sums returns: ms a step under `scopes`,
    or None where there is no split or the program sets none of
    them."""
    found = of(ctx)
    if found is None or not found.has(*scopes):
        return None
    return found.ms(*scopes)
