"""Layer `device`: share of the profiled slice's operation time that
has a NAME from the layer map: an operation under any scope of
`tracing.DEVICE_SCOPES`, by the live engine's own table of instruction
-> scope (`tracing.step_op_scopes()`), or named after a Pallas kernel.
100 less it is what no layer metric can reach. Logs the whole table:
ms a mixed step by scope, largest first, with the three largest
instructions of each (`harness/device_scopes.py`). None where the
program gives no table (before PR 35) or the table is not the running
executable's."""
from harness import device_scopes


def read(ctx):
    found = device_scopes.of(ctx)
    if found is None:
        return None
    total = found.total_ms
    ctx.log(f"device time by scope: {total:.3f} ms a mixed step in "
            f"{found.steps:.0f} steps of the slice; the engine's table "
            f"knows {100 * found.known:.1f}% of it ({found.table_s:.1f} s "
            "to lower the step and load its executable, after the "
            "window)")
    for scope, ms, top in found.rows():
        ctx.log(f"  {ms:8.3f} ms  {scope}: " + ", ".join(
            f"{name} {t:.3f}" for name, t in top))
    return 100.0 * (1.0 - found.ms(device_scopes.NONE) / total)
