"""Layer `mixed_step`: how many host arrays the engine hands the device
for one dispatch of the mixed step: the mean of the flight records'
`h2d_arrays` over the window (the packed plan is one; penalty counts and
a device loop's control tail are one each). Logs the mean `h2d_bytes`
beside it. None where the program records neither (a program that
uploads field by field does not count its uploads)."""


def read(ctx):
    recs = [r for r in ctx.flight if "h2d_arrays" in r]
    if not recs:
        return None
    ctx.log("uploads: %.1f bytes a step in %.2f arrays, over %d steps" % (
        sum(r.get("h2d_bytes", 0) for r in recs) / len(recs),
        sum(r["h2d_arrays"] for r in recs) / len(recs), len(recs)))
    return sum(r["h2d_arrays"] for r in recs) / len(recs)
