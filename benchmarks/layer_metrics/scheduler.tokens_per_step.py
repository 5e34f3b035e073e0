"""Layer `scheduler`: tokens the scheduler packed into a mixed step,
prefill and decode together, averaged over the steps of the window
(flight recorder: `prefill_tokens`, `decode_tokens`)."""


def read(ctx):
    if not ctx.flight:
        return None
    tokens = sum(r.get("prefill_tokens", 0) + r.get("decode_tokens", 0)
                 for r in ctx.flight)
    return tokens / len(ctx.flight)
