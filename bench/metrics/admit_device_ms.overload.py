"""Device busy time per admit batch in the traced window."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    calls = t.span_counts.get("admit", 0)
    return 1e3 * t.busy_s / calls if calls and t.busy_s > 0 else None
