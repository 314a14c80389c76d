"""Device busy time per 1,000 streamed items in the traced window: busy
seconds over the items of the chunks dispatched in it."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    items = t.span_counts.get("consume", 0) * ctx.samples["items_per_chunk"]
    return 1e9 * t.busy_s / items if items and t.busy_s > 0 else None
