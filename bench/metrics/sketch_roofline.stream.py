"""Share of its roofline that the stream's device work reaches: the least
time the chip could take for the items of the traced window (the larger of
their flops over peak flops and their bytes over peak bandwidth, from
``bench/work``), over the device busy time measured in it, in percent."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.peaks or t.busy_s <= 0:
        return None
    items = t.span_counts.get("consume", 0) * ctx.samples["items_per_chunk"]
    if not items:
        return None
    w = ctx.work.per_item(ctx.cfg)
    least = items * max(w["flops"] / ctx.peaks["flops_per_s"],
                        w["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t.busy_s
