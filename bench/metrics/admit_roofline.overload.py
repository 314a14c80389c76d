"""Share of its roofline that the admit batches' device work reaches: the
least time the chip could take for the rows of the admit calls in the traced
window (every row of a full batch; from ``bench/work``), over the device busy
time measured in it, in percent."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.peaks or t.busy_s <= 0:
        return None
    rows = t.span_counts.get("admit", 0) * ctx.samples["batch_rows"]
    if not rows:
        return None
    w = ctx.work.per_item(ctx.cfg)
    least = rows * max(w["flops"] / ctx.peaks["flops_per_s"],
                       w["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t.busy_s
