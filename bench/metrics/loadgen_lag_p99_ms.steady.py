"""99th percentile of how late the load generator submitted a request:
actual submit time less scheduled arrival, over every request due in the
window."""

from bench import common


def read(ctx):
    lag = ctx.samples.get("loadgen_lag_s")
    return 1e3 * common.nearest_rank(lag, 0.99) if lag else None
