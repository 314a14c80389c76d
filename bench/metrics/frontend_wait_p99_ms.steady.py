"""99th percentile of a served request's wait in the front end: from its
scheduled arrival to the start of the admit call that served it."""

from bench import common


def read(ctx):
    wait = ctx.samples.get("frontend_wait_s")
    return 1e3 * common.nearest_rank(wait, 0.99) if wait else None
