"""Median host time per chunk, from the H2D of its raw rows to the pull of
its verdicts, over the window."""

from bench import common


def read(ctx):
    calls = ctx.samples.get("consume_call_s")
    return 1e3 * common.median(calls) if calls else None
