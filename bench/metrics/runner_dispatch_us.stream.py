"""Median host time of the program's ``ace:stream/consume`` span, the
dispatch of one chunk, in the traced window (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes).get("runner_dispatch_us")
