"""Median, per verdict pull in the traced window, of its end less the end of
the pulled chunk's consume execution on the device (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes).get("pull_after_ready_ms")
