"""Self time of the device ops in the program's ``ace/score`` scope (the
table gather and the score), per 1,000 items of the consume spans in the
traced window (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes).get("score_device_us_per_kitem")
