"""Self time of the device ops in the program's ``ace/rotate`` scope (the
rotation of the ring: the expiring epoch zeroed, the tail recomputed and the
sum of squared counts re-anchored), per 1,000 items of the consume spans in
the traced window (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes).get("rotate_device_us_per_kitem")
