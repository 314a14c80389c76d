"""Self time of the device ops in the program's ``ace/insert`` scope (the
scatter-add into the count table), per 1,000 items of the consume spans in
the traced window (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes).get("insert_device_us_per_kitem")
