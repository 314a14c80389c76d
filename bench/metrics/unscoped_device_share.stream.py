"""Share of the device's self time in the traced window spent in ops of no
``ace/`` scope (index checks, copies, the scan loop), in percent
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.readings(ctx.scopes).get("unscoped_device_share")
