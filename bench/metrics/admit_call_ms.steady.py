"""Median host time of a ``Guardrail.admit`` call, which ends on the
verdicts' transfer to the host, over the window."""

from bench import common


def read(ctx):
    calls = ctx.samples.get("admit_call_s")
    return 1e3 * common.median(calls) if calls else None
