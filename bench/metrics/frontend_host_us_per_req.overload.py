"""Host time spent in ``FrontEnd.submit`` and ``FrontEnd.pump`` outside the
admit call, per served request, over the window."""


def read(ctx):
    served = ctx.samples.get("served")
    if not served:
        return None
    return 1e6 * ctx.samples["frontend_host_s"] / served
