"""``idle_share.peer``: the share of the traced window in which no
operation ran on the device (``tracecut``), during peer steps."""


def read(ctx):
    if not ctx or not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
