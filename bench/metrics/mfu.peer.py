"""``mfu.peer``: the peer step's model FLOPs over the traced window,
as a share of the chip's bf16 peak (``yardstick``). Model FLOPs are the
forward and backward the configuration requires; rematerialisation and
the DeMo codec do not count."""


def read(ctx):
    if not ctx or not ctx.get("steps"):
        return None
    rate = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / ctx["peak_flops"]
