"""``mfu.peer.moe``: the peer step's model FLOPs over the traced window,
as a share of the chip's bf16 peak, for a latent-attention and expert
configuration (``moe_yardstick``: the held experts' work counted at its
expectation, k·n/E of each token's picks)."""


def read(ctx):
    if not ctx or not ctx.get("steps") or "block_ms" not in ctx:
        return None
    rate = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / ctx["peak_flops"]
