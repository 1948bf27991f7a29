"""``moe_experts_roofline``: the grouped expert matmuls' share of their
roofline. The least time the chip could take for the products they
execute (``moe_yardstick.grouped_work``: FLOPs of the held assignments
the program routed, bytes of the expert weights and the rows in and
out) is the larger of FLOPs over the bf16 peak and bytes over the HBM
bandwidth; the share is that over the self time under ``moe.experts``
per step."""


def read(ctx):
    if not ctx or "block_ms" not in ctx:
        return None
    secs = ctx["block_ms"]["moe.experts"] / 1e3
    if secs <= 0:
        return None
    work = ctx["experts"]
    least = max(work["flops"] / ctx["peak_flops"],
                work["bytes"] / ctx["peak_bw"])
    return 100.0 * least / secs
