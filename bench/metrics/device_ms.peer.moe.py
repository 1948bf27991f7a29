"""``device_ms.peer.moe``: device self time per step under the expert
layer's own scopes (``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.combine``; forward and backward), ms. The shared experts'
``moe.shared`` is dense work and is left out."""

import moe_yardstick


def read(ctx):
    if not ctx or "block_ms" not in ctx:
        return None
    return sum(ctx["block_ms"][s] for s in moe_yardstick.MOE_SCOPES)
