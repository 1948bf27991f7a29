"""``device_ms.peer.mla``: device self time per step under the ``mla``
scope (latent attention, forward and backward), ms."""


def read(ctx):
    if not ctx or "block_ms" not in ctx:
        return None
    return ctx["block_ms"]["mla"]
