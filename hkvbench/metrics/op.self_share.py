"""Self time of the op-dispatch layer over the device's busy time in the
traced window, in %: ops whose innermost program scope is `hkv.op.*` or
`hkv.emb.*` (`core/ops.py`, `HKVEmbedding`; `layers.py`)."""


def read(ctx):
    if ctx.layers is None:
        return None
    return ctx.layers.shares().get("op.self_share")
