"""Self time of the batch closure over the device's busy time in the
traced window, in %: ops whose innermost program scope is `hkv.closure`
(`core/merge.py`, `u64.lexsort`; `layers.py`)."""


def read(ctx):
    if ctx.layers is None:
        return None
    return ctx.layers.shares().get("closure.self_share")
