"""Self time of the kernel wrappers over the device's busy time in the
traced window, in %: ops whose innermost program scope is `hkv.kernel_io`
(`kernels/ops.py`), the Pallas kernels they call left out (`layers.py`)."""


def read(ctx):
    if ctx.layers is None:
        return None
    return ctx.layers.shares().get("kernel_io.self_share")
