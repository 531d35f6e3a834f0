"""Device time of the benchmark's own programs over the device's busy
time in the traced window, in %: ops under no program scope (key and token
generators, `pick`, `jnp.uint32(i)`; `layers.py`)."""


def read(ctx):
    if ctx.layers is None:
        return None
    return ctx.layers.shares().get("client.device_share")
