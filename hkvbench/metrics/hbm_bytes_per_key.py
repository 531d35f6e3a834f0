"""Device bytes in use after the window, with the benchmark's own buffers
dropped, over the keys the table holds."""


def read(ctx):
    if ctx.resident_keys <= 0:
        return None
    return ctx.bytes_in_use / ctx.resident_keys
