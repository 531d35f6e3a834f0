"""Keys completed in the window over the window's length (host clock)."""


def read(ctx):
    w = ctx.window
    return w.keys / w.seconds if w.seconds > 0 else None
