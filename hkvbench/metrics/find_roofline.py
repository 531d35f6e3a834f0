"""Share of the HBM roofline the find path reaches: the bytes its
semantics need for the keys completed in the traced window
(`bytes/find.py`), over the chip's peak bandwidth times the device's busy
time in that window."""

from hkvbench import roofline


def read(ctx):
    return roofline.share(ctx, "find")
