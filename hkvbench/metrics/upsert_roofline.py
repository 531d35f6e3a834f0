"""Share of the HBM roofline an insert of new keys reaches: the bytes its
semantics need for the keys completed in the traced window
(`bytes/upsert.py`), over the chip's peak bandwidth times the device's busy
time in that window."""

from hkvbench import roofline


def read(ctx):
    return roofline.share(ctx, "upsert")
