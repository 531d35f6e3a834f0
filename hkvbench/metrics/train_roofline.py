"""Share of the HBM roofline a training step reaches: the bytes its
semantics need for the keys completed in the traced window
(`bytes/train.py`), over the chip's peak bandwidth times the device's busy
time in that window."""

from hkvbench import roofline


def read(ctx):
    return roofline.share(ctx, "train")
