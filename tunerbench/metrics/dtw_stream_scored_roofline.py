"""Tick kernel ``dtw_stream_scored``: 100 x the least time its work
(``work/dtw_stream_scored.py``) needs on the chip's peaks, over the
device time of its events."""
from tunerbench import layers


def read(ctx):
    return layers.tick_kernel_roofline(ctx)
