"""Verdict kernel ``dtw_score_offline_{3,6}ch``: 100 x the least time its
work (``work/dtw_score_offline.py``) needs on the chip's peaks, over the
device time of its events."""
from tunerbench import layers


def read(ctx):
    return layers.verdict_kernel_roofline(ctx)
