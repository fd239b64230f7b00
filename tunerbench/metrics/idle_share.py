"""Device idle share of the traced window: 100 x (1 - union of the
device's op intervals / window length)."""
from tunerbench import layers


def read(ctx):
    return layers.idle_share(ctx)
