"""Device time of the tick programs (``jit__scored_kernel_tick*``) per
tick and per chip: their time summed over the traced devices, over the
ticks and the device count, comparable with a one-chip cell's
``tick_device_ms``."""
from tunerbench import layers


def read(ctx):
    ms = layers.tick_device_ms(ctx)
    return None if ms is None else ms / ctx.trace.devices
