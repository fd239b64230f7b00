"""Device time of the tick programs (``jit__scored_kernel_tick*``: the
kernel, the layout copies around it and the score tail) per tick."""
from tunerbench import layers


def read(ctx):
    return layers.tick_device_ms(ctx)
