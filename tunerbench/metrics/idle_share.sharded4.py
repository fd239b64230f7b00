"""Device idle share of the traced window on several chips: the mean
over the chips of 100 x (1 - that chip's own busy time / window length),
each chip's busy time the union of its own op intervals
(``chips.py``)."""
from tunerbench import chips


def read(ctx):
    busy = chips.of(ctx)
    window = ctx.trace.window_s
    if not busy or window <= 0:
        return None
    return 100.0 * float(sum(1.0 - b / window for b in busy.values())
                         / len(busy))
