"""Score pull of the tick: wall time of the program's ``tuner.pull``
spans (the ``[S, K]`` scores gathered from the chips to the host and
scattered to bank columns) that start inside a ``bench.tick`` span, per
tick."""
from tunerbench import spans


def read(ctx):
    prog = spans.of(ctx)
    return None if prog is None else prog.ms_per_tick("tuner.pull")
