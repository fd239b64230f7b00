"""Decision rule of the tick: wall time of the program's
``tuner.decide`` spans (per-workload reduce, rank and gates of every
touched job) that start inside a ``bench.tick`` span, per tick."""
from tunerbench import spans


def read(ctx):
    prog = spans.of(ctx)
    return None if prog is None else prog.ms_per_tick("tuner.decide")
