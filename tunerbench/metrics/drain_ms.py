"""Ingest drain of the tick: wall time of the program's ``tuner.drain``
spans (the due-job loop with its causal-filter round trips) that start
inside a ``bench.tick`` span, per tick."""
from tunerbench import spans


def read(ctx):
    prog = spans.of(ctx)
    return None if prog is None else prog.ms_per_tick("tuner.drain")
