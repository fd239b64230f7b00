"""Causal-filter dispatches of the ingest drain: the ``filtered`` count
of the program's ``tuner.drain`` spans that start inside a ``bench.tick``
span, summed, per tick."""
from tunerbench import spans


def read(ctx):
    prog = spans.of(ctx)
    return None if prog is None else \
        prog.arg_per_tick("tuner.drain", "filtered")
