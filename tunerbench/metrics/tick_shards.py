"""Shard fan-out of the tick: the mean ``shards`` argument (devices the
dispatch fans over) of the program's ``tuner.dispatch`` spans that start
inside a ``bench.tick`` span.  A program whose spans lack the argument
gives nothing to read."""
from tunerbench import spans


def read(ctx):
    prog = spans.of(ctx)
    if prog is None:
        return None
    vals = [float(sp[3]["shards"]) for sp in prog.in_ticks("tuner.dispatch")
            if "shards" in sp[3]]
    return sum(vals) / len(vals) if vals else None
