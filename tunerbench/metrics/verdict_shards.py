"""Shard fan-out of the verdicts: the mean ``shards`` argument of the
program's ``tuner.verdict.dispatch`` spans in the traced window.  A
program whose spans lack the argument gives nothing to read."""
from tunerbench import spans


def read(ctx):
    prog = spans.of(ctx)
    if prog is None:
        return None
    vals = [float(sp[3]["shards"]) for sp in prog.spans
            if sp[2] == "tuner.verdict.dispatch" and "shards" in sp[3]]
    return sum(vals) / len(vals) if vals else None
