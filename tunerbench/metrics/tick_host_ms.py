"""Host path of the tick (ingest drain, chunk build, score pull,
decision rule): device-idle time inside ``bench.tick`` spans per tick."""
from tunerbench import layers


def read(ctx):
    return layers.tick_host_ms(ctx)
