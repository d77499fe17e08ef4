"""Share of admitted prompt tokens served from cached pages, in %."""
from chipbench import layer


def read(ctx):
    return layer.prefix_hit_rate(ctx)
