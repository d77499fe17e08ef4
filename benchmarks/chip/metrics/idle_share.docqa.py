"""Device idle share of the traced window, in %."""
from chipbench import layer


def read(ctx):
    return layer.idle_share(ctx)
