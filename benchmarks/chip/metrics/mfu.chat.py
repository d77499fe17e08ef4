"""Model FLOPs utilization of the traced engine steps, in %."""
from chipbench import layer


def read(ctx):
    return layer.mfu(ctx)
