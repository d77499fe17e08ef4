"""Roofline share of the packed LUT-Q matmul kernel, in %."""
from chipbench import layer


def read(ctx):
    return layer.lutq_dot_roofline(ctx)
