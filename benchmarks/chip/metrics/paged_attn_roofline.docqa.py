"""Roofline share of the paged decode attention kernel, in %."""
from chipbench import layer


def read(ctx):
    return layer.paged_attn_roofline(ctx)
