"""Padding among the prefill tokens of the traced steps, in %."""
from chipbench import engine_trace


def read(ctx):
    return engine_trace.prefill_pad_share(ctx)
