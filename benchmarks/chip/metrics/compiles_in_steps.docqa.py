"""Compiles inside the traced engine steps."""
from chipbench import engine_trace


def read(ctx):
    return engine_trace.compiles_in_steps(ctx)
