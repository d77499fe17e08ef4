"""Host time of a traced engine step outside its device syncs, in ms."""
from chipbench import engine_trace


def read(ctx):
    return engine_trace.engine_host_ms(ctx)
