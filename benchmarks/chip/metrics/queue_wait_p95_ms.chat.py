"""p95 of the wait from scheduled send to admission, in ms."""
from chipbench import layer


def read(ctx):
    return layer.queue_wait_p95_ms(ctx)
