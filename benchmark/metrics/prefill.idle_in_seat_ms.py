"""Device-idle time in admission's bookkeeping around a prime
(``engine.admit`` before it, ``engine.seat`` after it) per prime in the
trace."""
from benchmark.metrics._spans import idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], ("engine.admit", "engine.seat"),
                       "engine.seat")
