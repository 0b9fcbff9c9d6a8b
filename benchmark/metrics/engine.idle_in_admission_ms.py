"""Device-idle time inside admission (``engine.admit``, ``prefill.*``,
``engine.seat``) per decode cycle."""
from benchmark.metrics._spans import ADMISSION, idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], ADMISSION, "decode.forward")
