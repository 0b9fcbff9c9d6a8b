"""Device-idle time inside the decode side of the cycle (``decode.*`` and
``engine.sample``) per decode cycle, where prefill does the work."""
from benchmark.metrics._spans import DECODE_IO, SAMPLE, idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], DECODE_IO + SAMPLE, "decode.forward")
