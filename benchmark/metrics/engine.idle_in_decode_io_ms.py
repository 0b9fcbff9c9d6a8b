"""Device-idle time inside the decode dispatch's host round trip
(``decode.input``, ``decode.forward``, ``decode.fetch``) per decode
cycle."""
from benchmark.metrics._spans import DECODE_IO, idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], DECODE_IO, "decode.forward")
