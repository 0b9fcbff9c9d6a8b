"""Device-idle time inside ``engine.sample`` (the per-row draw, push,
retire) per such span."""
from benchmark.metrics._spans import SAMPLE, idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], SAMPLE, "engine.sample")
