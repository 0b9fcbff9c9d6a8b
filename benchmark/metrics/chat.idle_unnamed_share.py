"""Device-idle time inside no span of the program, over all idle time."""
from benchmark.metrics._spans import idle_unnamed_share


def read(ctx):
    return idle_unnamed_share(ctx["trace"])
