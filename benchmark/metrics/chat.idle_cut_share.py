"""Device-idle time before the first program span the trace holds or after
the last: it lay in a span the trace's start or end cut, over all idle
time."""
from benchmark.metrics._spans import idle_cut_share


def read(ctx):
    return idle_cut_share(ctx["trace"])
