"""Share of the rows the grouped expert product computed that held no
(token, held expert) pair: 1 - pairs routed over rows computed (whole
tiles), from ``health()["experts"]`` at both ends of the window."""
from benchmark.metrics._spans import health_delta


def read(ctx):
    pairs = health_delta(ctx, "experts", "held_pairs")
    rows = health_delta(ctx, "experts", "rows_computed")
    if pairs is None or not rows:
        return None
    return 100.0 * (1.0 - pairs / rows)
