"""Active rows per decode dispatch over the arena's slots, from the
engine's counters (``health()["decode_dispatch"]`` at both ends)."""
from benchmark.metrics._spans import health_delta


def read(ctx):
    rows = health_delta(ctx, "decode_dispatch", "rows")
    n = health_delta(ctx, "decode_dispatch", "count")
    if rows is None or not n:
        return None
    slots = ctx["record"]["serve"]["health1"]["slots"]
    return 100.0 * rows / (n * slots)
