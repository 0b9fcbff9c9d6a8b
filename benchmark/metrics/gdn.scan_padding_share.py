"""Share of the positions the primes' chunked scans went over that held
no token: 1 - fed positions over scanned positions, from
``health()["linear_attn"]`` at both ends of the window. Left pads of a
bucket are whole chunks a scan could skip; the fill to a whole chunk is
not."""
from benchmark.metrics._spans import health_delta


def read(ctx):
    fed = health_delta(ctx, "linear_attn", "fed_positions")
    scanned = health_delta(ctx, "linear_attn", "scanned_positions")
    if fed is None or not scanned:
        return None
    return 100.0 * (1.0 - fed / scanned)
