"""Share of the cache positions whose attention scores were computed
that the selection had not kept: 1 - selected over attended, from
``health()["sparse_attn"]`` at both ends of the window."""
from benchmark.metrics._spans import health_delta


def read(ctx):
    selected = health_delta(ctx, "sparse_attn", "selected_positions")
    attended = health_delta(ctx, "sparse_attn", "attended_positions")
    if selected is None or not attended:
        return None
    return 100.0 * (1.0 - selected / attended)
