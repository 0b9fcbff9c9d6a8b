"""How much of a layer's expert weights a decode step needs: over the
layers' calls of one position a row in the window, the held experts that
got at least one token (``decode_experts_touched``) over the experts held
(``num_experts`` a call, ``decode_calls``), from ``health()["experts"]``
at both ends. Nothing where the program has no such counters."""
from benchmark.metrics._spans import health_delta


def read(ctx):
    touched = health_delta(ctx, "experts", "decode_experts_touched")
    calls = health_delta(ctx, "experts", "decode_calls")
    if touched is None or not calls:
        return None
    return 100.0 * touched / (calls * ctx["config"]["num_experts"])
