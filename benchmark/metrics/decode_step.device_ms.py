"""Device busy time per decode dispatch, from the trace."""
from benchmark.metrics._common import stream_programs


def read(ctx):
    decode, _ = stream_programs(ctx["trace"])
    if not decode:
        return None
    return ctx["trace"].busy_within(decode) / len(decode) * 1e3
