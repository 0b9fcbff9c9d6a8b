"""Median time to first token beside the tail, client side."""
from benchmark.metrics._common import median


def read(ctx):
    return median(ctx["record"]["serve"]["ttfts"])
