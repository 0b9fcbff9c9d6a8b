"""Bytes of the numpy arrays handed to and fetched from ``rnn_time_step``
(decode and prefill, both ways) per output token of the window."""
from benchmark.metrics._spans import host_io_bytes


def read(ctx):
    nbytes = host_io_bytes(ctx)
    tokens = ctx["record"]["serve"]["tokens"]
    if nbytes is None or not tokens:
        return None
    return nbytes / tokens / 1e6
