"""Median wait between send and admission, from the requests' traces."""
from benchmark.metrics._common import median


def read(ctx):
    sent = ctx["record"]["serve"]["sent"]
    return median([r.handle.trace().breakdown()["queue_wait_s"]
                   for r in sent if r.token_t])
