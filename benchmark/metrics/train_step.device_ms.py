"""Device busy time per executed train step, from the trace."""
from benchmark.metrics._common import TRAIN_STEP


def read(ctx):
    tr = ctx["trace"]
    runs = tr.module_runs(TRAIN_STEP)
    if not runs:
        return None
    return tr.busy_within(runs) / len(runs) * 1e3
