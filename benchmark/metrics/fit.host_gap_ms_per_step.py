"""Wall time per step minus device busy time per step, in the traced part
of the window: what the fit loop and the upload leave the chip waiting."""
from benchmark.metrics._common import TRAIN_STEP


def read(ctx):
    tr = ctx["trace"]
    steps = len(tr.module_runs(TRAIN_STEP))
    if not steps:
        return None
    return (tr.window_s() - tr.busy_s()) / steps * 1e3
