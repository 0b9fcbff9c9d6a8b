"""Seconds of set-up spent tracing programs to jaxprs and lowering them to
MLIR modules (Mosaic's lowering of a Pallas kernel included): ``trace_s``
+ ``lower_s`` of the compile ledger's set-up entries (ended before the
trace began), whatever their outcome."""
from deeplearning4j_tpu.monitoring.events import global_event_log


def read(ctx):
    if not ctx.get("trace_interval"):
        return None
    built = [e.attrs for e in global_event_log().tail(category="compile")
             if e.attrs["end"] < ctx["trace_interval"][0]]
    if not built:
        return None
    return sum(b["trace_s"] + b["lower_s"] for b in built)
