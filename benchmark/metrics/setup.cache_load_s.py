"""Seconds of set-up spent loading programs from the persistent compile
cache: the backend seconds of the compile ledger's set-up entries (ended
before the trace began) with outcome ``loaded``."""
from deeplearning4j_tpu.monitoring.events import global_event_log


def read(ctx):
    if not ctx.get("trace_interval"):
        return None
    built = [e.attrs for e in global_event_log().tail(category="compile")
             if e.attrs["end"] < ctx["trace_interval"][0]]
    if not built:
        return None
    return sum(b["backend_s"] for b in built if b["outcome"] == "loaded")
