"""Seconds of set-up spent compiling: the backend seconds of every program
the compile ledger (``monitoring/runtime.py``, event category ``compile``)
saw built before the trace began with outcome ``compiled`` (the persistent
cache missed) or ``uncached``. No program is built in the window, so all
of them are set-up; the reference's, built after it, are left out."""
from deeplearning4j_tpu.monitoring.events import global_event_log


def read(ctx):
    if not ctx.get("trace_interval"):
        return None
    built = [e.attrs for e in global_event_log().tail(category="compile")
             if e.attrs["end"] < ctx["trace_interval"][0]]
    if not built:
        return None
    return sum(b["backend_s"] for b in built if b["outcome"] != "loaded")
