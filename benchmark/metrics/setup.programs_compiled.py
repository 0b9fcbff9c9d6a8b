"""Programs set-up compiled rather than loaded: the compile ledger's
set-up entries (ended before the trace began) with outcome ``compiled``
or ``uncached``. A run behind a warm cache reads 0; above 0 there, a
program misses the cache on every run."""
from deeplearning4j_tpu.monitoring.events import global_event_log


def read(ctx):
    if not ctx.get("trace_interval"):
        return None
    built = [e.attrs for e in global_event_log().tail(category="compile")
             if e.attrs["end"] < ctx["trace_interval"][0]]
    if not built:
        return None
    return sum(b["outcome"] != "loaded" for b in built)
