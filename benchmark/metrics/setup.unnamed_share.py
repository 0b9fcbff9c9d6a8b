"""The share of ``setup_s`` that neither the compile ledger nor the
lead-in names: imports, backend start, weights, building the engine or the
net, and the warm-up's executions. 100 x (``setup_s`` - the ledger's
trace, lower and backend seconds of programs built before the engine
started (before the trace began where there is no engine) -
``setup.lead_in_s``) / ``setup_s``. The four parts add up to ``setup_s``;
two threads building at once would make this read low."""
from deeplearning4j_tpu.monitoring.events import global_event_log


def read(ctx):
    if not ctx.get("trace_interval"):
        return None
    end, lead_in = ctx["trace_interval"][0], 0.0
    if "serve" in ctx["record"]:
        end = ctx["record"]["serve"]["health0"].get(
            "setup", {}).get("started_at")
        lead_in = ctx["cell"].reader("setup.lead_in_s")(ctx)
        if end is None or lead_in is None:
            return None
    built = [e.attrs for e in global_event_log().tail(category="compile")
             if e.attrs["end"] < end]
    if not built:
        return None
    named = sum(b["trace_s"] + b["lower_s"] + b["backend_s"] for b in built)
    setup = ctx["record"]["setup_s"]
    return 100.0 * (setup - named - lead_in) / setup
