"""Share of the window the engine spent inside admissions' prefill, from
the requests' traces (prefill_start to prefill_end)."""


def read(ctx):
    s = ctx["record"]["serve"]
    replay = s["replay"]
    total = sum(r.handle.trace().breakdown()["prefill_s"]
                for r in replay.requests
                if r.token_t and replay.in_window(r.token_t[0]))
    return 100.0 * total / ctx["record"]["window_s"]
