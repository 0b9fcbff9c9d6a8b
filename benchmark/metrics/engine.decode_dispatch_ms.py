"""Mean host-side time of a decode dispatch over the window, from the
engine's own counters (``health()["decode_dispatch"]`` at both ends)."""


def read(ctx):
    s = ctx["record"]["serve"]
    d0, d1 = (s[k]["decode_dispatch"] for k in ("health0", "health1"))
    n = d1["count"] - d0["count"]
    if n <= 0:
        return None
    return (d1["count"] * d1["mean_ms"] - d0["count"] * d0["mean_ms"]) / n
