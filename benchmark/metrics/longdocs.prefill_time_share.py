"""Share of the window the engine spent inside admissions' prefill:
``engine.prefill_time_share``'s reading, in a cell where every prime
holds all 16 streams still."""


def read(ctx):
    return ctx["cell"].reader("engine.prefill_time_share")(ctx)
