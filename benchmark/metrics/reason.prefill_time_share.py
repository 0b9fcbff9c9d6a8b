"""Share of the window the engine spent inside admissions' prefill:
``engine.prefill_time_share``'s reading, in a cell where a prime holds
all 32 streams still about twice a second."""


def read(ctx):
    return ctx["cell"].reader("engine.prefill_time_share")(ctx)
