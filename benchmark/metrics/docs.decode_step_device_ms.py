"""Device busy time per decode dispatch, from the trace:
``decode_step.device_ms``'s reading, in a cell of 16 rows whose contexts
are 3,000 to 8,192 positions."""


def read(ctx):
    return ctx["cell"].reader("decode_step.device_ms")(ctx)
