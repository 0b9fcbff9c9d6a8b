"""Device busy time per decode dispatch, from the trace:
``decode_step.device_ms``'s reading, in a cell of 16 rows whose contexts
are 2,600 to 12,500 positions and whose step reads the experts' weights."""


def read(ctx):
    return ctx["cell"].reader("decode_step.device_ms")(ctx)
