"""Device busy time per decode dispatch, from the trace:
``decode_step.device_ms``'s reading, in a cell of 32 rows whose step
updates a float32 state a slot in 12 layers and reads pages in 4."""


def read(ctx):
    return ctx["cell"].reader("decode_step.device_ms")(ctx)
