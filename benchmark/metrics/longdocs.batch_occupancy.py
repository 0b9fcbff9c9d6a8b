"""Active rows per decode dispatch over the arena's 16 slots:
``engine.batch_occupancy``'s reading, in a closed loop of one client a
slot, where a row is empty only while its client's next prompt primes."""


def read(ctx):
    return ctx["cell"].reader("engine.batch_occupancy")(ctx)
