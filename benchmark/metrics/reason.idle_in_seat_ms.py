"""Device-idle time in admission's bookkeeping around a prime
(``engine.admit`` before it, ``engine.seat`` after it) per prime in the
trace: ``prefill.idle_in_seat_ms``'s reading, in a cell whose seat moves
a whole state row (27 MB) into the arena beside the page table."""


def read(ctx):
    return ctx["cell"].reader("prefill.idle_in_seat_ms")(ctx)
