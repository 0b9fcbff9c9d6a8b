"""Median wait between send and admission: ``sched.queue_wait_p50_s``'s
reading, in a closed loop whose every request finds its slot free and
waits for the primes ahead of it."""


def read(ctx):
    return ctx["cell"].reader("sched.queue_wait_p50_s")(ctx)
