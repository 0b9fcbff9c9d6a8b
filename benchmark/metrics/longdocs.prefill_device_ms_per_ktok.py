"""Device time of the primes the trace holds whole, per thousand tokens
they were fed: ``docs.prefill_device_ms_per_ktok``'s reading (whole
primes laid on their requests' records), in a cell whose primes of 2,560
to 12,000 positions go in buckets of 4,096, 8,192 and 12,544."""


def read(ctx):
    return ctx["cell"].reader("docs.prefill_device_ms_per_ktok")(ctx)
