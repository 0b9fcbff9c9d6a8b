"""Device time of the primes the trace holds whole, per thousand tokens
they were fed: ``docs.prefill_device_ms_per_ktok``'s reading (whole
primes laid on their requests' records), in a cell whose primes of 256
to 2,048 positions run the chunked scan in 12 layers of 16."""


def read(ctx):
    return ctx["cell"].reader("docs.prefill_device_ms_per_ktok")(ctx)
