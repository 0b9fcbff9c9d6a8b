"""Share of the dispatched prefill positions that are padding:
``prefill.padding_share``'s reading, in a cell whose prompts of 2,560 to
12,000 tokens go in buckets of 4,096, 8,192 and 12,544."""


def read(ctx):
    return ctx["cell"].reader("prefill.padding_share")(ctx)
