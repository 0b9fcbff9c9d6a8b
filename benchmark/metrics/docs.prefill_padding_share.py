"""Share of the dispatched prefill positions that are padding:
``prefill.padding_share``'s reading, in a cell whose prompts of 3,072 to
8,000 tokens go in buckets of 4,096 and 8,192."""


def read(ctx):
    return ctx["cell"].reader("prefill.padding_share")(ctx)
