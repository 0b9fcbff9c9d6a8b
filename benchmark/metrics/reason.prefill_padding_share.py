"""Share of the dispatched prefill positions that are padding:
``prefill.padding_share``'s reading, in a cell whose prompts of 256 to
2,048 tokens go in buckets of 256, 512, 1,024 and 2,048."""


def read(ctx):
    return ctx["cell"].reader("prefill.padding_share")(ctx)
