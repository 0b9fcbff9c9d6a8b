"""Share of the rows the grouped expert product computed that held no
(token, expert) pair, with every layer's 128 experts held whole:
``moe.expert_padding_share``'s reading (1 - pairs routed over rows
computed, from ``health()["experts"]`` at both ends of the window)."""


def read(ctx):
    return ctx["cell"].reader("moe.expert_padding_share")(ctx)
