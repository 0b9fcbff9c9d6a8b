"""The served model's share of the chip's peak over the whole window: the
operations of every prompt whose first token fell in the window
(``prefill_flops``) and of every decode token stamped in it
(``decode_flops``), as the configuration's reference counts them — the
delta rule at 6 dk dv a head a token whatever form computes it, causal
attention over live positions in the full layers alone — over window x
peak: the count ``decode_step.mfu`` makes, with this configuration's
functions."""


def read(ctx):
    return ctx["cell"].reader("decode_step.mfu")(ctx)
