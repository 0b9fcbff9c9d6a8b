"""The served model's share of the chip's peak over the whole window: the
operations of every prompt whose first token fell in the window
(``prefill_flops``) and of every decode token stamped in it
(``decode_flops``), as the configuration's reference counts them — index
scores over every earlier position, attention over the selected ones, 8
experts a token whatever computes them — over window x peak: the count
``decode_step.mfu`` makes, with this configuration's functions."""


def read(ctx):
    return ctx["cell"].reader("decode_step.mfu")(ctx)
