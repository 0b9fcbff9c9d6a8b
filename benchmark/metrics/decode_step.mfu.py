"""The served model's share of the chip's peak over the whole window:
operations of every token processed (prefills whose first token fell in
the window, and every decode token stamped in it) over window x peak."""
from benchmark.metrics._common import (decode_contexts_in,
                                       requests_with_first_token_in)


def read(ctx):
    replay = ctx["record"]["serve"]["replay"]
    cfg, win = ctx["config"], (replay.t0, replay.t1)
    ref = ctx["cell"].reference()
    need = sum(ref.prefill_flops(cfg, len(r.prompt))
               for r in requests_with_first_token_in(ctx, win))
    need += sum(ref.decode_flops(cfg, c)
                for c in decode_contexts_in(ctx, win))
    if not need:
        return None
    return 100.0 * need / (ctx["record"]["window_s"]
                           * ctx["peaks"]["bf16_flops_per_s"])
