"""The prefill's share of the chip's peak: operations the prompts of the
traced interval need over the device time of the prefill dispatches."""
from benchmark.metrics._common import traced_prefill


def read(ctx):
    busy, reqs = traced_prefill(ctx)
    if not busy or not reqs:
        return None
    prefill_flops = ctx["cell"].reference().prefill_flops
    need = sum(prefill_flops(ctx["config"], len(r.prompt)) for r in reqs)
    return 100.0 * need / (busy * ctx["peaks"]["bf16_flops_per_s"])
