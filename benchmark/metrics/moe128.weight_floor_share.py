"""How much of a decode step the experts it needs cost at the least: the
mean number of experts that got a token in a layer's decode call over the
window (``decode_experts_touched`` over ``decode_calls``, from
``health()["experts"]`` at both ends), times the layers that route, times
the bytes of one expert's matrices (the reference's ``expert_bytes``),
over the chip's HBM bandwidth, as a share of the traced decode
dispatches' mean device time. The needed experts' own floor, not a
kernel's roofline — the step also reads attention, head and pages — and
it reads the same work whatever implements the product: a product that
skips idle experts moves it towards 100 and cannot pass it."""
from benchmark.metrics._common import stream_programs
from benchmark.metrics._spans import health_delta


def read(ctx):
    ref = ctx["cell"].reference()
    count = getattr(ref, "expert_bytes", None)
    touched = health_delta(ctx, "experts", "decode_experts_touched")
    calls = health_delta(ctx, "experts", "decode_calls")
    if count is None or touched is None or not calls:
        return None
    decode, _ = stream_programs(ctx["trace"])
    busy = ctx["trace"].busy_within(decode) if decode else 0.0
    if not busy:
        return None
    cfg = ctx["config"]
    layers = sum(ref.routes(cfg, n) for n in range(cfg["num_hidden_layers"]))
    floor_s = touched / calls * layers * count(cfg) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (busy / len(decode))
