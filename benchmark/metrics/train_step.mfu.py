"""The whole step's share of the chip's peak: forward + backward
operations per sample (the ``train_flops`` of the configuration's
reference file) x samples/s over chips x peak."""


def read(ctx):
    rate = ctx["record"]["end_to_end"].get("train_samples_per_s")
    if not rate:
        return None
    need = ctx["cell"].reference().train_flops(ctx["config"]) * rate
    return 100.0 * need / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
