"""The paged-attention kernel against its roofline: the least time the
chip could take for the live keys and values the decode tokens of the
traced interval had to read (bytes over 819 GB/s, or operations over the
peak, whichever is longer), over the kernel's time in the trace. Tokens
stamped in the first quarter second are left out: their kernels may have
run before the trace began."""
from benchmark import flops
from benchmark.metrics._common import PAGED_KERNEL, decode_contexts_in


def read(ctx):
    tr, cfg, pk = ctx["trace"], ctx["config"], ctx["peaks"]
    kernel_s = tr.seconds_matching(PAGED_KERNEL)
    a, b = ctx["trace_interval"]
    contexts = decode_contexts_in(ctx, (a + 0.25, b))
    if not kernel_s or not contexts:
        return None
    layers = cfg["num_hidden_layers"]
    least = layers * max(
        flops.paged_attention_bytes(cfg, contexts) / pk["hbm_bytes_per_s"],
        flops.paged_attention_flops(cfg, contexts) / pk["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
