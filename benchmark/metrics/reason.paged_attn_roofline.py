"""The paged-attention kernel against its roofline in this cell: the
least time the chip could take for the live keys and values the decode
tokens of the traced interval had to read in the FULL-attention layers
(bytes over 819 GB/s, or operations over the peak, whichever is longer),
over the kernel's time in the trace. ``paged_attn.roofline``'s reading
with this configuration's shapes: as many key-value heads as query heads,
of ``hidden_size / num_attention_heads``, and only the layers
``layer_types`` names ``full_attention`` among those built. Tokens
stamped in the first quarter second are left out: their kernels may have
run before the trace began. Nothing where no such kernel ran."""
from benchmark.metrics._common import PAGED_KERNEL, decode_contexts_in


def read(ctx):
    tr, cfg, pk = ctx["trace"], ctx["config"], ctx["peaks"]
    kernel_s = tr.seconds_matching(PAGED_KERNEL)
    a, b = ctx["trace_interval"]
    contexts = decode_contexts_in(ctx, (a + 0.25, b))
    if not kernel_s or not contexts:
        return None
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    layers = ctx["cell"].reference().layer_kinds(cfg).count(
        "full_attention")
    moved = sum(int(c) * 2 * kv * d * 2 + 2 * heads * d * 2
                for c in contexts)
    ops = sum(4 * heads * d * int(c) for c in contexts)
    least = layers * max(moved / pk["hbm_bytes_per_s"],
                         ops / pk["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
