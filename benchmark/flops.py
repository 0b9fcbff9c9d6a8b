"""Operation and byte counts, from shapes alone.

These count what the ALGORITHM needs (2 operations per multiply-add in the
convolutions and matrix products; recomputation, padding, one-hot products
and elementwise work are not counted), so a share of the peak computed from
them cannot be raised by doing more work, only by doing the work faster.
"""

from __future__ import annotations


# ---------------------------------------------------------------- ResNet50
def conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def conv_flops(h_out: int, w_out: int, kernel: int, c_in: int,
               c_out: int) -> int:
    return 2 * h_out * w_out * kernel * kernel * c_in * c_out


def resnet50_layers(cfg: dict):
    """Every convolution and the head as
    ``(name, h_out, w_out, kernel, c_in, c_out, needs_input_grad)``."""
    size = conv_out(cfg["image_size"], 7, 2, 3)
    out = [("stem", size, size, 7, cfg["channels"], cfg["stem_filters"],
            False)]
    size = conv_out(size, 3, 2, 1)                    # 3x3/2 max pool
    c_in = cfg["stem_filters"]
    for s, (reps, (f1, f2, f3), stride) in enumerate(zip(
            cfg["stage_blocks"], cfg["stage_filters"],
            cfg["stage_strides"])):
        for r in range(reps):
            st = stride if r == 0 else 1
            size_out = conv_out(size, 1, st, 0)
            n = f"s{s + 2}b{r}"
            out.append((n + "_a", size_out, size_out, 1, c_in, f1, True))
            out.append((n + "_b", size_out, size_out, 3, f1, f2, True))
            out.append((n + "_c", size_out, size_out, 1, f2, f3, True))
            if r == 0:
                out.append((n + "_skip", size_out, size_out, 1, c_in, f3,
                            True))
            size, c_in = size_out, f3
    out.append(("output", 1, 1, 1, c_in, cfg["num_classes"], True))
    return out


def resnet50_forward_flops(cfg: dict) -> int:
    """Per sample."""
    return sum(conv_flops(h, w, k, ci, co)
               for _, h, w, k, ci, co, _ in resnet50_layers(cfg))


def resnet50_train_flops(cfg: dict) -> int:
    """Forward + backward per sample: each layer's forward, its weight
    gradient and (except for the first layer, whose input is data) its
    input gradient."""
    total = 0
    for _, h, w, k, ci, co, dgrad in resnet50_layers(cfg):
        total += conv_flops(h, w, k, ci, co) * (3 if dgrad else 2)
    return total


# -------------------------------------------------------------- StarCoder2
def starcoder2_layer_matmul_flops(cfg: dict) -> int:
    """Per token, one layer, projections and FFN only."""
    h = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    i = cfg["intermediate_size"]
    return 2 * (h * q + 2 * h * kv + q * h + 2 * h * i)


def starcoder2_attention_flops(cfg: dict, keys: int) -> int:
    """One query token against ``keys`` keys, one layer: QK^T and PV."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def starcoder2_head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def starcoder2_prefill_flops(cfg: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens processed causally; the embedding is
    a lookup (0 operations) and only the last position needs logits."""
    layers = cfg["num_hidden_layers"]
    attn = starcoder2_attention_flops(cfg, 1) * prompt * (prompt + 1) // 2
    return (layers * (prompt * starcoder2_layer_matmul_flops(cfg) + attn)
            + starcoder2_head_flops(cfg))


def starcoder2_decode_flops(cfg: dict, context: int) -> int:
    """One generated token whose query sees ``context`` keys (itself
    included)."""
    return (cfg["num_hidden_layers"]
            * (starcoder2_layer_matmul_flops(cfg)
               + starcoder2_attention_flops(cfg, context))
            + starcoder2_head_flops(cfg))


def paged_attention_bytes(cfg: dict, contexts, kv_bytes: int = 2) -> int:
    """Bytes ONE layer's decode attention must move for a step over rows
    with ``contexts`` live keys each: every live key and value once, the
    query in and the output out. Depends on the work, not on page size or
    on how the kernel walks the table."""
    d = cfg["head_dim"]
    kv_row = 2 * cfg["num_key_value_heads"] * d * kv_bytes
    qo_row = 2 * cfg["num_attention_heads"] * d * kv_bytes
    return sum(int(c) * kv_row + qo_row for c in contexts)


def paged_attention_flops(cfg: dict, contexts) -> int:
    return sum(starcoder2_attention_flops(cfg, int(c)) for c in contexts)
