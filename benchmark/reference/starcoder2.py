"""Plain reference for the ``starcoder2-3b`` configuration: the decoder's
forward pass in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, no cache, no kernels, no batching. Imports nothing of the
program.

StarCoder2 (bigcode/starcoder2-3b config.json): token embedding, then per
layer pre-LayerNorm -> grouped-query causal attention with rotary
positions (rotate-half pairs i, i + D/2) -> residual, pre-LayerNorm ->
Linear, tanh-GELU, Linear -> residual; a final LayerNorm and a linear head.
Departures are the configuration's ``assumed`` and ``departures`` (untied
head with a bias, an embedding bias, full causal attention at contexts up
to 4,096 where the 4,096 window is the same function).

``low`` is the 8-bit control as ``reference/quant.py`` defines it: both
operands of every product (projections, FFN, QK, PV, head) and every
tensor an op hands on (the embedding, each product's result, the residual
stream after each sum) rounded to float8; the head's logits stay float32.

``prefill_flops`` and ``decode_flops`` are the operation counts the shares
of the peak are taken from (the ``*.mfu`` readers find them here, by the
configuration's ``model``).

The model is walked layer by layer (one small jitted function per layer,
weights cast up from their stored bfloat16 one layer at a time), so that it
fits beside nothing else on a 16 GB chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.flops import (  # noqa: F401
    starcoder2_decode_flops as decode_flops,
    starcoder2_prefill_flops as prefill_flops)
from benchmark.reference.quant import stored

HI = lax.Precision.HIGHEST


def param_specs(cfg):
    """(name, shape, mean, std). Layouts are those of a kernel-1
    convolution ([out, in, 1]) for the embedding and the FFN, and
    [in, out] for the attention projections and the head."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    i = cfg["intermediate_size"]

    def xavier(a, b):
        return math.sqrt(2.0 / (a + b))

    def ln(name):
        return [(f"{name}/gamma", (e,), 1.0, 0.02),
                (f"{name}/beta", (e,), 0.0, 0.02)]

    # token embeddings at the scale trained ones have (0.05 a channel),
    # well above the constant the embedding bias adds to every position:
    # a stream the same at every position would serve one token for ever
    specs = [("embed/W", (e, v, 1), 0.0, 0.05),
             ("embed/b", (e,), 0.0, 0.005)]
    for n in range(cfg["num_hidden_layers"]):
        specs += ln(f"ln{n}a")
        for p, (a, b) in (("q", (e, q)), ("k", (e, kv)), ("v", (e, kv)),
                          ("o", (q, e))):
            specs.append((f"attn{n}/W{p}", (a, b), 0.0, xavier(a, b)))
            specs.append((f"attn{n}/b{p}", (b,), 0.0, 0.02))
        specs += ln(f"ln{n}b")
        specs += [(f"ffn{n}a/W", (i, e, 1), 0.0, xavier(e, i)),
                  (f"ffn{n}a/b", (i,), 0.0, 0.02),
                  (f"ffn{n}b/W", (e, i, 1), 0.0, xavier(i, e)),
                  (f"ffn{n}b/b", (e,), 0.0, 0.02)]
    specs += ln("ln_f")
    specs += [("out/W", (e, v), 0.0, xavier(e, v)),
              ("out/b", (v,), 0.0, 0.02)]
    return specs


def _mm(x, w, b, low, keep_result=False):
    """x [.., a] @ w [a, b] + b in float32 at ``highest``; with ``low``
    both operands, and the result unless ``keep_result``, are handed on in
    8-bit floats."""
    y = jnp.matmul(stored(x, low), stored(w.astype(jnp.float32), low),
                   precision=HI) + b.astype(jnp.float32)
    return y if keep_result else stored(y, low)


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32))


def _rope(x, positions, base):
    """x [H, T, D]; pairs channel i with i + D/2."""
    half = x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv        # [T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "base", "low"))
def layer(x, p, *, heads, kv_heads, eps, base, low):
    """One decoder layer over one sequence, x [T, E] float32; ``p`` holds
    the layer's leaves under their short names."""
    t, e = x.shape
    d = p["Wq"].shape[1] // heads
    h = _layer_norm(x, p["ln_a_gamma"], p["ln_a_beta"], eps)

    def proj(name, n):
        y = _mm(h, p["W" + name], p["b" + name], low)
        return y.reshape(t, n, d).transpose(1, 0, 2)             # [n, T, D]

    pos = jnp.arange(t)
    q = _rope(proj("q", heads), pos, base)
    k = _rope(proj("k", kv_heads), pos, base)
    v = proj("v", kv_heads)
    reps = heads // kv_heads                 # query head j reads kv j // reps
    k = jnp.repeat(k, reps, axis=0)
    v = jnp.repeat(v, reps, axis=0)
    q, k = stored(q, low), stored(k, low)
    s = jnp.einsum("htd,hsd->hts", q, k, precision=HI) / math.sqrt(d)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    a, v = stored(a, low), stored(v, low)
    o = jnp.einsum("hts,hsd->htd", a, v, precision=HI)
    o = o.transpose(1, 0, 2).reshape(t, heads * d)
    x = stored(x + _mm(o, p["Wo"], p["bo"], low), low)
    h = _layer_norm(x, p["ln_b_gamma"], p["ln_b_beta"], eps)
    h = _mm(h, p["ffn_a_W"][:, :, 0].T, p["ffn_a_b"], low)
    h = jax.nn.gelu(h, approximate=True)
    h = _mm(h, p["ffn_b_W"][:, :, 0].T, p["ffn_b_b"], low)
    return stored(x + h, low)


@functools.partial(jax.jit, static_argnames=("low",))
def embed(ids, w, b, *, low=False):
    """Token lookup: row ``id`` of the [V, E] embedding, plus its bias."""
    return stored(jnp.take(w[:, :, 0], ids, axis=1).T.astype(jnp.float32)
                  + b.astype(jnp.float32), low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def head(x, gamma, beta, w, b, *, eps, low):
    h = _layer_norm(x, gamma, beta, eps)
    return _mm(h, w, b, low, keep_result=True)


def layer_params(params, n):
    p = {k: params[f"attn{n}/{k}"] for k in
         ("Wq", "bq", "Wk", "bk", "Wv", "bv", "Wo", "bo")}
    p.update(ln_a_gamma=params[f"ln{n}a/gamma"],
             ln_a_beta=params[f"ln{n}a/beta"],
             ln_b_gamma=params[f"ln{n}b/gamma"],
             ln_b_beta=params[f"ln{n}b/beta"],
             ffn_a_W=params[f"ffn{n}a/W"], ffn_a_b=params[f"ffn{n}a/b"],
             ffn_b_W=params[f"ffn{n}b/W"], ffn_b_b=params[f"ffn{n}b/b"])
    return p


def logits_at(cfg, params, ids, positions, low=False):
    """Logits [len(positions), V] that follow ``ids[:p + 1]`` for each p in
    ``positions``, from one causal pass over the whole of ``ids`` (padded
    by the caller to a shared length if it wants one compile)."""
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              eps=cfg["norm_epsilon"], base=cfg["rope_theta"], low=low)
    x = embed(jnp.asarray(ids, jnp.int32), params["embed/W"],
              params["embed/b"], low=low)
    for n in range(cfg["num_hidden_layers"]):
        x = layer(x, layer_params(params, n), **kw)
    x = x[jnp.asarray(positions, jnp.int32)]
    return head(x, params["ln_f/gamma"], params["ln_f/beta"],
                params["out/W"], params["out/b"],
                eps=cfg["norm_epsilon"], low=low)
