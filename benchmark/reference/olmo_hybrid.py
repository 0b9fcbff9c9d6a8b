"""Plain reference for the ``olmo-hybrid-7b`` configuration: the decoder's
forward pass in straightforward ``jax.numpy``, float32, ``highest`` matmul
precision, no cache, no chunks, no kernels, no batching. Imports nothing
of the program.

Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B config.json): token embedding,
then the layers ``layer_types`` names (the first ``num_hidden_layers`` of
them), each ``x <- x + RMSNorm(mix(x))``, ``x <- x + RMSNorm(ffn(x))``
(the family's placement: a branch's output is normalised, its input is
not); a final RMSNorm and an untied linear head. No biases.

``linear_attention`` — the gated delta rule, written as the recurrence it
is, one token after another (``lax.scan`` over positions). For a token
``x_t`` (``H`` heads, keys ``dk`` wide, values ``dv``):

    q, k, v, z = x Wq, x Wk, x Wv, x Wz           a, b = x Wa, x Wb
    (q, k, v)_t <- silu(sum_i conv[i] * (q, k, v)_{t-K+1+i})   (causal,
                                          depthwise, zeros before t = 0)
    q <- q / |q| * dk^-1/2,   k <- k / |k|                  (per head)
    beta = 2 sigmoid(b)              (2: linear_allow_neg_eigval)
    alpha = exp(-exp(A_log) softplus(a + dt_bias))
    S_t = alpha S_{t-1} + beta k (v - alpha S_{t-1}^T k)^T,  o = S_t^T q
    y = RMSNorm_dv(o) * norm * silu(z)            out = y Wo

``full_attention`` — ``H`` query and key-value heads of ``E / H``:
``q = RMSNorm(x Wq) q_norm``, ``k = RMSNorm(x Wk) k_norm`` over the whole
projected width, no rotation (``rope_parameters.rope_theta`` is null),
causal softmax of ``q . k / sqrt(D)``, ``o Wo``.

Both — ``ffn(x) = (silu(x Wg) * x Wu) Wd``.

``low`` is the 8-bit control as ``reference/quant.py`` defines it: both
operands of every product (the recurrence's ``S^T k``, ``S^T q`` and the
outer product among them) and every tensor an op hands on rounded to
float8; the state a stream carries stays float32, as the configuration
states (``departures.state_dtype``), and the head's logits stay wide.

The model is walked layer by layer (one jitted function a layer kind,
weights cast up from their stored bfloat16 one layer at a time), the full
layers' queries in blocks, so that 3,072 positions fit beside the
bfloat16 leaves of the whole share on a 16 GB chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.quant import stored

HI = lax.Precision.HIGHEST
#: queries a block of the full attention takes at once: [H, block, T]
QUERY_BLOCK = 1024
L2_EPS = 1e-6


def layer_kinds(cfg):
    """The kinds of the layers that are built: the first
    ``num_hidden_layers`` entries of ``layer_types``."""
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def mixer_name(kind: str, n: int) -> str:
    return ("gdn" if kind == "linear_attention" else "attn") + str(n)


def param_specs(cfg):
    """(name, shape, mean, std), ``name`` = ``<vertex>/<leaf>`` of the
    program's tree. Every matrix is [in, out] and drawn N(0, 1 / in)
    (``assumed.init``): a unit-RMS input stays at unit RMS. The two norms
    that close a block's branches have gains about 0.05, the scale of the
    token embedding, so that a branch writes into the residual stream as
    loudly as a token does."""
    e, v, i = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv, kw = cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]

    def mat(name, a, b):
        return (name, (a, b), 0.0, 1.0 / math.sqrt(a))

    def gain(name, n, mean=1.0):
        return (name, (n,), mean, 0.02 * mean)

    specs = [("embed/W", (v, e), 0.0, 0.05)]
    for n, kind in enumerate(layer_kinds(cfg)):
        m = mixer_name(kind, n)
        if kind == "linear_attention":
            specs += [mat(f"{m}/Wq", e, h * dk), mat(f"{m}/Wk", e, h * dk),
                      mat(f"{m}/Wv", e, h * dv), mat(f"{m}/Wz", e, h * dv),
                      mat(f"{m}/Wa", e, h), mat(f"{m}/Wb", e, h),
                      mat(f"{m}/Wo", h * dv, e),
                      (f"{m}/conv", (kw, h * (2 * dk + dv)), 0.0,
                       1.0 / math.sqrt(kw)),
                      (f"{m}/A_log", (h,), 0.0, 0.5),
                      (f"{m}/dt_bias", (h,), -3.0, 0.5),
                      gain(f"{m}/norm", dv)]
        else:
            specs += [mat(f"{m}/W{p}", e, e) for p in "qkvo"]
            specs += [gain(f"{m}/q_norm", e), gain(f"{m}/k_norm", e)]
        specs += [gain(f"norm{n}a/gamma", e, 0.05),
                  mat(f"ffn{n}/Wg", e, i), mat(f"ffn{n}/Wu", e, i),
                  mat(f"ffn{n}/Wd", i, e),
                  gain(f"norm{n}b/gamma", e, 0.05)]
    specs += [gain("norm_f/gamma", e), mat("out/W", e, v)]
    return specs


# ------------------------------------------------------------- arithmetic
def _mm(x, w, low, keep_result=False):
    """x [.., a] @ w [a, b] in float32 at ``highest``; with ``low`` both
    operands, and the result unless ``keep_result``, in 8-bit floats."""
    y = jnp.matmul(stored(x, low), stored(w.astype(jnp.float32), low),
                   precision=HI)
    return y if keep_result else stored(y, low)


def _rms_norm(x, gain, eps):
    return (x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * gain.astype(jnp.float32))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _delta_rule(q, k, v, alpha, beta, low):
    """The recurrence, token by token. q, k [T, H, dk], v [T, H, dv],
    alpha, beta [T, H]; returns o [T, H, dv]."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, x):
        q_t, k_t, v_t, a_t, b_t = x
        s_in = stored(s, low)                 # an operand, not the carry
        u = b_t[:, None] * (v_t - a_t[:, None] * jnp.einsum(
            "hk,hkv->hv", k_t, s_in, precision=HI))
        u = stored(u, low)
        s = a_t[:, None, None] * s + jnp.einsum("hk,hv->hkv", k_t, u,
                                                precision=HI)
        o = jnp.einsum("hk,hkv->hv", q_t, stored(s, low), precision=HI)
        return s, o

    _, o = lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32),
                    (q, k, v, alpha, beta))
    return o


def _linear_attention(x, p, *, heads, dk, dv, eps, low):
    t = x.shape[0]
    kw = p["conv"].shape[0]
    qkv = jnp.concatenate([_mm(x, p[n], low) for n in ("Wq", "Wk", "Wv")],
                          axis=-1)
    z = _mm(x, p["Wz"], low).reshape(t, heads, dv)
    a = _mm(x, p["Wa"], low, keep_result=True)
    b = _mm(x, p["Wb"], low, keep_result=True)
    past = jnp.concatenate(
        [jnp.zeros((kw - 1, qkv.shape[1]), jnp.float32), qkv], axis=0)
    taps = p["conv"].astype(jnp.float32)
    conv = sum(stored(past[i:i + t], low) * stored(taps[i], low)
               for i in range(kw))
    conv = stored(jax.nn.silu(conv), low)
    q, k, v = jnp.split(conv, [heads * dk, 2 * heads * dk], axis=-1)
    q = stored(_unit(q.reshape(t, heads, dk)) * dk ** -0.5, low)
    k = stored(_unit(k.reshape(t, heads, dk)), low)
    v = v.reshape(t, heads, dv)
    beta = 2.0 * jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32))
                    * jax.nn.softplus(a + p["dt_bias"].astype(jnp.float32)))
    o = stored(_delta_rule(q, k, v, alpha, beta, low), low)
    y = stored(_rms_norm(o, p["norm"], eps) * jax.nn.silu(z), low)
    return _mm(y.reshape(t, heads * dv), p["Wo"], low)


def _full_attention(x, p, *, heads, eps, low):
    t, e = x.shape
    d = e // heads

    def proj(name, norm=None):
        y = _mm(x, p[name], low)
        if norm is not None:
            y = stored(_rms_norm(y, p[norm], eps), low)
        return y.reshape(t, heads, d).transpose(1, 0, 2)        # [H, T, D]

    q, k, v = proj("Wq", "q_norm"), proj("Wk", "k_norm"), proj("Wv")
    pos = jnp.arange(t)
    outs = []
    for start in range(0, t, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        s = jnp.einsum("htd,hsd->hts", qb, k, precision=HI) / math.sqrt(d)
        causal = pos[start:start + QUERY_BLOCK, None] >= pos[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        a = stored(jax.nn.softmax(s, axis=-1), low)
        outs.append(jnp.einsum("hts,hsd->htd", a, v, precision=HI))
    o = stored(jnp.concatenate(outs, axis=1), low)
    return _mm(o.transpose(1, 0, 2).reshape(t, e), p["Wo"], low)


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "dk", "dv", "eps", "low"))
def layer(x, p, *, kind, heads, dk, dv, eps, low):
    """One block over one sequence, x [T, E] float32; ``p`` holds the
    block's leaves under their short names."""
    if kind == "linear_attention":
        m = _linear_attention(x, p, heads=heads, dk=dk, dv=dv, eps=eps,
                              low=low)
    else:
        m = _full_attention(x, p, heads=heads, eps=eps, low=low)
    x = stored(x + stored(_rms_norm(m, p["gamma_a"], eps), low), low)
    f = _mm(x, p["Wg"], low, keep_result=True)
    f = stored(jax.nn.silu(f) * _mm(x, p["Wu"], low, keep_result=True), low)
    f = _mm(f, p["Wd"], low)
    return stored(x + stored(_rms_norm(f, p["gamma_b"], eps), low), low)


@functools.partial(jax.jit, static_argnames=("low",))
def embed(ids, w, *, low=False):
    return stored(jnp.take(w, ids, axis=0).astype(jnp.float32), low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def head(x, gain, w, *, eps, low):
    return _mm(stored(_rms_norm(x, gain, eps), low), w, low,
               keep_result=True)


def layer_params(params, kind: str, n: int):
    m = mixer_name(kind, n)
    leaves = (("Wq", "Wk", "Wv", "Wz", "Wa", "Wb", "Wo", "conv", "A_log",
               "dt_bias", "norm") if kind == "linear_attention"
              else ("Wq", "Wk", "Wv", "Wo", "q_norm", "k_norm"))
    p = {k: params[f"{m}/{k}"] for k in leaves}
    p.update(gamma_a=params[f"norm{n}a/gamma"],
             gamma_b=params[f"norm{n}b/gamma"],
             Wg=params[f"ffn{n}/Wg"], Wu=params[f"ffn{n}/Wu"],
             Wd=params[f"ffn{n}/Wd"])
    return p


def logits_at(cfg, params, ids, positions, low=False):
    """Logits [len(positions), V] that follow ``ids[:p + 1]`` for each p in
    ``positions``, from one causal pass over the whole of ``ids`` (padded
    by the caller to a shared length if it wants one compile)."""
    kw = dict(heads=cfg["num_attention_heads"],
              dk=cfg["linear_key_head_dim"],
              dv=cfg["linear_value_head_dim"], eps=cfg["rms_norm_eps"],
              low=low)
    if cfg["linear_num_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("this reference gives both layer kinds the same "
                         "number of heads, as the source does")
    x = embed(jnp.asarray(ids, jnp.int32), params["embed/W"], low=low)
    for n, kind in enumerate(layer_kinds(cfg)):
        x = layer(x, layer_params(params, kind, n), kind=kind, **kw)
    x = x[jnp.asarray(positions, jnp.int32)]
    return head(x, params["norm_f/gamma"], params["out/W"],
                eps=cfg["rms_norm_eps"], low=low)


# ------------------------------------------------------ operation counts
def _token_flops(cfg) -> int:
    """Products one token needs in every layer, whatever its context: 2 a
    multiply-add; the delta rule 6 dk dv a head (S^T k, the outer
    product, S^T q) whatever form computes it."""
    e, i = cfg["hidden_size"], cfg["intermediate_size"]
    h, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv, kw = cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    linear = (2 * e * (2 * h * dk + 2 * h * dv + 2 * h) + 2 * h * dv * e
              + 2 * kw * h * (2 * dk + dv) + 6 * h * dk * dv)
    full = 2 * 4 * e * e
    kinds = layer_kinds(cfg)
    n_linear = kinds.count("linear_attention")
    return (n_linear * linear + (len(kinds) - n_linear) * full
            + len(kinds) * 2 * 3 * e * i)


def _attention_flops(cfg, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs in every full
    layer."""
    return (layer_kinds(cfg).count("full_attention")
            * 4 * cfg["hidden_size"] * pairs)


def _head_flops(cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg, prompt: int) -> int:
    """A prompt of ``prompt`` tokens processed causally; the embedding is
    a lookup and only the last position needs logits."""
    return (prompt * _token_flops(cfg)
            + _attention_flops(cfg, prompt * (prompt + 1) // 2)
            + _head_flops(cfg))


def decode_flops(cfg, context: int) -> int:
    """One generated token whose query sees ``context`` keys (itself
    included) in the full layers."""
    return (_token_flops(cfg) + _attention_flops(cfg, context)
            + _head_flops(cfg))


def state_bytes_per_slot(cfg) -> int:
    """What the linear layers keep a stream: a float32 state a head and
    the convolution's K - 1 last inputs in bfloat16."""
    h, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv, kw = cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    return layer_kinds(cfg).count("linear_attention") * (
        4 * h * dk * dv + 2 * (kw - 1) * h * (2 * dk + dv))
