"""The arithmetic of ``GatedDeltaNetLayer`` (nn/conf/layers.py): linear
attention by the gated delta rule (Yang, Kautz & Hatamizadeh 2024, "Gated
Delta Networks"), in the two forms a decoder needs, which agree.

A head keeps a state ``S`` [dk, dv] in float32. For a token with key
``k`` (unit length), value ``v``, query ``q``, decay ``alpha`` in (0, 1]
and write strength ``beta`` in [0, 2]:

    S <- alpha S + beta k (v - alpha S^T k)^T,        o = S^T q

- ``gdn_step``: that update, once, for one token a row (decode). The
  state is read twice and written once: ``o`` is taken from the old state
  (``alpha S^T q + (k . q) u``), so the new one is never read back.
- ``gdn_chunked``: the same recurrence over a whole sequence in chunks of
  ``CHUNK`` positions. With ``g`` the running sum of ``log alpha`` inside
  a chunk and ``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)`` the value a
  position really writes, the chunk's ``u`` solve a unit lower-triangular
  system (the WY form of a product of Householder-like factors):

      (I + A) U = beta V - (beta K * e^g) S_0,
      A[t, j] = beta_t e^{g_t - g_j} (k_t . k_j),  j < t

  whose inverse is built from matrix products alone
  (``_unit_lower_inverse``); then ``o = (q e^g) S_0 + (q k^T * decay,
  lower) U`` and ``S_C = e^{g_C} S_0 + (k e^{g_C - g})^T U``. Everything
  that does not need ``S_0`` is computed for all chunks at once; a
  ``lax.scan`` over the chunks carries ``S``. Products take operands in
  the sequence's dtype (bfloat16 on the chip) and accumulate in float32;
  decays, ``beta``, the triangular inverse and the state are float32.
  Every decay that appears is a ratio of a later running product to an
  earlier one, so none exceeds 1.

A masked position has ``alpha = 1`` and ``beta = 0``: it writes nothing
and decays nothing, so left padding leaves the state of the unpadded
sequence (``gdn_chunked`` pads to whole chunks that way itself).

``causal_conv`` is the width-K depthwise causal convolution in front of
q, k and v, with the K - 1 inputs before the sequence given (a stream's
tail) and a per-row count of left pads: the tail is laid directly before
a row's first real position, so pads shift nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: positions a chunk of ``gdn_chunked`` holds
CHUNK = 64
#: the triangular inverse is built by halving down to blocks this wide,
#: where the Neumann product of a nilpotent block is short and tame
_INVERSE_BASE = 16

HI = lax.Precision.HIGHEST


def l2_normalize(x, eps: float):
    """x / |x| over the last axis, float32 inside, x's dtype out."""
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)
            ).astype(x.dtype)


def gated_rms_norm(o, z, gain, eps: float):
    """``RMSNorm(o) * gain * silu(z)`` over the last axis (a head's value
    width), statistics in float32."""
    of = o.astype(jnp.float32)
    y = of * lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + eps)
    return y * gain.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))


def causal_conv(x, w, tail, pad):
    """Depthwise causal convolution of width K over time.

    x [N, T, C] with zeros at its ``pad`` [N] leading (left-pad)
    positions, w [K, C] (tap K - 1 multiplies the current position), tail
    [N, K - 1, C] the inputs that came before the row's first real
    position. Returns ``(y [N, T, C] float32, new_tail [N, K - 1, C])``:
    the new tail holds the last K - 1 inputs, the old tail's where the row
    brought fewer."""
    k = w.shape[0]
    z = jnp.concatenate([jnp.zeros_like(tail), x], axis=1)   # [N, K-1+T, C]
    z = jax.vmap(lambda zr, tr, p: lax.dynamic_update_slice(
        zr, tr, (p, jnp.zeros_like(p))))(z, tail.astype(x.dtype), pad)
    t = x.shape[1]
    wf = w.astype(jnp.float32)
    y = sum(z[:, i:i + t].astype(jnp.float32) * wf[i] for i in range(k))
    return y, z[:, t:]


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., C, C]
    (float32), from matrix products alone: blocks of ``_INVERSE_BASE`` by
    the Neumann product ``(I + m)(I + m^2)(I + m^4)...`` of ``m = -a``
    (exact: ``m`` is nilpotent), joined two and two by
    ``[[P, 0], [-R a21 P, R]]``."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    if c <= _INVERSE_BASE:
        m = -a
        inv, span = eye + m, 1
        while 2 * span < c:
            m = jnp.matmul(m, m, precision=HI)
            inv = inv + jnp.matmul(inv, m, precision=HI)
            span *= 2
        return inv
    h = c // 2
    p = _unit_lower_inverse(a[..., :h, :h])
    r = _unit_lower_inverse(a[..., h:, h:])
    low = -jnp.matmul(jnp.matmul(r, a[..., h:, :h], precision=HI), p,
                      precision=HI)
    top = jnp.concatenate([p, jnp.zeros_like(low).swapaxes(-1, -2)], -1)
    return jnp.concatenate([top, jnp.concatenate([low, r], -1)], -2)


def gdn_step(q, k, v, log_alpha, beta, state):
    """One token a row. q, k [N, H, dk], v [N, H, dv], log_alpha, beta
    [N, H] float32, state [N, H, dk, dv] float32. Returns ``(o [N, H, dv]
    float32, new state)``."""
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    alpha = jnp.exp(log_alpha)[..., None]                       # [N, H, 1]
    sk = jnp.einsum("nhk,nhkv->nhv", kf, state)
    sq = jnp.einsum("nhk,nhkv->nhv", qf, state)
    u = beta[..., None] * (vf - alpha * sk)
    o = alpha * sq + jnp.sum(kf * qf, axis=-1, keepdims=True) * u
    new = alpha[..., None] * state + kf[..., :, None] * u[..., None, :]
    return o, new


def gdn_chunked(q, k, v, log_alpha, beta, state, chunk: int = CHUNK):
    """A whole sequence. q, k [N, H, T, dk], v [N, H, T, dv], log_alpha,
    beta [N, H, T] float32 (0 and 0 at masked positions), state
    [N, H, dk, dv] float32. Returns ``(o [N, H, T, dv] float32, new
    state)``. T is padded on the left to whole chunks with masked
    positions."""
    n, h, t, dk = q.shape
    dv = v.shape[-1]
    cd = q.dtype
    lead = -t % chunk
    if lead:
        def pad(a):
            return jnp.pad(a, [(0, 0), (0, 0), (lead, 0)]
                           + [(0, 0)] * (a.ndim - 3))
        q, k, v, log_alpha, beta = (pad(a) for a in
                                    (q, k, v, log_alpha, beta))
    nc = (t + lead) // chunk

    def chunks(a):
        return a.reshape(n, h, nc, chunk, *a.shape[3:])

    q, k, v, beta = chunks(q), chunks(k), chunks(v), chunks(beta)
    g = jnp.cumsum(chunks(log_alpha), axis=-1)                # [N,H,nc,C]
    decay = jnp.exp(g[..., :, None] - g[..., None, :])        # e^{g_t-g_j}
    idx = jnp.arange(chunk)
    strict = idx[:, None] > idx[None, :]
    k_beta = (k.astype(jnp.float32) * beta[..., None]).astype(cd)
    kk = jnp.einsum("nhctk,nhcjk->nhctj", k_beta, k,
                    preferred_element_type=jnp.float32)
    # the exponent is positive above the diagonal: select, never multiply
    a = jnp.where(strict, kk * decay, 0.0)
    inv = _unit_lower_inverse(a).astype(cd)                   # [.., C, C]
    v_beta = (v.astype(jnp.float32) * beta[..., None]).astype(cd)
    u0 = jnp.einsum("nhctj,nhcjv->nhctv", inv, v_beta,
                    preferred_element_type=jnp.float32)
    w = jnp.einsum(
        "nhctj,nhcjk->nhctk", inv,
        (k_beta.astype(jnp.float32) * jnp.exp(g)[..., None]).astype(cd),
        preferred_element_type=jnp.float32).astype(cd)
    qk = jnp.einsum("nhctk,nhcjk->nhctj", q, k,
                    preferred_element_type=jnp.float32)
    qk = jnp.where(idx[:, None] >= idx[None, :], qk * decay, 0.0).astype(cd)
    q_in = (q.astype(jnp.float32) * jnp.exp(g)[..., None]).astype(cd)
    g_end = g[..., -1:]                                       # [N,H,nc,1]
    k_out = (k.astype(jnp.float32)
             * jnp.exp(g_end - g)[..., None]).astype(cd)
    end = jnp.exp(g_end[..., 0])                              # [N,H,nc]

    def body(s, xs):
        u0_c, w_c, qk_c, q_c, k_c, end_c = xs
        s_cd = s.astype(cd)
        u = u0_c - jnp.einsum("nhtk,nhkv->nhtv", w_c, s_cd,
                              preferred_element_type=jnp.float32)
        o = (jnp.einsum("nhtk,nhkv->nhtv", q_c, s_cd,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("nhtj,nhjv->nhtv", qk_c, u.astype(cd),
                          preferred_element_type=jnp.float32))
        s = (end_c[..., None, None] * s
             + jnp.einsum("nhtk,nhtv->nhkv", k_c, u.astype(cd),
                          preferred_element_type=jnp.float32))
        return s, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (u0, w, qk, q_in, k_out, end))
    state, o = lax.scan(body, state, xs)
    o = jnp.moveaxis(o, 0, 2).reshape(n, h, nc * chunk, dv)
    return o[:, :, lead:], state
