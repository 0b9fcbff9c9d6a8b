"""Pipeline (stage) parallelism: GPipe-style microbatched execution.

Completes the parallelism suite (data parallel — parallel/wrapper;
sequence parallel — parallel/sequence; tensor parallel — parallel/tensor)
with the fourth axis: each device of a "pipe" mesh axis owns ONE STAGE of
the network; microbatches stream through the stages, activations hop to
the next stage over ICI with `ppermute`. The schedule is the classic
GPipe fill-drain loop: with S stages and M microbatches, the loop runs
S+M-1 ticks, each device computing its stage on the microbatch currently
resident (or idling in the bubble); bubble fraction (S-1)/(S+M-1)
shrinks as M grows.

All stages must share one apply signature (params, x) -> y with equal
activation shapes (classic homogeneous-block pipelining, the transformer
case). Exactness vs sequentially composing the stages is tested on the
virtual mesh; gradients flow through the ppermutes so the same program
trains under jax.grad.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_stage_params(stage_params: list, mesh: Mesh, axis: str = "pipe"):
    """Stack per-stage param pytrees along a new leading axis and shard it
    over the pipe axis (device s holds stage s's params)."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params)
    sh = lambda a: NamedSharding(  # noqa: E731
        mesh, P(*([axis] + [None] * (a.ndim - 1))))
    return jax.tree.map(lambda a: jax.device_put(a, sh(a)), stacked)


def _prepare(stage_fn, stacked_params, x, mesh: Mesh, axis: str,
             n_microbatches: int):
    """Shared schedule setup: validate one-stage-per-device and the
    microbatch split; build the per-stage param sharding specs.
    Returns (S, M, micro, param_specs).

    The microbatches are cast to the STAGE OUTPUT dtype (traced
    abstractly) — the pipeline carries activations stage-to-stage, so a
    type-stable loop needs stage output dtype == stage input dtype; with
    mixed user dtypes (e.g. f64 params on f32 inputs under x64) the
    widening the math would do anyway happens once, up front."""
    S = mesh.shape[axis]
    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_stages != S:
        raise ValueError(
            f"{n_stages} stacked stages but the '{axis}' mesh axis has "
            f"{S} devices — one stage per device (a larger multiple "
            "would silently drop stages)")
    M = n_microbatches or S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")
    micro = x.reshape(M, B // M, *x.shape[1:])
    p0 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      stacked_params)
    h = jax.ShapeDtypeStruct(micro.shape[1:], micro.dtype)
    try:
        h_out = jax.eval_shape(stage_fn, p0, h)
        micro = micro.astype(h_out.dtype)
    except Exception:
        # stage_fn may use mesh collectives, which only trace inside the
        # shard_map body (axes unbound here) — keep the input dtype; the
        # user then owns type stability, as before
        h_out = None
    # params: each device sees its own stage's slice (leading axis 1)
    param_specs = jax.tree.map(
        lambda a: P(*([axis] + [None] * (a.ndim - 1))), stacked_params)
    return S, M, micro, param_specs, h_out


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                   axis: str = "pipe", n_microbatches: int = None):
    """Run `stage_fn(params_s, h)` for stages s=0..S-1 over the pipe axis.

    stacked_params: pytree with leading stage axis (shard_stage_params).
    x: [B, ...] global batch; B must divide by n_microbatches (default =
    number of stages). Returns the final stage's output for the full
    batch. Differentiable (fori_loop-free: a lax.scan drives the
    schedule, ppermute moves activations stage->stage).
    """
    S, M, micro, param_specs, _ = _prepare(stage_fn, stacked_params, x,
                                           mesh, axis, n_microbatches)
    B = x.shape[0]

    @partial(shard_map, mesh=mesh,
             in_specs=(param_specs, P()), out_specs=P(),
             check_vma=False)
    def run(params, micro):
        me = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda a: a[0], params)  # my stage's params
        n_ticks = S + M - 1
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outs = carry
            # which microbatch enters stage 0 this tick (garbage when
            # t >= M; masked out below)
            feed = micro[jnp.minimum(t, M - 1)]
            h_in = jnp.where(me == 0,
                             jnp.where(t < M, feed, jnp.zeros_like(feed)),
                             buf)
            h_out = stage_fn(p_local, h_in)
            # last stage finishes microbatch t-(S-1) at tick t
            done_idx = t - (S - 1)
            valid = (done_idx >= 0) & (done_idx < M)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.clip(done_idx, 0, M - 1)].set(h_out),
                lambda o: o, outs)
            buf_next = jax.lax.ppermute(h_out, axis, fwd_perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(micro[0])
        outs0 = jnp.zeros_like(micro)
        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                      jnp.arange(n_ticks))
        # only the LAST stage's outs are real; broadcast them to everyone
        # so the out_spec P() (replicated) holds
        last = jax.lax.psum(
            jnp.where(me == S - 1, outs, jnp.zeros_like(outs)), axis)
        return last

    outs = run(stacked_params, micro)
    return outs.reshape(B, *x.shape[1:])


def pipeline_train_step(stage_fn: Callable, loss_fn: Callable,
                        stacked_params, x, y, mesh: Mesh,
                        axis: str = "pipe", n_microbatches: int = None):
    """One 1F1B-style pipelined train step: returns (mean loss, dparams).

    `pipeline_apply` under `jax.grad` is GPipe: the scan's autodiff saves
    residuals for every (tick, stage) — activation memory grows O(M) with
    the microbatch count. This schedule interleaves each microbatch's
    backward with later microbatches' forwards, so a device only holds
    the stage INPUTS of its in-flight microbatches: at most 2S-1 of them,
    independent of M (the 1F1B property; classic refs: PipeDream/Megatron
    one-forward-one-backward). Backward is recompute-form — a tick's
    backward re-runs stage_fn from the saved input under jax.vjp, the
    same FLOP profile as a jax.checkpoint-ed GPipe — so for long trains
    (M >> S) memory drops from O(M) to O(S) at ~S extra pipeline ticks.

    stage_fn(params_s, h) -> h (homogeneous stages, as pipeline_apply);
    loss_fn(h_out, y_mb) -> scalar mean loss of one microbatch.
    Returns (loss, dparams): loss = mean over microbatches, dparams has
    the same stage-stacked layout as `stacked_params` (device s
    contributes the grads of its own stage). Input-grads (dx) are not
    returned — this is a train step, not a general VJP.
    """
    S, M, micro_x, param_specs, h_out = _prepare(stage_fn, stacked_params,
                                                 x, mesh, axis,
                                                 n_microbatches)
    micro_y = y.reshape(M, x.shape[0] // M, *y.shape[1:])
    K = 2 * S  # residual ring: >= max in-flight stage inputs (2S-1)
    # the loss accumulator carry must match what loss_fn actually
    # returns (x64-safe): trace it abstractly on the stage-output aval
    # from _prepare; when that was untraceable (collective-using
    # stage_fn) fall back to a dtype-promotion estimate
    try:
        if h_out is None:
            raise TypeError
        loss_dtype = jax.eval_shape(
            loss_fn, h_out,
            jax.ShapeDtypeStruct(micro_y.shape[1:], micro_y.dtype)).dtype
    except Exception:
        loss_dtype = jnp.result_type(
            jnp.float32, micro_x.dtype, micro_y.dtype,
            *[a.dtype for a in jax.tree.leaves(stacked_params)])

    @partial(shard_map, mesh=mesh,
             in_specs=(param_specs, P(), P()),
             out_specs=(P(), param_specs),
             check_vma=False)
    def run(params, mx, my):
        me = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda a: a[0], params)
        # schedule: fwd(s, m) at tick s + m; bwd(s, m) at tick
        # (2S - 1 - s) + m — the last stage's backward trails its forward
        # by one tick, cotangents ppermute upstream one stage per tick
        n_ticks = 2 * S + M - 2 + 1
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]

        def tick(carry, t):
            fbuf, bbuf, resid, dp_acc, loss_acc = carry
            # ---- forward half: microbatch m_f enters this stage
            m_f = t - me
            f_valid = (m_f >= 0) & (m_f < M)
            feed = mx[jnp.clip(m_f, 0, M - 1)]
            h_in = jnp.where(me == 0, feed, fbuf)
            h_out = stage_fn(p_local, h_in)
            resid = jax.lax.cond(
                f_valid,
                lambda r: r.at[jnp.clip(m_f, 0, M - 1) % K].set(h_in),
                lambda r: r, resid)
            fbuf_next = jax.lax.ppermute(h_out, axis, fwd_perm)

            # ---- backward half: microbatch m_b leaves this stage
            m_b = t - (2 * S - 1 - me)
            b_valid = (m_b >= 0) & (m_b < M)
            mi = jnp.clip(m_b, 0, M - 1)
            h_saved = resid[mi % K]
            h2, vjp_fn = jax.vjp(lambda p, h: stage_fn(p, h),
                                 p_local, h_saved)
            # last stage seeds the cotangent from the loss; others use
            # the cotangent ppermuted down from stage s+1
            y_mb = my[mi]
            loss_mb, g_loss = jax.value_and_grad(loss_fn)(h2, y_mb)
            cot = jnp.where(me == S - 1, g_loss, bbuf)
            dp, dh = vjp_fn(cot)
            dp_acc = jax.tree.map(
                lambda acc, g: acc + jnp.where(b_valid, g, 0.0),
                dp_acc, dp)
            loss_acc = loss_acc + jnp.where(
                b_valid & (me == S - 1), loss_mb, 0.0)
            bbuf_next = jax.lax.ppermute(dh, axis, bwd_perm)
            return (fbuf_next, bbuf_next, resid, dp_acc, loss_acc), None

        z = jnp.zeros_like(mx[0])
        resid0 = jnp.zeros((K,) + z.shape, z.dtype)
        dp0 = jax.tree.map(jnp.zeros_like, p_local)
        carry0 = (z, z, resid0, dp0, jnp.zeros((), loss_dtype))
        (_, _, _, dp_acc, loss_acc), _ = jax.lax.scan(
            tick, carry0, jnp.arange(n_ticks))
        # objective = (1/M) sum of per-microbatch mean losses, so the
        # accumulated per-microbatch grads average the same way
        loss = jax.lax.psum(loss_acc, axis) / M
        dparams = jax.tree.map(lambda a: (a / M)[None], dp_acc)
        return loss, dparams

    return run(stacked_params, micro_x, micro_y)
