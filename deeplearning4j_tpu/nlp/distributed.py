"""Distributed embedding training over a device mesh.

TPU-native equivalent of dl4j-spark-nlp's cluster Word2Vec/GloVe
(deeplearning4j-scaleout/spark/dl4j-spark-nlp/.../word2vec/Word2Vec.java:
vocab on the driver, per-partition training functions, parameter averaging
across executors). Here the tables stay replicated on every device of a
`jax.sharding.Mesh`; each device computes the gradient rows for its shard
of the pair batch, the (indices, row-grad) pairs are all-gathered over the
"data" axis — O(B*D) traffic, NOT O(V*D) full-table allreduce — and every
device applies the identical scatter-add to its replica. Because the
single-device kernels already SUM in-batch collisions, the distributed
result matches a single-device dispatch of the same global batch (modulo
fp reduction order), which is the
TestCompareParameterAveragingSparkVsSingleMachine invariant (SURVEY §4)
for the embedding engines. The same program runs multi-host over DCN via
jax.distributed — shard_map and the collectives are backend-agnostic.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_distributed_glove_step(mesh: Mesh, data_axis: str = "data"):
    """SPMD version of glove._glove_step: the pair batch is sharded over
    the mesh, each device computes its shard's gradient rows, the
    (row, grad) pairs are all-gathered and the AdaGrad scatter-update is
    applied identically on every replica — same summed-update semantics
    as the single-device step on the whole global batch (dl4j-spark-nlp's
    Glove-on-Spark role)."""

    def gather(a):
        return jax.lax.all_gather(a, data_axis, tiled=True)

    repl, shard = P(), P(data_axis)

    @partial(shard_map, mesh=mesh,
             in_specs=(repl, repl, repl, repl, shard, shard, shard, shard,
                       shard, repl),
             out_specs=(repl, repl, repl, repl, repl), check_vma=False)
    def step(w, b, hist_w, hist_b, rows_i, rows_j, logX, fX, valid, lr):
        wi, wj = w[rows_i], w[rows_j]
        diff = jnp.einsum("bd,bd->b", wi, wj) + b[rows_i] + b[rows_j] - logX
        fdiff = fX * diff * valid
        gi = fdiff[:, None] * wj
        gj = fdiff[:, None] * wi
        gb = fdiff
        ri, rj = gather(rows_i), gather(rows_j)
        gi, gj, gb = gather(gi), gather(gj), gather(gb)
        hist_w = hist_w.at[ri].add(gi * gi).at[rj].add(gj * gj)
        hist_b = hist_b.at[ri].add(gb * gb).at[rj].add(gb * gb)
        upd_i = lr * gi / jnp.sqrt(hist_w[ri] + 1e-8)
        upd_j = lr * gj / jnp.sqrt(hist_w[rj] + 1e-8)
        upd_bi = lr * gb / jnp.sqrt(hist_b[ri] + 1e-8)
        upd_bj = lr * gb / jnp.sqrt(hist_b[rj] + 1e-8)
        w = w.at[ri].add(-upd_i).at[rj].add(-upd_j)
        b = b.at[ri].add(-upd_bi).at[rj].add(-upd_bj)
        loss = jax.lax.psum(0.5 * jnp.sum(fX * diff * diff * valid),
                            data_axis)
        return w, b, hist_w, hist_b, loss

    return jax.jit(step)


class DistributedSequenceVectors:
    """Wrap a SequenceVectors-family model so its device dispatches run
    SPMD across `mesh` (skip-gram NS/HS paths — the Word2Vec defaults).

    Usage:
        w2v = Word2Vec(...)
        dist = DistributedSequenceVectors(w2v, mesh)
        dist.fit(sentences)   # or w2v.fit(...) — dispatches are patched
    """

    def __init__(self, sv, mesh: Mesh, data_axis: str = "data"):
        if sv.algo != "skipgram":
            raise NotImplementedError(
                "distributed path covers the skip-gram elements learning "
                "algorithm (Word2Vec/DBOW default); CBOW runs single-device")
        self.sv = sv
        self.mesh = mesh
        self.axis = data_axis
        self.n_devices = int(np.prod(mesh.devices.shape))
        self._ns = self._hs = None
        sv._dispatch_sg = self._dispatch_sg  # patch the device dispatch
        self._orig_reset = sv._reset_weights
        sv._reset_weights = self._reset_weights
        if sv.vocab is not None:  # vocab built before wrapping
            sv._eff_batch = self._global_batch(sv._eff_batch)

    # -- setup -------------------------------------------------------------
    def _global_batch(self, eff: int) -> int:
        """The update summation is GLOBAL, so the collision bound of
        sequencevectors._reset_weights applies to the global batch — keep
        its value, just round up to a mesh-divisible size (the pad rows
        are masked)."""
        n = self.n_devices
        return -(-eff // n) * n

    def _reset_weights(self):
        self._orig_reset()
        self.sv._eff_batch = self._global_batch(self.sv._eff_batch)
        self._ns = self._hs = None

    def _build(self):
        axis = self.axis
        repl, shard = P(), P(axis)

        def gather(a):
            return jax.lax.all_gather(a, axis, tiled=True)

        # check_vma off: every device applies the identical gathered
        # update to its replica, which the static replication checker
        # cannot prove
        @partial(shard_map, mesh=self.mesh,
                 in_specs=(repl, repl, shard, shard, shard, shard, shard),
                 out_specs=(repl, repl), check_vma=False)
        def ns_step(syn0, syn1neg, inputs, targets, labels, valid, lr):
            # local gradient rows (same math as sequencevectors._ns_step)
            l1 = syn0[inputs]
            w = syn1neg[targets]
            f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", l1, w))
            g = (labels - f) * (lr * valid)[:, None]
            grad_l1 = jnp.einsum("bk,bkd->bd", g, w)
            grad_w = (g[..., None] * l1[:, None, :]).reshape(-1, l1.shape[-1])
            # exchange (index, row-grad) pairs, apply identically everywhere
            syn0 = syn0.at[gather(inputs)].add(gather(grad_l1))
            syn1neg = syn1neg.at[gather(targets.reshape(-1))].add(
                gather(grad_w))
            return syn0, syn1neg

        @partial(shard_map, mesh=self.mesh,
                 in_specs=(repl, repl, shard, shard, shard, shard, shard),
                 out_specs=(repl, repl), check_vma=False)
        def hs_step(syn0, syn1, inputs, points, codes, mask, lr):
            l1 = syn0[inputs]
            w = syn1[points]
            f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", l1, w))
            g = (1.0 - codes - f) * lr[:, None] * mask
            grad_l1 = jnp.einsum("bl,bld->bd", g, w)
            grad_w = (g[..., None] * l1[:, None, :]).reshape(-1, w.shape[-1])
            syn0 = syn0.at[gather(inputs)].add(gather(grad_l1))
            syn1 = syn1.at[gather(points.reshape(-1))].add(gather(grad_w))
            return syn0, syn1

        self._ns = jax.jit(ns_step)
        self._hs = jax.jit(hs_step)

    # -- patched dispatch --------------------------------------------------
    def _dispatch_sg(self, bi, bo, alphas):
        sv = self.sv
        if self._ns is None and self._hs is None:
            self._build()
        bi, bo, alphas, pad = sv._pad(bi, bo, alphas)
        lr = jnp.asarray(alphas)
        if sv.negative > 0:
            targets, labels = sv._sample_negatives(bo)
            sv.syn0, sv.syn1neg = self._ns(
                sv.syn0, sv.syn1neg, jnp.asarray(bi), jnp.asarray(targets),
                jnp.asarray(labels), jnp.asarray(1.0 - pad), lr)
        if sv.use_hs:
            pts = sv._points[bo]
            cds = sv._codes[bo]
            msk = sv._path_mask[bo] * (1.0 - pad[:, None])
            sv.syn0, sv.syn1 = self._hs(
                sv.syn0, sv.syn1, jnp.asarray(bi), jnp.asarray(pts),
                jnp.asarray(cds), jnp.asarray(msk), lr)

    # -- passthrough -------------------------------------------------------
    def fit(self, *args, **kwargs):
        return self.sv.fit(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.sv, name)
