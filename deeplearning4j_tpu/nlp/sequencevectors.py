"""SequenceVectors: generic embedding trainer over sequences of elements.

Equivalent of deeplearning4j-nlp SequenceVectors.java:1244 (buildVocab :108,
fit :192, pluggable learning algos :56) + the SkipGram/CBOW elements learning
algorithms and InMemoryLookupTable syn0/syn1/syn1Neg storage.

TPU-first design: the reference trains via hogwild threads issuing native
AggregateSkipGram ops one pair at a time (SkipGram.java); here the host packs
(input, label) pairs + presampled negatives into fixed-shape int32 batches and
ONE jitted step does the whole batch on device — gathers, a [B,K+1,D]·[B,D]
batched dot (MXU), and scatter-adds back into the tables. In-batch index
collisions sum their updates (vs. sequential overwrite in hogwild) — same
stochastic objective.
"""

from __future__ import annotations

import itertools
import logging
from functools import partial
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.vocab import (
    VocabCache, VocabConstructor, VocabWord, codes_points_arrays,
    make_unigram_table,
)

log = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# Device kernels
# --------------------------------------------------------------------------

def _ns_update(syn0, syn1neg, inputs, targets, labels, valid, lr):
    """Negative-sampling update for a batch of pairs.

    inputs [B] int32 — rows of syn0 (context words / doc vectors)
    targets [B,K1] int32 — col 0 = positive word, cols 1.. = negatives
    labels [B,K1] float32 — 1 for positive, 0 for negatives
    valid [B] float32 — 0 for trailing pad rows (their update is zeroed)
    lr [B] float32 — per-pair learning rate (pairs from different points of
    the corpus share one device batch but keep their own decayed alpha).
    """
    l1 = syn0[inputs]                      # [B,D]
    w = syn1neg[targets]                   # [B,K1,D]
    f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", l1, w))
    g = (labels - f) * (lr * valid)[:, None]  # [B,K1]
    grad_l1 = jnp.einsum("bk,bkd->bd", g, w)
    grad_w = g[..., None] * l1[:, None, :]  # [B,K1,D]
    syn0 = syn0.at[inputs].add(grad_l1)
    syn1neg = syn1neg.at[targets.reshape(-1)].add(
        grad_w.reshape(-1, grad_w.shape[-1]))
    return syn0, syn1neg


_ns_step = jax.jit(_ns_update)


def _hs_update(syn0, syn1, inputs, points, codes, mask, lr):
    """Hierarchical-softmax update for a batch of pairs.

    points [B,L] int32 — inner-node rows along the label word's huffman path
    codes [B,L] float32 — path bits; mask [B,L] zeroes padded path slots.
    lr [B] float32 — per-pair learning rate.
    """
    l1 = syn0[inputs]                      # [B,D]
    w = syn1[points]                       # [B,L,D]
    f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", l1, w))
    g = (1.0 - codes - f) * lr[:, None] * mask  # [B,L]
    grad_l1 = jnp.einsum("bl,bld->bd", g, w)
    grad_w = g[..., None] * l1[:, None, :]
    syn0 = syn0.at[inputs].add(grad_l1)
    syn1 = syn1.at[points.reshape(-1)].add(grad_w.reshape(-1, w.shape[-1]))
    return syn0, syn1


_hs_step = jax.jit(_hs_update)


@partial(jax.jit, static_argnames=("negative", "use_hs"))
def _sg_scan(syn0, syn1, syn1neg, inputs, targets, labels, points, codes,
             pmask, valid, lr, *, negative: bool, use_hs: bool):
    """Many skip-gram batches in ONE dispatch: lax.scan over the leading
    batch axis (inputs [Nb,B], targets [Nb,B,K1], ...). Math and batch
    order identical to Nb sequential _ns_step/_hs_step dispatches — the
    device-side loop exists purely to cut host->device dispatch count
    (the Word2Vec bottleneck in the pre-PR-1 chip sessions, PERF.md). Unused table/xs slots are passed as dummies and returned
    untouched when the corresponding variant is off."""
    def body(carry, xs):
        s0, s1, s1n = carry
        i, t, l, p, c, m, v, a = xs
        if negative:
            s0, s1n = _ns_update(s0, s1n, i, t, l, v, a)
        if use_hs:
            s0, s1 = _hs_update(s0, s1, i, p, c, m, a)
        return (s0, s1, s1n), None
    (syn0, syn1, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        (inputs, targets, labels, points, codes, pmask, valid, lr))
    return syn0, syn1, syn1neg


@partial(jax.jit, static_argnames=("negative", "use_hs"))
def _sg_scan_devneg(syn0, syn1, syn1neg, table, key, inputs, outs, points,
                    codes, pmask, valid, lr, *, negative: int, use_hs: bool):
    """_sg_scan with the unigram-table negatives drawn ON DEVICE: the
    host ships only the pair streams (inputs/outs [Nb,B]) instead of the
    [Nb,B,K+1] targets + labels arrays — ~5x less host->device transfer
    per dispatch, which was the Word2Vec ceiling in the pre-PR-1 chip
    sessions (PERF.md). Same stochastic objective as the host
    sampler (uniform draws into the same freq^0.75 table, no positive
    dedup — matching _sample_negatives); different rng stream, so the
    bit-exact scan==per-batch equivalence holds only for
    device_negatives=False."""
    B = inputs.shape[1]
    labels = jnp.zeros((B, negative + 1), jnp.float32).at[:, 0].set(1.0)

    def body(carry, xs):
        s0, s1, s1n, k = carry
        i, o, p, c, m, v, a = xs
        k, sub = jax.random.split(k)
        negs = table[jax.random.randint(sub, (B, negative), 0,
                                        table.shape[0])]
        t = jnp.concatenate([o[:, None], negs], axis=1)
        s0, s1n = _ns_update(s0, s1n, i, t, labels, v, a)
        if use_hs:
            s0, s1 = _hs_update(s0, s1, i, p, c, m, a)
        return (s0, s1, s1n, k), None

    (syn0, syn1, syn1neg, _), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg, key),
        (inputs, outs, points, codes, pmask, valid, lr))
    return syn0, syn1, syn1neg


@partial(jax.jit, static_argnames=("negative", "use_hs"))
def _cbow_scan_devneg(syn0, syn1, syn1neg, table, key, ctx, cmask, centers,
                      points, codes, pmask, valid, lr, *, negative: int,
                      use_hs: bool):
    """CBOW twin of _sg_scan_devneg (centers are the positive targets)."""
    B = centers.shape[1]
    labels = jnp.zeros((B, negative + 1), jnp.float32).at[:, 0].set(1.0)

    def body(carry, xs):
        s0, s1, s1n, k = carry
        cx, cm, o, p, c, m, v, a = xs
        k, sub = jax.random.split(k)
        negs = table[jax.random.randint(sub, (B, negative), 0,
                                        table.shape[0])]
        t = jnp.concatenate([o[:, None], negs], axis=1)
        s0, s1n = _cbow_ns_update(s0, s1n, cx, cm, t, labels, v, a)
        if use_hs:
            s0, s1 = _cbow_hs_update(s0, s1, cx, cm, p, c, m, a)
        return (s0, s1, s1n, k), None

    (syn0, syn1, syn1neg, _), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg, key),
        (ctx, cmask, centers, points, codes, pmask, valid, lr))
    return syn0, syn1, syn1neg


def _cbow_ns_update(syn0, syn1neg, ctx, ctx_mask, targets, labels, valid,
                    lr):
    """CBOW with negative sampling: input = mean of context rows
    (ref: CBOW.java — sums context + optional label vectors)."""
    denom = jnp.maximum(ctx_mask.sum(-1, keepdims=True), 1.0)  # [B,1]
    vecs = syn0[ctx] * ctx_mask[..., None]  # [B,C,D]
    l1 = vecs.sum(1) / denom                # [B,D]
    w = syn1neg[targets]                    # [B,K1,D]
    f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", l1, w))
    g = (labels - f) * (lr * valid)[:, None]
    grad_l1 = jnp.einsum("bk,bkd->bd", g, w) / denom   # distribute mean grad
    grad_w = g[..., None] * l1[:, None, :]
    grad_ctx = grad_l1[:, None, :] * ctx_mask[..., None]  # [B,C,D]
    syn0 = syn0.at[ctx.reshape(-1)].add(
        grad_ctx.reshape(-1, grad_ctx.shape[-1]))
    syn1neg = syn1neg.at[targets.reshape(-1)].add(
        grad_w.reshape(-1, grad_w.shape[-1]))
    return syn0, syn1neg


_cbow_ns_step = jax.jit(_cbow_ns_update)


def _cbow_hs_update(syn0, syn1, ctx, ctx_mask, points, codes, mask, lr):
    denom = jnp.maximum(ctx_mask.sum(-1, keepdims=True), 1.0)
    vecs = syn0[ctx] * ctx_mask[..., None]
    l1 = vecs.sum(1) / denom
    w = syn1[points]
    f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", l1, w))
    g = (1.0 - codes - f) * lr[:, None] * mask
    grad_l1 = jnp.einsum("bl,bld->bd", g, w) / denom
    grad_w = g[..., None] * l1[:, None, :]
    grad_ctx = grad_l1[:, None, :] * ctx_mask[..., None]
    syn0 = syn0.at[ctx.reshape(-1)].add(
        grad_ctx.reshape(-1, grad_ctx.shape[-1]))
    syn1 = syn1.at[points.reshape(-1)].add(grad_w.reshape(-1, w.shape[-1]))
    return syn0, syn1


_cbow_hs_step = jax.jit(_cbow_hs_update)


@partial(jax.jit, static_argnames=("negative", "use_hs"))
def _cbow_scan(syn0, syn1, syn1neg, ctx, cmask, targets, labels, points,
               codes, pmask, valid, lr, *, negative: bool, use_hs: bool):
    """Many CBOW batches in ONE dispatch (see _sg_scan)."""
    def body(carry, xs):
        s0, s1, s1n = carry
        cx, cm, t, l, p, c, m, v, a = xs
        if negative:
            s0, s1n = _cbow_ns_update(s0, s1n, cx, cm, t, l, v, a)
        if use_hs:
            s0, s1 = _cbow_hs_update(s0, s1, cx, cm, p, c, m, a)
        return (s0, s1, s1n), None
    (syn0, syn1, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        (ctx, cmask, targets, labels, points, codes, pmask, valid, lr))
    return syn0, syn1, syn1neg


# --------------------------------------------------------------------------
# Host-side batch accumulation
# --------------------------------------------------------------------------

class _BatchBuffer:
    """Accumulates (pair, alpha) examples across many sequences into
    fixed-shape device batches, so the device sees one large jit dispatch
    per `batch_size` examples instead of one tiny dispatch per sentence
    (the reference amortizes per-pair cost with a hogwild worker pool,
    SequenceVectors.java:192; on TPU batching is the equivalent lever)."""

    def __init__(self):
        self._sg = []        # list of (ins [n], outs [n], lr [n])
        self._n_sg = 0
        self._cb = []        # list of (ctxs [n,C], cmask [n,C], centers [n], lr [n])
        self._n_cb = 0

    # -- skip-gram ---------------------------------------------------------
    def add_sg(self, ins: np.ndarray, outs: np.ndarray,
               alpha: float) -> None:
        n = len(ins)
        if n == 0:
            return
        self._sg.append((ins.astype(np.int32), outs.astype(np.int32),
                         np.full(n, alpha, np.float32)))
        self._n_sg += n

    def drain_sg(self, batch_size: int, final: bool = False):
        """Yield (ins, outs, lr) chunks of exactly `batch_size` rows; with
        final=True also yield the trailing partial chunk. Rows that don't
        fill a batch stay buffered for the next call."""
        if self._n_sg == 0 or (self._n_sg < batch_size and not final):
            return
        ins = np.concatenate([t[0] for t in self._sg])
        outs = np.concatenate([t[1] for t in self._sg])
        lr = np.concatenate([t[2] for t in self._sg])
        self._sg, self._n_sg = [], 0
        stop = len(ins) if final else len(ins) // batch_size * batch_size
        for s in range(0, stop, batch_size):
            yield ins[s:s + batch_size], outs[s:s + batch_size], \
                lr[s:s + batch_size]
        if stop < len(ins):  # keep the remainder buffered
            self._sg.append((ins[stop:], outs[stop:], lr[stop:]))
            self._n_sg = len(ins) - stop

    # -- CBOW --------------------------------------------------------------
    def add_cbow(self, ctxs: np.ndarray, cmask: np.ndarray,
                 centers: np.ndarray, alpha: float) -> None:
        n = len(centers)
        if n == 0:
            return
        self._cb.append((ctxs.astype(np.int32), cmask.astype(np.float32),
                         centers.astype(np.int32),
                         np.full(n, alpha, np.float32)))
        self._n_cb += n

    def drain_cbow(self, batch_size: int, final: bool = False):
        if self._n_cb == 0 or (self._n_cb < batch_size and not final):
            return
        # context width can differ when some sequences carry doc labels
        # (DM) and others don't — pad every chunk to the buffered max so
        # one concatenated array feeds fixed-shape kernels
        C = max(t[0].shape[1] for t in self._cb)

        def widen(a, fill=0):
            if a.shape[1] == C:
                return a
            return np.pad(a, ((0, 0), (0, C - a.shape[1])),
                          constant_values=fill)

        ctxs = np.concatenate([widen(t[0]) for t in self._cb])
        cmask = np.concatenate([widen(t[1]) for t in self._cb])
        centers = np.concatenate([t[2] for t in self._cb])
        lr = np.concatenate([t[3] for t in self._cb])
        self._cb, self._n_cb = [], 0
        stop = len(centers) if final \
            else len(centers) // batch_size * batch_size
        for s in range(0, stop, batch_size):
            yield ctxs[s:s + batch_size], cmask[s:s + batch_size], \
                centers[s:s + batch_size], lr[s:s + batch_size]
        if stop < len(centers):
            self._cb.append((ctxs[stop:], cmask[stop:], centers[stop:],
                             lr[stop:]))
            self._n_cb = len(centers) - stop


# --------------------------------------------------------------------------
# Engine
# --------------------------------------------------------------------------

class SequenceVectors:
    """Trains element embeddings over sequences (ref: SequenceVectors.java
    Builder defaults :375-386 — lr .025, minLr 1e-4, layerSize 100,
    window 5, negative 0 → hierarchical softmax on by default)."""

    def __init__(self, layer_size: int = 100, window: int = 5,
                 learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4,
                 negative: int = 0, sampling: float = 0.0,
                 min_word_frequency: int = 1, epochs: int = 1,
                 iterations: int = 1, batch_size: int = 4096,
                 elements_learning_algorithm: str = "skipgram",
                 use_hierarchic_softmax: Optional[bool] = None,
                 seed: int = 42, stop_words: Sequence[str] = (),
                 vocab_limit: int = 0, device_negatives: bool = True):
        self.layer_size = layer_size
        self.window = window
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = int(negative)
        self.sampling = sampling
        self.min_word_frequency = min_word_frequency
        self.epochs = epochs
        self.iterations = iterations
        self.batch_size = batch_size
        self._eff_batch = batch_size  # collision-bounded in _reset_weights
        algo = elements_learning_algorithm.lower()
        if algo not in ("skipgram", "cbow"):
            raise ValueError(f"unknown elements learning algorithm {algo!r}")
        self.algo = algo
        # ref semantics: negative>0 switches to NS unless HS explicitly kept
        self.use_hs = (self.negative == 0) if use_hierarchic_softmax is None \
            else use_hierarchic_softmax
        self.seed = seed
        self.stop_words = stop_words
        self.vocab_limit = vocab_limit
        #: sample NS negatives on device inside the scan dispatch (~5x
        #: less host->device traffic); False restores the host rng stream
        #: (bit-exact scan == per-batch equivalence)
        self.device_negatives = device_negatives

        self.vocab: Optional[VocabCache] = None
        self.syn0 = None            # [V,D] jnp
        self.syn1 = None            # HS inner nodes
        self.syn1neg = None         # NS output table
        self._codes = self._points = self._path_mask = None
        self._table: Optional[np.ndarray] = None
        self._rng = np.random.default_rng(seed)
        #: epochs completed so far (advanced by fit; persisted by save so a
        #: reloaded model resumes its learning-rate schedule mid-run)
        self.epochs_trained = 0

    # -- vocab + weights ---------------------------------------------------
    def build_vocab(self, sequences: Iterable[Sequence[str]],
                    extra_labels: Sequence[str] = ()) -> None:
        """ref: SequenceVectors.buildVocab :108 via VocabConstructor."""
        # type-check the FIRST element only, preserving streaming for
        # generator corpora (VocabConstructor.build is single-pass)
        if isinstance(sequences, (list, tuple)):
            first = sequences[0] if sequences else None
        else:
            it = iter(sequences)
            first = next(it, None)
            sequences = itertools.chain([first], it) if first is not None \
                else []
        if isinstance(first, str):
            raise TypeError(
                "build_vocab expects sequences of tokens (List[List[str]]);"
                " got strings — tokenize first, or use Word2Vec with a "
                "sentence_iterator/tokenizer_factory")
        ctor = VocabConstructor(self.min_word_frequency,
                                stop_words=self.stop_words,
                                build_huffman_tree=True,
                                vocab_limit=self.vocab_limit)
        self.vocab = ctor.build(sequences)
        for lb in extra_labels:
            if not self.vocab.contains_word(lb):
                vw = VocabWord(lb, frequency=1.0, is_label=True)
                self.vocab.add_token(vw)
        if extra_labels:
            self.vocab.build_index(order_by_frequency=False)
            from deeplearning4j_tpu.nlp.vocab import build_huffman
            build_huffman(self.vocab)
        self._reset_weights()

    def _reset_weights(self) -> None:
        """ref: InMemoryLookupTable.resetWeights — syn0 ~ U(-.5,.5)/D,
        syn1/syn1Neg zero."""
        V, D = self.vocab.num_words(), self.layer_size
        rnd = np.random.default_rng(self.seed)
        self.syn0 = jnp.asarray(
            (rnd.random((V, D), np.float32) - 0.5) / D)
        if self.use_hs:
            self.syn1 = jnp.zeros((max(V - 1, 1), D), jnp.float32)
        if self.negative > 0:
            self.syn1neg = jnp.zeros((V, D), jnp.float32)
        self._init_tables()

    def _init_tables(self) -> None:
        """(Re)build everything derived from the vocab but not trained:
        huffman path arrays, the NS unigram table, the device-negatives rng
        stream, and the collision-bounded dispatch batch. Called by
        _reset_weights on a fresh model and by the serializer after
        restoring trained syn0/syn1/syn1neg (nlp/serializer.py)."""
        V = self.vocab.num_words()
        if self.use_hs:
            c, p, m = codes_points_arrays(self.vocab)
            self._codes, self._points, self._path_mask = c, p, m
        if self.negative > 0:
            self._table = make_unigram_table(self.vocab)
            self._table_dev = None          # uploaded lazily per fit
            self._devneg_key = jax.random.PRNGKey(self.seed)
            self._devneg_ctr = 0
        # In-batch index collisions SUM their updates (hogwild would
        # interleave them); on a tiny vocab a big batch revisits each row
        # so often that summed stale gradients overshoot and collapse the
        # embedding. Bound expected collisions per table row: each batch
        # row touches `traffic` table entries (CBOW context width /
        # negatives+positive / huffman path), spread over the non-label
        # vocab. (DBOW label rows DO self-collide — every pair of a doc
        # shares its label input — but those collisions are bounded by the
        # doc's length, not the batch size, and match the reference's
        # per-sequence AggregateSkipGram batching, so they're excluded
        # here.) Real vocabs (>=10k) keep the full configured batch.
        v_words = sum(1 for vw in self.vocab.vocab_words()
                      if not vw.is_label) or V
        in_traffic = 2 * self.window if self.algo == "cbow" else 1
        out_traffic = 1
        if self.negative > 0:
            out_traffic = max(out_traffic, self.negative + 1)
        if self.use_hs:  # worst-case huffman path length actually built
            out_traffic = max(out_traffic, int(self._codes.shape[1]))
        traffic = max(in_traffic, out_traffic)
        self._eff_batch = min(self.batch_size,
                              max(64, (8 * v_words) // traffic))
        if self._eff_batch < self.batch_size:
            log.info(
                "dispatch batch clamped %d -> %d (vocab %d words, "
                "traffic %d/row) to bound in-batch update collisions",
                self.batch_size, self._eff_batch, v_words, traffic)

    # -- training ----------------------------------------------------------
    def fit(self, sequences: Iterable[Sequence[str]],
            labels_per_sequence: Optional[List[Sequence[str]]] = None,
            train_words: bool = True, train_labels: bool = False,
            start_epoch: Optional[int] = None,
            stop_epoch: Optional[int] = None,
            resume: bool = False) -> None:
        """ref: SequenceVectors.fit :192. `labels_per_sequence` attaches doc
        labels (ParagraphVectors DBOW/DM use them as extra input rows).

        The reference dispatches one native op per (pair, thread) from a
        worker pool (SequenceVectors.java:192 fit); here pairs ACCUMULATE
        across sequences into fixed-shape device batches and one jit step
        consumes each full batch — the device sees a few large dispatches
        per epoch instead of one tiny dispatch per sentence.

        start_epoch/stop_epoch run a slice of the epoch schedule (defaults
        0..self.epochs): the learning-rate decay and the rng streams are
        positioned exactly as the uninterrupted run would have them, so
        fit(stop_epoch=k); save; load; fit(start_epoch=k) equals one
        uninterrupted fit bit for bit (save persists the rng state —
        nlp/serializer.py trainer_state). resume=True is shorthand for
        start_epoch=self.epochs_trained (continue a checkpointed fit);
        a plain fit() always runs the full schedule from epoch 0."""
        if self.vocab is None:
            raise RuntimeError("call build_vocab first")
        if start_epoch is None:
            e0 = self.epochs_trained if resume else 0
        else:
            e0 = int(start_epoch)
        e1 = self.epochs if stop_epoch is None else int(stop_epoch)
        seqs = sequences if isinstance(sequences, list) else list(sequences)
        if seqs and isinstance(seqs[0], str):
            # a raw string would be iterated character-by-character and
            # silently train a character vocab — Word2Vec tokenizes
            # sentence strings; SequenceVectors wants token sequences
            raise TypeError(
                "SequenceVectors.fit expects sequences of tokens "
                "(List[List[str]]); got strings — tokenize first, or use "
                "Word2Vec with a sentence_iterator/tokenizer_factory")
        if (train_words and not train_labels
                and labels_per_sequence is None
                and self._fit_native(seqs, e0, e1)):
            self.epochs_trained = e1
            return
        total_words = sum(len(s) for s in seqs) * max(1, self.epochs)
        words_seen = sum(len(s) for s in seqs) * e0
        sg = self.algo == "skipgram"
        buf = _BatchBuffer()
        for epoch in range(e0, e1):
            for si, seq in enumerate(seqs):
                idxs = self._to_indices(seq)
                words_seen += len(seq)
                if len(idxs) == 0:
                    continue
                alpha = self._alpha(words_seen, total_words)
                lbl = None
                if labels_per_sequence is not None:
                    lbl = [self.vocab.index_of(l)
                           for l in labels_per_sequence[si]
                           if self.vocab.index_of(l) >= 0]
                for _ in range(self.iterations):
                    if sg:
                        if train_words:
                            ins, outs = self._pairs(idxs)
                            buf.add_sg(ins, outs, alpha)
                        if train_labels and lbl:
                            li, lo = self._label_pairs(idxs, lbl)
                            buf.add_sg(li, lo, alpha)
                    else:
                        ctxs, cmask, centers = self._cbow_contexts(idxs, lbl)
                        buf.add_cbow(ctxs, cmask, centers, alpha)
                # dispatch every full batch currently buffered.
                # (the per-batch H2D inside _dispatch_* is the native
                # word2vec path's jit boundary: pairs are BUILT on host
                # each batch — there is no device-resident iterator for
                # a prefetch stage to overlap, PR 2's documented
                # host-numpy exemption)
                if sg:
                    for bi, bo, ba in buf.drain_sg(self._eff_batch):
                        # tpulint: disable=device-transfer-in-hot-loop
                        self._dispatch_sg(bi, bo, ba)
                else:
                    for bx, bm, bc, ba in buf.drain_cbow(self._eff_batch):
                        # tpulint: disable=device-transfer-in-hot-loop
                        self._dispatch_cbow(bx, bm, bc, ba)
            # trailing partial batch — flushed per EPOCH (not per fit) so
            # the batch composition is identical whether the epoch range
            # runs in one call or is split for mid-fit checkpointing
            if sg:
                for bi, bo, ba in buf.drain_sg(self._eff_batch, final=True):
                    # tpulint: disable=device-transfer-in-hot-loop
                    self._dispatch_sg(bi, bo, ba)
            else:
                for bx, bm, bc, ba in buf.drain_cbow(self._eff_batch,
                                                     final=True):
                    # tpulint: disable=device-transfer-in-hot-loop
                    self._dispatch_cbow(bx, bm, bc, ba)
        self.epochs_trained = e1

    def _keep_probs(self) -> Optional[np.ndarray]:
        """Per-vocab-index keep probability for word2vec subsampling
        (None = no subsampling) — the vectorized form of _to_indices'
        per-token keep computation."""
        if self.sampling <= 0:
            return None
        t = self.sampling
        total = max(1.0, self.vocab.total_word_count)
        keep = np.ones(self.vocab.num_words(), np.float32)
        for i in range(self.vocab.num_words()):
            vw = self.vocab.element_at_index(i)
            f = (vw.frequency if vw is not None else 0.0) / total
            if f > 0:
                keep[i] = min(1.0, (np.sqrt(f / t) + 1) * (t / f))
        return keep

    def _fit_native(self, seqs, e0: int = 0, e1: Optional[int] = None) -> bool:
        """Epoch-at-a-time pair generation in the C++ runtime
        (native/src/word2vec.cpp; ref: the SequenceVectors.java:192
        multithreaded fit). Vocab lookup happens ONCE for the whole fit;
        each epoch×iteration generates all pairs across threads and
        dispatches the existing batched device steps. Returns False (use
        the numpy path) when the native lib is unavailable."""
        from deeplearning4j_tpu.native import word2vec as nw
        if not nw.native_available():
            return False
        # corpus as indices, once (OOV = -1, skipped natively but still
        # counted in the learning-rate schedule like the numpy path).
        # Vectorized: one numpy searchsorted over the flattened corpus
        # instead of 400k Python index_of calls (measured ~0.44s/400k
        # words — a material slice of the fit at device speeds)
        lens = np.asarray([len(s) for s in seqs], np.int64)
        offsets = np.zeros(len(seqs) + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        toks = np.asarray([t for s in seqs for t in s], dtype=np.str_)
        index_of = self.vocab.index_of
        names = [vw.word for vw in self.vocab.vocab_words()]
        # host python list of vocab words, not a device value
        # tpulint: disable=host-sync-in-hot-loop
        name_arr = np.asarray(names, dtype=np.str_)
        vidx = np.asarray([index_of(w) for w in names], np.int32)
        order = np.argsort(name_arr)
        sorted_names, sorted_idx = name_arr[order], vidx[order]
        if len(toks) and len(sorted_names):
            pos = np.searchsorted(sorted_names, toks)
            pc = pos.clip(0, len(sorted_names) - 1)
            corpus = np.where(sorted_names[pc] == toks, sorted_idx[pc],
                              -1).astype(np.int32)
        else:           # empty vocab: every token is OOV (silent no-op fit)
            corpus = np.full(len(toks), -1, np.int32)
        keep = self._keep_probs()
        # per-sequence alpha: the numpy path's words_seen schedule.
        # `lens`/`self._rng` here are HOST numpy state (native word2vec
        # path, no device values) — the int() casts below cannot sync.
        # tpulint: disable=host-sync-in-hot-loop
        total_words = int(lens.sum()) * max(1, self.epochs)
        sg = self.algo == "skipgram"
        # bound host memory: generate per SHARD of sequences (~1M corpus
        # words => tens of MB of pairs), not per whole epoch — big
        # corpora keep the numpy path's bounded-memory property
        shard_words = 1 << 20
        shards = [0]
        acc = 0
        for si in range(len(seqs)):
            acc += int(lens[si])  # tpulint: disable=host-sync-in-hot-loop
            if acc >= shard_words:
                shards.append(si + 1)
                acc = 0
        if shards[-1] != len(seqs):
            shards.append(len(seqs))
        if e1 is None:
            e1 = self.epochs
        for epoch in range(e0, e1):
            # host numpy schedule arithmetic, not a device sync
            # tpulint: disable=host-sync-in-hot-loop
            seen = int(lens.sum()) * epoch + np.cumsum(lens)
            seq_alpha = np.maximum(
                self.min_learning_rate,
                self.learning_rate
                * (1.0 - np.minimum(1.0, seen / max(1, total_words)))
            ).astype(np.float32)
            for _ in range(self.iterations):
                # host np.random draw, not a device sync
                # tpulint: disable=host-sync-in-hot-loop
                seed = int(self._rng.integers(2 ** 63))
                for s0, s1 in zip(shards[:-1], shards[1:]):
                    sub_off = offsets[s0:s1 + 1] - offsets[s0]
                    sub_corpus = corpus[offsets[s0]:offsets[s1]]
                    if sg:
                        ins, outs, pair_seq = nw.sg_pairs(
                            sub_corpus, sub_off, self.window, keep,
                            seed + s0)
                        alphas = seq_alpha[pair_seq + s0]
                        # native-built host rows: the H2D inside the
                        # scan dispatch is this path's jit boundary
                        # (see the fit-loop exemption above)
                        # tpulint: disable=device-transfer-in-hot-loop
                        self._dispatch_sg_many(ins, outs, alphas)
                    else:
                        ctxs, cmask, centers, row_seq = nw.cbow_rows(
                            sub_corpus, sub_off, self.window, keep,
                            seed + s0, row_width=2 * self.window)
                        alphas = seq_alpha[row_seq + s0]
                        # tpulint: disable=device-transfer-in-hot-loop
                        self._dispatch_cbow_many(ctxs, cmask, centers,
                                                 alphas)
        return True

    def _alpha(self, seen: int, total: int) -> float:
        frac = min(1.0, seen / max(1, total))
        return max(self.min_learning_rate,
                   self.learning_rate * (1.0 - frac))

    def _to_indices(self, seq: Sequence[str]) -> np.ndarray:
        out = []
        t = self.sampling
        total = max(1.0, self.vocab.total_word_count)
        for tok in seq:
            i = self.vocab.index_of(tok)
            if i < 0:
                continue
            if t > 0:  # word2vec subsampling (ref SkipGram.applySubsampling)
                f = self.vocab.word_frequency(tok) / total
                keep = (np.sqrt(f / t) + 1) * (t / f) if f > 0 else 1.0
                if keep < self._rng.random():
                    continue
            out.append(i)
        # host-built index list -> host array: no device value involved
        # tpulint: disable=host-sync-in-hot-loop
        return np.asarray(out, np.int32)

    def _pairs(self, idxs: np.ndarray):
        """(input=context row, predict=center word) window pairs, mirroring
        word2vec C / SkipGram.java windowing with random window shrink
        b ∈ [0, window): offsets b-window .. window-b inclusive, skip 0.
        Vectorized: one [n, 2w] mask instead of a per-position Python loop."""
        n = len(idxs)
        w = self.window
        if n == 0:
            return (np.empty(0, np.int32),) * 2
        b = self._rng.integers(0, w, n)                      # [n]
        offs = np.concatenate([np.arange(-w, 0), np.arange(1, w + 1)])  # [2w]
        pos = np.arange(n)[:, None]                          # [n,1]
        c = pos + offs[None, :]                              # [n,2w]
        valid = (np.abs(offs)[None, :] <= (w - b)[:, None]) & \
            (c >= 0) & (c < n)
        ins = idxs[c.clip(0, n - 1)][valid]
        outs = np.broadcast_to(idxs[:, None], c.shape)[valid]
        return ins.astype(np.int32), outs.astype(np.int32)

    def _cbow_contexts(self, idxs: np.ndarray, label_rows=None):
        """Per-center context rows + mask, vectorized like _pairs.
        Returns (ctxs [n,C], cmask [n,C], centers [n])."""
        n = len(idxs)
        w = self.window
        n_lbl = len(label_rows) if label_rows else 0
        C = 2 * w + n_lbl
        b = self._rng.integers(0, w, n)
        offs = np.concatenate([np.arange(-w, 0), np.arange(1, w + 1)])
        pos = np.arange(n)[:, None]
        c = pos + offs[None, :]
        valid = (np.abs(offs)[None, :] <= (w - b)[:, None]) & \
            (c >= 0) & (c < n)
        ctxs = np.zeros((n, C), np.int32)
        cmask = np.zeros((n, C), np.float32)
        ctxs[:, :2 * w] = idxs[c.clip(0, n - 1)] * valid
        cmask[:, :2 * w] = valid
        if n_lbl:  # DM: doc vector(s) join the context average
            # host label-row list -> host array: no device value involved
            # tpulint: disable=host-sync-in-hot-loop
            ctxs[:, 2 * w:] = np.asarray(label_rows, np.int32)[None, :]
            cmask[:, 2 * w:] = 1.0
        return ctxs, cmask, idxs.astype(np.int32)

    def _dispatch_sg(self, bi, bo, alphas):
        """One device step on a full/padded skip-gram batch."""
        bi, bo, alphas, pad = self._pad(bi, bo, alphas)
        lr = jnp.asarray(alphas)
        if self.negative > 0:
            targets, labels = self._sample_negatives(bo)
            self.syn0, self.syn1neg = _ns_step(
                self.syn0, self.syn1neg, jnp.asarray(bi),
                jnp.asarray(targets), jnp.asarray(labels),
                jnp.asarray(1.0 - pad), lr)
        if self.use_hs:
            pts = self._points[bo]
            cds = self._codes[bo]
            msk = self._path_mask[bo] * (1.0 - pad[:, None])
            self.syn0, self.syn1 = _hs_step(
                self.syn0, self.syn1, jnp.asarray(bi), jnp.asarray(pts),
                jnp.asarray(cds), jnp.asarray(msk), lr)

    #: batches per _sg_scan dispatch: bounds the per-dispatch host->device
    #: transfer (~scan_chunk * B * (K+2+L) * 4 bytes) while still cutting
    #: dispatch count by the same factor
    scan_chunk = 64

    @staticmethod
    def _pad_rows(a, rows_to):
        """Zero-pad array `a` along axis 0 to `rows_to` rows."""
        if len(a) == rows_to:
            return a
        widths = [(0, rows_to - len(a))] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths)

    #: prepare+upload the NEXT scan group on a worker thread while the
    #: current group's scan runs on device (the measured Word2Vec ceiling
    #: was upload serialization between groups — PERF.md; the single
    #: worker preserves the host rng draw order, so exactness holds)
    upload_prefetch = True

    def _run_scan_dispatch(self, rows, alphas, lead_fn, scan_fn,
                           devneg_fn):
        """Shared scaffolding for the scan-batched dispatchers: group
        scan_chunk full batches per device dispatch, threading the table
        carries across groups. The remainder runs as ONE more scan group
        padded to a power-of-two batch count (pad rows carry lr=0 and
        valid=0, so their update is exactly zero — and at most
        log2(scan_chunk) extra compiled group sizes exist), instead of
        up to scan_chunk-1 individual per-batch dispatches.

        `rows` [n] are the output-table rows (sg labels / cbow centers)
        that negatives + huffman paths are drawn from — in batch order,
        so with device_negatives=False the rng stream matches the
        per-batch path and the result is numerically equivalent to
        per-batch dispatching (pinned to 1e-6 by the equivalence tests;
        XLA may reorder float ops inside the scan body). With
        device_negatives (default) the NS negatives are drawn on device
        by `devneg_fn` and only the pair streams ship. `lead_fn(a, b,
        nb)` supplies the variant-specific leading xs for rows [a:b)
        zero-padded to nb full batches (sg: inputs; cbow: ctx + mask).

        Payload prep + host->device upload of group i+1 runs on a
        single-slot worker thread while group i's scan executes
        (`upload_prefetch`; the groups' rng draws happen in prep order
        on ONE worker, so the stream is identical to serial prep)."""
        B = self._eff_batch
        nb = self.scan_chunk
        n = len(rows)
        ns, hs = self.negative > 0, self.use_hs
        devneg = ns and self.device_negatives
        D = self.syn0.shape[1]
        dummy1 = self.syn1 if hs else jnp.zeros((1, D), jnp.float32)
        dummy1n = self.syn1neg if ns else jnp.zeros((1, D), jnp.float32)
        if devneg and n and self._table_dev is None:
            self._table_dev = jnp.asarray(self._table)
        # group schedule: full scan_chunk groups, then one padded
        # power-of-two group for the remainder
        n_scan = ((n // B) // nb) * nb
        groups = [(g0 * B, (g0 + nb) * B, nb)
                  for g0 in range(0, n_scan, nb)]
        if n_scan * B < n:
            rem_b = -(-(n - n_scan * B) // B)       # ceil batches
            gb = 1
            while gb < rem_b:
                gb *= 2
            # the group constants are allocated [nb, ...]: a
            # non-power-of-two scan_chunk must not round past it
            # (rem_b <= nb always holds)
            gb = min(gb, nb)
            groups.append((n_scan * B, n, gb))
        # constant across groups: upload once, reuse every dispatch
        # (full groups slice nothing; the padded group slices [:g])
        ones = jnp.ones((nb, B), jnp.float32)
        if not ns:
            targets0 = jnp.zeros((nb, B, 1), jnp.int32)
            labels0 = jnp.zeros((nb, B, 1), jnp.float32)
        elif not devneg:
            # NS labels are the constant [1, 0, ...] pattern — never
            # re-ship them per group (they were ~40% of the payload)
            lab = np.zeros((nb, B, self.negative + 1), np.float32)
            lab[:, :, 0] = 1.0
            labels0 = jnp.asarray(lab)
        if not hs:
            pts0 = jnp.zeros((nb, B, 1), jnp.int32)
            cds0 = jnp.zeros((nb, B, 1), jnp.float32)
            msk0 = jnp.zeros((nb, B, 1), jnp.float32)
        def prep(a, b, g):
            """Build + upload one group's payload (rng draws happen
            here, in prep order). Returns the dispatch closure inputs."""
            k = b - a                                # real rows
            full = k == g * B
            ro = self._pad_rows(
                np.ascontiguousarray(rows[a:b]), g * B).reshape(g, B)
            lr = self._pad_rows(alphas[a:b].astype(np.float32),
                                g * B).reshape(g, B)
            if full:
                valid = ones if g == nb else ones[:g]
                vnp = None
            else:
                vnp = self._pad_rows(np.ones(k, np.float32),
                                     g * B).reshape(g, B)
                valid = jax.device_put(vnp)
            if hs:
                m = self._path_mask[ro]
                if vnp is not None:
                    m = m * vnp[..., None]
                pts = jax.device_put(self._points[ro])
                cds = jax.device_put(self._codes[ro])
                msk = jax.device_put(m)
            else:
                pts, cds, msk = pts0[:g], cds0[:g], msk0[:g]
            lead = tuple(jax.device_put(np.asarray(x)) if not isinstance(
                x, jax.Array) else x for x in lead_fn(a, b, g))
            if devneg:
                key = jax.random.fold_in(self._devneg_key,
                                         self._devneg_ctr)
                self._devneg_ctr += 1
                targets = None
            else:
                key = None
                if ns:
                    # sample only batches with >=1 real row: the padded
                    # group may round up to a power of two with fully-pad
                    # batches the per-batch path never sampled — drawing
                    # for them would advance _rng and break the bit-exact
                    # cross-call equivalence with per-batch dispatching
                    real_b = -(-k // B)
                    t_np = np.zeros((g, B, self.negative + 1), np.int32)
                    for j in range(real_b):
                        t_np[j] = self._sample_negatives(ro[j])[0]
                    targets = jax.device_put(t_np)
                else:
                    targets = targets0[:g]
            return (g, lead, jax.device_put(ro), pts, cds, msk, valid,
                    jax.device_put(lr), key, targets)

        def dispatch(payload):
            nonlocal dummy1, dummy1n
            g, lead, ro, pts, cds, msk, valid, lr, key, targets = payload
            if devneg:
                self.syn0, s1, s1n = devneg_fn(
                    self.syn0, dummy1, dummy1n, self._table_dev, key,
                    *lead, ro, pts, cds, msk, valid, lr,
                    negative=self.negative, use_hs=hs)
            else:
                self.syn0, s1, s1n = scan_fn(
                    self.syn0, dummy1, dummy1n, *lead, targets,
                    labels0[:g], pts, cds, msk, valid, lr,
                    negative=ns, use_hs=hs)
            if hs:
                self.syn1 = dummy1 = s1
            if ns:
                self.syn1neg = dummy1n = s1n

        if self.upload_prefetch and len(groups) > 1:
            import concurrent.futures as _cf
            if getattr(self, "_uploader", None) is None:
                self._uploader = _cf.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="w2v-upload")
            # 1-deep pipeline: while group i's scan runs, the worker
            # preps + uploads group i+1
            fut = self._uploader.submit(prep, *groups[0])
            for grp in groups[1:]:
                payload = fut.result()
                # snapshot BEFORE submitting the next prep (no concurrent
                # mutation): if dispatch fails, the already-prepped-but-
                # never-dispatched group's rng/counter draws are undone,
                # keeping the save/resume stream contract intact
                snap = (self._rng.bit_generator.state,
                        getattr(self, "_devneg_ctr", None))
                fut = self._uploader.submit(prep, *grp)
                try:
                    dispatch(payload)
                except BaseException:
                    try:
                        fut.result()          # worker must finish first
                    except Exception:         # noqa: BLE001
                        pass
                    self._rng.bit_generator.state = snap[0]
                    if snap[1] is not None:
                        self._devneg_ctr = snap[1]
                    raise
            dispatch(fut.result())
        else:
            for grp in groups:
                dispatch(prep(*grp))

    def _dispatch_sg_many(self, ins, outs, alphas):
        """Shard-sized skip-gram training through _run_scan_dispatch."""
        B = self._eff_batch

        def lead(a, b, g):
            return (jnp.asarray(self._pad_rows(
                np.ascontiguousarray(ins[a:b]), g * B).reshape(g, B)),)

        self._run_scan_dispatch(outs, alphas, lead, _sg_scan,
                                _sg_scan_devneg)

    def _dispatch_cbow_many(self, ctxs, cmask, centers, alphas):
        """CBOW twin of _dispatch_sg_many (same scaffolding)."""
        B = self._eff_batch
        C = ctxs.shape[1]

        def lead(a, b, g):
            return (jnp.asarray(self._pad_rows(
                        np.ascontiguousarray(ctxs[a:b]),
                        g * B).reshape(g, B, C)),
                    jnp.asarray(self._pad_rows(
                        np.ascontiguousarray(cmask[a:b]).astype(
                            np.float32), g * B).reshape(g, B, C)))

        self._run_scan_dispatch(centers, alphas, lead, _cbow_scan,
                                _cbow_scan_devneg)

    def _dispatch_cbow(self, bx, bm, bc, alphas):
        B = self._eff_batch
        pad = np.zeros(B, np.float32)
        k = len(bc)
        if k < B:
            pad[k:] = 1.0
            bc = np.pad(bc, (0, B - k))
            bx = np.pad(bx, ((0, B - k), (0, 0)))
            bm = np.pad(bm, ((0, B - k), (0, 0)))
            alphas = np.pad(alphas, (0, B - k))
        lr = jnp.asarray(alphas.astype(np.float32))
        if self.negative > 0:
            targets, labels = self._sample_negatives(bc)
            self.syn0, self.syn1neg = _cbow_ns_step(
                self.syn0, self.syn1neg, jnp.asarray(bx), jnp.asarray(bm),
                jnp.asarray(targets), jnp.asarray(labels),
                jnp.asarray(1.0 - pad), lr)
        if self.use_hs:
            pts, cds = self._points[bc], self._codes[bc]
            msk = self._path_mask[bc] * (1.0 - pad[:, None])
            self.syn0, self.syn1 = _cbow_hs_step(
                self.syn0, self.syn1, jnp.asarray(bx), jnp.asarray(bm),
                jnp.asarray(pts), jnp.asarray(cds), jnp.asarray(msk), lr)

    @staticmethod
    def _label_pairs(idxs: np.ndarray, label_rows: List[int]):
        """DBOW: each label row predicts every word of the sequence."""
        ins, outs = [], []
        for lr_ in label_rows:
            for w in idxs:
                ins.append(lr_)
                outs.append(w)
        # host-built pair lists -> host arrays: no device value involved
        # tpulint: disable=host-sync-in-hot-loop
        return np.asarray(ins, np.int32), np.asarray(outs, np.int32)

    def _train_label_pairs(self, idxs, alpha, label_rows) -> None:
        """DBOW-style label->word updates for a single sequence, dispatched
        immediately (used by ParagraphVectors.infer_vector, where the output
        tables are frozen between steps so buffering across calls would
        change semantics)."""
        ins, outs = self._label_pairs(idxs, label_rows)
        for s in range(0, len(ins), self._eff_batch):
            bi, bo = ins[s:s + self._eff_batch], outs[s:s + self._eff_batch]
            alphas = np.full(len(bi), alpha, np.float32)
            self._dispatch_sg(bi, bo, alphas)

    def _pad(self, bi: np.ndarray, bo: np.ndarray, alphas=None):
        """Pad a trailing partial batch to `batch_size` (static shapes for
        jit); returns pad mask (1 where padded). With `alphas` given, the
        per-pair lr array is padded too and returned before the mask."""
        pad = np.zeros(self._eff_batch, np.float32)
        if len(bi) < self._eff_batch:
            n = self._eff_batch - len(bi)
            pad[len(bi):] = 1.0
            bi = np.pad(bi, (0, n))
            bo = np.pad(bo, (0, n))
            if alphas is not None:
                alphas = np.pad(alphas, (0, n))
        if alphas is not None:
            return bi, bo, alphas.astype(np.float32), pad
        return bi, bo, pad

    def _sample_negatives(self, bo: np.ndarray):
        """Unigram-table negatives; col 0 is the positive word. Pad rows are
        zeroed inside the kernels via the `valid` mask."""
        K = self.negative
        B = len(bo)
        negs = self._table[self._rng.integers(0, len(self._table), (B, K))]
        targets = np.concatenate([bo[:, None], negs], axis=1).astype(np.int32)
        labels = np.zeros((B, K + 1), np.float32)
        labels[:, 0] = 1.0
        return targets, labels

    def __del__(self):
        up = getattr(self, "_uploader", None)
        if up is not None:
            up.shutdown(wait=False)

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        """Full-model save — vocab with counts/labels, huffman codes, syn0/
        syn1/syn1neg, trainer config AND rng state, in the reference's
        writeWord2VecModel zip layout (ref WordVectorSerializer.java:472-677)
        plus a trainer_state.json entry for exact mid-fit resume."""
        from deeplearning4j_tpu.nlp import serializer
        serializer.write_full_model(self, path)

    @classmethod
    def load(cls, path: str) -> "SequenceVectors":
        """Restore a model saved by save() — or a reference-written
        Word2Vec/ParagraphVectors zip (ref WordVectorSerializer
        readWord2Vec/readParagraphVectors :811-950)."""
        from deeplearning4j_tpu.nlp import serializer
        return serializer.read_full_model(path, cls=cls)

    # -- queries (ref: BasicModelUtils.java wordsNearest/similarity) -------
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        if i < 0:
            return None
        return np.asarray(self.syn0[i])

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.get_word_vector(a), self.get_word_vector(b)
        if va is None or vb is None:
            return float("nan")
        denom = (np.linalg.norm(va) * np.linalg.norm(vb)) or 1e-12
        return float(va @ vb / denom)

    def words_nearest(self, word_or_vec, top_n: int = 10,
                      exclude: Sequence[str] = ()) -> List[str]:
        if isinstance(word_or_vec, str):
            v = self.get_word_vector(word_or_vec)
            exclude = list(exclude) + [word_or_vec]
            if v is None:
                return []
        else:
            v = np.asarray(word_or_vec, np.float32)
        syn0 = np.asarray(self.syn0)
        norms = np.linalg.norm(syn0, axis=1) + 1e-12
        sims = syn0 @ v / (norms * (np.linalg.norm(v) + 1e-12))
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at_index(int(i))
            vw = self.vocab.element_at_index(int(i))
            if w in exclude or (vw is not None and vw.is_label):
                continue
            out.append(w)
            if len(out) >= top_n:
                break
        return out
