"""TextGenerationTransformer: a decoder-only character/token LM.

Post-parity zoo model (the 2017 reference's sequence model is
TextGenerationLSTM; this is its modern long-context counterpart built
from the same config DSL): pre-LN transformer blocks —
LN → causal multi-head SelfAttentionLayer → residual add →
LN → position-wise FFN (Convolution1D kernel=1) → residual add —
behind a TokenProjectionLayer, RnnOutputLayer softmax head. The net takes
token ids [N, T] (what the decoders of util/decoding and the serving
engine send: 4 bytes a token) and, through the same two leaves, the
RNN-format float [N, V, T] — one-hot training data for ``fit``,
``sample()``'s padded one-hot, soft distributions.
The attention core is the flash-style blockwise kernel, so contexts of
tens of thousands of tokens train on a single chip; sequence sharding
over a mesh uses ring/Ulysses attention on the same math
(parallel/sequence.py).
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    Convolution1DLayer, LayerNormalization, PositionalEmbeddingLayer,
    RnnOutputLayer, SelfAttentionLayer, TokenProjectionLayer,
)
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.zoo.base import ZooModel, register_model


from deeplearning4j_tpu.util.decoding import draw as _draw


@register_model
class TextGenerationTransformer(ZooModel):
    def __init__(self, vocab_size: int = 128, seed: int = 12345,
                 embed_dim: int = 256, n_heads: int = 8, n_layers: int = 4,
                 ffn_mult: int = 4, max_length: int = 1024,
                 block_size: int = 512, positional: str = "learned",
                 n_kv_heads=None, window=None, **kw):
        super().__init__(vocab_size, seed, **kw)
        if embed_dim % n_heads:
            raise ValueError("embed_dim must divide by n_heads")
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.ffn_mult = ffn_mult
        self.max_length = max_length
        self.block_size = block_size
        if positional not in ("learned", "rope"):
            raise ValueError(f"unknown positional {positional!r}")
        self.positional = positional
        self.n_kv_heads = n_kv_heads
        self.window = window

    def conf(self):
        E = self.embed_dim
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.kwargs.get("updater", Adam(3e-4)))
             .weight_init("xavier")
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.recurrent(self.vocab_size,
                                                  self.max_length)))
        # token projection: ids [N,T] -> [N,E,T], column id of W [E,V,1];
        # a float [N,V,T] -> the kernel-1 convolution over the same W
        g.add_layer("embed", TokenProjectionLayer(
            n_out=E, activation="identity"), "in")
        if self.positional == "learned":
            g.add_layer("pos", PositionalEmbeddingLayer(
                max_length=self.max_length), "embed")
            prev = "pos"
        else:  # rope: positions enter inside attention, no table
            prev = "embed"
        for i in range(self.n_layers):
            g.add_layer(f"ln{i}a", LayerNormalization(), prev)
            g.add_layer(f"attn{i}", SelfAttentionLayer(
                n_out=E, n_heads=self.n_heads, causal=True,
                block_size=self.block_size, activation="identity",
                cache_length=self.max_length,
                n_kv_heads=self.n_kv_heads, window=self.window,
                rope=self.positional == "rope"), f"ln{i}a")
            g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                         prev, f"attn{i}")
            g.add_layer(f"ln{i}b", LayerNormalization(), f"res{i}a")
            g.add_layer(f"ffn{i}a", Convolution1DLayer(
                n_out=E * self.ffn_mult, kernel=1,
                convolution_mode="same", activation="gelu"), f"ln{i}b")
            g.add_layer(f"ffn{i}b", Convolution1DLayer(
                n_out=E, kernel=1, convolution_mode="same",
                activation="identity"), f"ffn{i}a")
            g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                         f"res{i}a", f"ffn{i}b")
            prev = f"res{i}b"
        g.add_layer("ln_f", LayerNormalization(), prev)
        g.add_layer("out", RnnOutputLayer(
            n_out=self.vocab_size, loss="mcxent", activation="softmax"),
            "ln_f")
        return g.set_outputs("out").build()

    # -- convenience: sampling (ref TextGenerationLSTM usage pattern) ------
    def sample(self, net, seed_ids, steps: int, vocab_size: int = None,
               rng: np.random.Generator = None, temperature: float = 1.0,
               top_k: int = None, top_p: float = None):
        """Autoregressive sampling from a trained net. The input is padded
        to max_length so XLA compiles ONE shape (causal attention + the
        per-position layers make trailing zero padding inert for the
        position being read). `top_k`/`top_p` filter each draw exactly
        as in sample_stream."""
        V = vocab_size or self.vocab_size
        L = self.max_length
        rng = rng or np.random.default_rng(0)
        ids = list(seed_ids)
        x = np.zeros((1, V, L), np.float32)
        x[0, ids, np.arange(len(ids))] = 1.0
        for _ in range(steps):
            pos = len(ids) - 1
            if pos + 1 >= L:
                break
            out = net.output(x)
            probs = np.asarray(out[0] if isinstance(out, (list, tuple))
                               else out)[0, :, pos]
            nxt = _draw(probs, temperature, rng, top_k=top_k, top_p=top_p)
            ids.append(nxt)
            x[0, nxt, len(ids) - 1] = 1.0
        return ids

    def sample_stream(self, net, seed_ids, steps: int,
                      vocab_size: int = None,
                      rng: np.random.Generator = None,
                      temperature: float = 1.0,
                      prime_padded: bool = False,
                      top_k: int = None, top_p: float = None,
                      stop_tokens=()):
        """KV-cache incremental decoding (shared implementation:
        util/decoding.sample_stream) — O(steps) single-position forwards
        instead of the padded full-forward-per-token of `sample`, with an
        identical sampling distribution (tested). `prime_padded=True`
        primes the prompt in ONE left-padded dispatch; `top_k`/`top_p`
        filter each draw (top_k=1 is greedy)."""
        from deeplearning4j_tpu.util.decoding import sample_stream
        return sample_stream(net, seed_ids, steps,
                             vocab_size or self.vocab_size,
                             temperature=temperature, rng=rng,
                             max_length=self.max_length,
                             prime_padded=prime_padded,
                             top_k=top_k, top_p=top_p,
                             stop_tokens=stop_tokens)

    def sample_stream_batch(self, net, prompts, steps: int,
                            vocab_size: int = None,
                            rng: np.random.Generator = None,
                            temperature: float = 1.0,
                            top_k: int = None, top_p: float = None,
                            stop_tokens=()):
        """Decode a batch of prompts in lockstep — one dispatch advances
        every row (shared implementation
        util/decoding.sample_stream_batch). Mixed lengths left-pad and
        need rope positions (positional='rope'); learned-positional
        models require equal-length prompts."""
        from deeplearning4j_tpu.util.decoding import sample_stream_batch
        return sample_stream_batch(net, prompts, steps,
                                   vocab_size or self.vocab_size,
                                   temperature=temperature, rng=rng,
                                   max_length=self.max_length,
                                   top_k=top_k, top_p=top_p,
                                   stop_tokens=stop_tokens)

    def speculative_sample(self, net, draft, seed_ids, steps: int,
                           gamma: int = 4, vocab_size: int = None,
                           rng: np.random.Generator = None,
                           temperature: float = 1.0,
                           top_k: int = None, top_p: float = None,
                           prime_padded: bool = False, stop_tokens=()):
        """Speculative decoding: `draft` proposes `gamma` tokens, this
        model verifies them in ONE forward (shared implementation
        util/decoding.speculative_sample — the target distribution is
        exactly preserved; top_k=1 reproduces greedy decoding
        bit-for-bit). `draft` is a same-vocab streaming net (typically a
        smaller/quantized TextGenerationTransformer) or a host proposer
        callable such as decoding.prompt_lookup_proposer()."""
        from deeplearning4j_tpu.util.decoding import speculative_sample
        return speculative_sample(net, draft, seed_ids, steps,
                                  vocab_size or self.vocab_size,
                                  gamma=gamma, temperature=temperature,
                                  rng=rng, max_length=self.max_length,
                                  top_k=top_k, top_p=top_p,
                                  prime_padded=prime_padded,
                                  stop_tokens=stop_tokens)

    def speculative_sample_batch(self, net, draft, prompts, steps: int,
                                 gamma: int = 4, vocab_size: int = None,
                                 rngs=None, temperature: float = 1.0,
                                 top_k: int = None, top_p: float = None,
                                 stop_tokens=()):
        """Batched speculative decoding with per-row acceptance (shared
        implementation util/decoding.speculative_sample_batch): one
        batched verify dispatch serves every prompt's speculation round,
        each row rewinding only its own rejections. top_k=1 reproduces
        per-prompt speculative_sample exactly. Needs rope/position-free
        attention (per-row rewind is attention-only)."""
        from deeplearning4j_tpu.util.decoding import speculative_sample_batch
        return speculative_sample_batch(net, draft, prompts, steps,
                                        vocab_size or self.vocab_size,
                                        gamma=gamma, rngs=rngs,
                                        temperature=temperature,
                                        max_length=self.max_length,
                                        top_k=top_k, top_p=top_p,
                                        stop_tokens=stop_tokens)

    def beam_search_batch(self, net, prompts, steps: int,
                          beam_width: int = 4, vocab_size: int = None,
                          stop_tokens=()):
        """Beam search over a batch of prompts — the [prompts x beams]
        grid rides the batch axis, one dispatch per step for the whole
        batch (shared implementation util/decoding.beam_search_batch).
        Returns [(best_sequence, log_prob)] per prompt."""
        from deeplearning4j_tpu.util.decoding import beam_search_batch
        return beam_search_batch(net, prompts, steps,
                                 vocab_size or self.vocab_size,
                                 beam_width=beam_width,
                                 max_length=self.max_length,
                                 stop_tokens=stop_tokens)

    def beam_search(self, net, seed_ids, steps: int, beam_width: int = 4,
                    vocab_size: int = None, prime_padded: bool = False,
                    stop_tokens=()):
        """Beam-search decoding on the streaming KV-cache machinery
        (shared implementation: util/decoding.beam_search — beams ride
        the batch dimension, pruning gathers the carried state). Returns
        (best token sequence, its log-probability)."""
        from deeplearning4j_tpu.util.decoding import beam_search
        return beam_search(net, seed_ids, steps,
                           vocab_size or self.vocab_size,
                           beam_width=beam_width,
                           max_length=self.max_length,
                           prime_padded=prime_padded,
                           stop_tokens=stop_tokens)
