"""Seq2seq decoding: BeamSearchDecoder + dynamic_decode.

Reference parity: python/paddle/fluid/layers/rnn.py BeamSearchDecoder /
dynamic_decode (exported as paddle.nn.BeamSearchDecoder,
paddle.nn.dynamic_decode).

TPU-native design: the reference drives a While loop of beam_search +
beam_search_decode ops over LoD tensors; here decoding is a dense
fixed-shape loop over ``ops.decode_extra.beam_search_step`` (top-k over
MXU-friendly [batch*beam, vocab] logits) with the backtrace done by
``gather_tree`` — the whole decode can sit inside one jit when shapes are
static.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from .. import dispatch
from ..ops.decode_extra import beam_search_step, gather_tree
from ..tensor import Tensor
from .layer import Layer

F = dispatch.wrapped_ops

__all__ = ["BeamSearchDecoder", "dynamic_decode", "sample_token",
           "fused_sample_token", "fused_verify_tokens",
           "speculative_verify_tokens"]


# ---------------------------------------------------------------------------
# Shared autoregressive sampler (jit-safe, pure JAX)
# ---------------------------------------------------------------------------

def sample_token(last, temperature: float = 0.0, top_k=None, key=None):
    """ONE sampling semantics for every decode path: greedy argmax at
    ``temperature == 0``, temperature/top-k categorical otherwise.

    ``last``: [B, V] final-position logits; returns ``(tokens [B]
    int32, new_key)``. The jitted whole-generate scan, the chunked
    per-block generate, the continuous-batching engine's prefill and
    decode steps, and the speculative verify step all call THIS
    function, so their token streams provably share one sampler
    (previously the same four lines lived in three places).
    ``temperature``/``top_k`` must be Python statics under jit; ``key``
    is unused (and may be None) on the greedy path."""
    import jax

    if temperature == 0.0:
        return jnp.argmax(last, -1).astype(jnp.int32), key
    scaled = last.astype(jnp.float32) / temperature
    if top_k is not None:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -1e10, scaled)
    key, sub = jax.random.split(key)
    return jax.random.categorical(sub, scaled, axis=-1).astype(
        jnp.int32), key


def _head_logits(hidden, weight, bias, transpose_y: bool):
    """The unfused lm_head matmul (models/gpt.py ``logits`` semantics:
    ``hidden @ W.T`` for the tied [V, D] layout, ``hidden @ W`` for the
    untied [D, V] head) — the fallback the fused sampler delegates to
    whenever streaming cannot reproduce the exact unfused behavior."""
    logits = jnp.matmul(hidden, weight.T if transpose_y else weight)
    if bias is not None:
        logits = logits + bias
    return logits


def fused_sample_token(hidden, weight, temperature: float = 0.0,
                       top_k=None, key=None, transpose_y: bool = False,
                       bias=None, tile: int = 2048):
    """:func:`sample_token` twin over FINAL HIDDEN STATES + the lm_head
    weight instead of materialized logits (the r13 fused decode hot
    path): the jitted whole-generate scan, the continuous-batching
    engine's fused prefill/decode steps and the fused speculative
    verify all call THIS function, so their token streams still share
    ONE sampler while the [B, vocab] logits tensor never reaches HBM
    on the paths that can stream it.

    ``hidden``: [B, D]; ``weight``/``transpose_y``/``bias``: the head
    layout (models/gpt.py ``head_params``). Routing:

    - greedy (``temperature == 0``): streaming argmax over vocab tiles
      (ops/pallas/fused_sample.py) — bit-identical tokens to
      ``argmax(logits)`` by the first-index tie rule;
    - ``top_k`` sampling: streaming top-k reservoir, then one
      categorical over the k candidates (the same top-k distribution;
      the [B, V] tensor still never materializes);
    - plain temperature sampling, or an active serving-mesh trace
      (vocab-sharded weights — GSPMD already keeps per-device logits
      tiles, and the tile scan would fight the sharding): the exact
      unfused logits + :func:`sample_token`.

    Returns ``(tokens [B] int32, new_key)`` like ``sample_token``."""
    import jax

    from ..ops.pallas.fused_sample import fused_sample
    from ..ops.pallas.paged_attention import get_head_sharding

    if get_head_sharding() is not None:
        return sample_token(_head_logits(hidden, weight, bias,
                                         transpose_y),
                            temperature, top_k, key)
    if temperature == 0.0:
        return fused_sample(hidden, weight, bias=bias,
                            transpose_y=transpose_y, tile=tile), key
    if top_k is not None:
        vals, idxs = fused_sample(hidden, weight, bias=bias,
                                  transpose_y=transpose_y, top_k=top_k,
                                  tile=tile)
        key, sub = jax.random.split(key)
        pick = jax.random.categorical(
            sub, vals.astype(jnp.float32) / temperature, axis=-1)
        tok = jnp.take_along_axis(idxs, pick[:, None], axis=1)[:, 0]
        return tok.astype(jnp.int32), key
    return sample_token(_head_logits(hidden, weight, bias, transpose_y),
                        temperature, top_k, key)


def fused_verify_tokens(hidden, drafts, weight, temperature: float = 0.0,
                        top_k=None, key=None, transpose_y: bool = False,
                        bias=None, tile: int = 2048):
    """:func:`speculative_verify_tokens` twin over the verify chunk's
    final hidden states [B, s, D]: on the greedy single-device path the
    per-position target tokens come from the STREAMING argmax (one
    fused scoring+acceptance program, no [B, s, V] logits in HBM);
    temperature/top-k verification needs full per-position
    distributions (acceptance probabilities + residual resampling), so
    those — and serving-mesh traces — delegate to the exact unfused
    logits + ``speculative_verify_tokens``. Same return contract."""
    from ..ops.pallas.fused_sample import fused_sample
    from ..ops.pallas.paged_attention import get_head_sharding

    b, s, d = hidden.shape
    if temperature == 0.0 and get_head_sharding() is None:
        full = fused_sample(hidden.reshape(b * s, d), weight, bias=bias,
                            transpose_y=transpose_y, tile=tile)
        full = full.reshape(b, s).astype(jnp.int32)
        accept = drafts.astype(jnp.int32) == full[:, :-1]
        return accept, full[:, :-1], full, key
    return speculative_verify_tokens(
        _head_logits(hidden, weight, bias, transpose_y), drafts,
        temperature, top_k, key)


def speculative_verify_tokens(logits, drafts, temperature: float = 0.0,
                              top_k=None, key=None):
    """Per-position accept/replace decisions for speculative decoding.

    ``logits``: [B, s, V] target-model logits over the verify chunk
    ``[cur, d_0, .., d_{s-2}]`` — position ``j`` scores the token that
    follows ``cur, d_0..d_{j-1}``. ``drafts``: [B, s-1] the draft
    tokens ``d_0..d_{s-2}``. Returns ``(accept [B, s-1] bool,
    resampled [B, s-1] int32, full [B, s] int32, key)``:

    - ``full[:, j]``: the token the target itself would emit at
      position ``j`` (``sample_token`` semantics — argmax when greedy),
      i.e. exactly the vanilla decode token given that prefix;
    - ``accept[:, j]``: whether draft ``d_j`` survives at position
      ``j`` — greedy: exact match against ``full``; temperature: a
      uniform draw under the target probability of ``d_j`` (the
      deterministic-draft acceptance rule, q = point mass);
    - ``resampled[:, j]``: the replacement token if ``j`` is the FIRST
      rejection — greedy: the argmax correction (== ``full``);
      temperature: a sample from the residual distribution (target
      probabilities with the rejected draft token's mass removed and
      renormalized), which keeps the emitted stream distributed
      exactly as the target model.

    The caller takes ``n`` = length of the leading all-accepted prefix
    (over its per-sequence valid draft count) and emits
    ``drafts[:n] + (resampled[n] if n < valid else full[valid])``."""
    import jax

    b, s, _ = logits.shape
    if temperature == 0.0:
        full = jnp.argmax(logits, -1).astype(jnp.int32)
        accept = drafts.astype(jnp.int32) == full[:, :-1]
        return accept, full[:, :-1], full, key
    scaled = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -1e10, scaled)
    probs = jax.nn.softmax(scaled, axis=-1)
    key, k_acc, k_resid, k_full = jax.random.split(key, 4)
    # full-distribution samples at every position (sample_token
    # semantics, batched over positions)
    full = jax.random.categorical(
        k_full, scaled.reshape(b * s, -1), axis=-1).reshape(
        b, s).astype(jnp.int32)
    d32 = drafts.astype(jnp.int32)
    p_draft = jnp.take_along_axis(probs[:, :-1], d32[..., None],
                                  axis=-1)[..., 0]
    u = jax.random.uniform(k_acc, (b, s - 1))
    accept = u < p_draft
    # residual: remove the rejected draft's mass, renormalize (in the
    # log domain: mask the draft token out and re-sample)
    masked = scaled[:, :-1].at[
        jnp.arange(b)[:, None], jnp.arange(s - 1)[None], d32].set(-1e10)
    resampled = jax.random.categorical(
        k_resid, masked.reshape(b * (s - 1), -1), axis=-1).reshape(
        b, s - 1).astype(jnp.int32)
    return accept, resampled, full, key


class BeamSearchDecoder:
    """Beam-search decoder over a recurrent cell (reference:
    fluid/layers/rnn.py BeamSearchDecoder).

    cell: an RNN cell ``(inputs, states) -> (output, new_states)``.
    output_fn: maps cell output -> logits over the vocabulary (e.g. the
    projection layer); defaults to identity.
    embedding_fn: maps token ids -> cell inputs; required unless the cell
    consumes raw ids.
    """

    def __init__(self, cell, start_token: int, end_token: int,
                 beam_size: int, embedding_fn: Optional[Callable] = None,
                 output_fn: Optional[Callable] = None):
        self.cell = cell
        self.start_token = int(start_token)
        self.end_token = int(end_token)
        self.beam_size = int(beam_size)
        self.embedding_fn = embedding_fn
        self.output_fn = output_fn

    # -- helpers --------------------------------------------------------------

    def _merge(self, t):
        v = t.value if isinstance(t, Tensor) else jnp.asarray(t)
        return v.reshape((-1,) + v.shape[2:])  # [B, beam, ...] -> [B*beam]

    def _split(self, v, batch):
        v = v.value if isinstance(v, Tensor) else jnp.asarray(v)
        return v.reshape((batch, self.beam_size) + v.shape[1:])

    def _logits(self, cell_out):
        out = self.output_fn(cell_out) if self.output_fn else cell_out
        return out.value if isinstance(out, Tensor) else jnp.asarray(out)

    def decode(self, initial_states, max_step_num: int):
        """Run the full beam search; returns (ids [B, T], scores [B])."""
        import jax
        # infer batch from the states pytree
        leaves = jax.tree_util.tree_leaves(
            initial_states, is_leaf=lambda t: isinstance(t, Tensor))
        batch = (leaves[0].shape[0] if leaves else 1)

        def tile_state(t):
            v = t.value if isinstance(t, Tensor) else jnp.asarray(t)
            v = jnp.repeat(v[:, None], self.beam_size, axis=1)
            return Tensor(v.reshape((-1,) + v.shape[2:]))

        states = jax.tree_util.tree_map(
            tile_state, initial_states,
            is_leaf=lambda t: isinstance(t, Tensor))

        tokens = jnp.full((batch, self.beam_size), self.start_token,
                          jnp.int32)
        # first expansion starts from one live beam per batch row
        scores = jnp.where(
            jnp.arange(self.beam_size)[None, :] == 0, 0.0, -jnp.inf
        ) * jnp.ones((batch, 1))
        finished = jnp.zeros((batch, self.beam_size), bool)
        ids_steps, parent_steps = [], []

        for _ in range(max_step_num):
            flat_tok = Tensor(tokens.reshape(-1))
            inp = self.embedding_fn(flat_tok) if self.embedding_fn \
                else flat_tok
            cell_out, states = self.cell(inp, states)
            logits = self._logits(cell_out)            # [B*beam, V]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            logp = logp.reshape(batch, self.beam_size, -1)
            scores, parent, tok = beam_search_step(
                logp, scores, self.beam_size, end_token=self.end_token,
                finished=finished)
            # reorder states along the chosen parents
            flat_parent = (parent +
                           jnp.arange(batch)[:, None] * self.beam_size
                           ).reshape(-1)
            states = jax.tree_util.tree_map(
                lambda t: Tensor(jnp.take(
                    t.value if isinstance(t, Tensor) else jnp.asarray(t),
                    flat_parent, axis=0)),
                states, is_leaf=lambda t: isinstance(t, Tensor))
            finished = jnp.take_along_axis(finished, parent, axis=1) | (
                tok == self.end_token)
            tokens = tok
            ids_steps.append(tok)
            parent_steps.append(parent)
            from jax._src import core as _jc
            if _jc.trace_state_clean() and bool(jnp.all(finished)):
                break  # eager early exit; under jit the loop is static

        ids = jnp.stack(ids_steps)                     # [T, B, beam]
        parents = jnp.stack(parent_steps)
        full = gather_tree(ids, parents)               # [T, B, beam]
        best = jnp.argmax(scores, axis=1)              # [B]
        seq = jnp.take_along_axis(
            full, best[None, :, None], axis=2)[:, :, 0]
        return Tensor(seq.swapaxes(0, 1)), Tensor(
            jnp.max(scores, axis=1))

    @staticmethod
    def tile_beam_merge_with_batch(x, beam_size):
        """reference helper: repeat batch entries beam_size times."""
        v = x.value if isinstance(x, Tensor) else jnp.asarray(x)
        v = jnp.repeat(v[:, None], beam_size, axis=1)
        return Tensor(v.reshape((-1,) + v.shape[2:]))


def dynamic_decode(decoder, inits=None, max_step_num: int = 100,
                   output_time_major: bool = False, impute_finished=False,
                   is_test: bool = False, return_length: bool = False,
                   **kwargs):
    """Drive a decoder to completion (reference: fluid/layers/rnn.py
    dynamic_decode). Returns (ids, scores) — and lengths when
    ``return_length``."""
    ids, scores = decoder.decode(inits, max_step_num)
    lengths = None
    if return_length:
        v = ids.value  # [B, T] batch-major here, before any transpose
        lengths = jnp.argmax(
            jnp.concatenate(
                [(v == decoder.end_token),
                 jnp.ones_like(v[:, :1], bool)], axis=1), axis=1)
    if output_time_major:
        ids = F["transpose"](ids, [1, 0])
    if return_length:
        return ids, scores, Tensor(lengths.astype(jnp.int32))
    return ids, scores
