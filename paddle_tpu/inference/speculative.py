"""Speculative decoding over the paged continuous-batching engine.

Decode at b128 runs 1.63x off its own measured streaming floor
(pre-round decode trace): every emitted token re-reads the full weight
set and the KV prefix once. Speculative decoding amortizes that stream
over multiple tokens per step — a cheap DRAFT proposes ``k`` tokens,
the target model scores all ``k+1`` positions in ONE forward (the
chained-prefill ragged paged-attention path, models/gpt.py
``verify_step``), and the longest accepted prefix is emitted together
with one correction/bonus token. Greedy outputs are BIT-IDENTICAL to
the vanilla engine: acceptance is exact-match against the target's own
argmax, so a wrong draft costs only speed, never tokens.

This module holds the HOST half — draft sources and the config the
engine consumes (`ContinuousBatchingEngine(speculative=...)`); the
device half (verify forward + accept/resample math) lives in
models/gpt.py ``verify_step`` and nn/decode.py
``speculative_verify_tokens``. Draft sources are duck-typed::

    propose(histories, k) -> np.ndarray [len(histories), k] int32

where ``histories[i]`` is slot i's full token history (prompt +
generated, None for an empty slot). A draft's QUALITY moves the
acceptance rate; its correctness is irrelevant to the output stream —
which is why the n-gram source may guess from padded context and the
model source may truncate its context window without ceremony.

Paper basis: *Ragged Paged Attention* (PAPERS.md) — the multi-token
verify is exactly its q_len>1 ragged prefill over a non-empty slot;
fused multi-token steps echo *Operator Fusion for LLM Inference on the
Tensix Architecture* (PAPERS.md).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

__all__ = ["SpeculativeConfig", "NGramDraft", "ModelDraft",
           "CallableDraft", "SelfDraft", "as_spec_config"]


class NGramDraft:
    """Prompt-lookup drafting: no second model, no device work.

    For each sequence, take the longest suffix of length
    ``max_ngram .. min_ngram`` that re-occurs EARLIER in the history
    (most recent occurrence wins) and propose the ``k`` tokens that
    followed it there. Greedy decode of a fixed model is eventually
    periodic and real text is self-repeating (system prompts, code,
    quoted spans), so this accepts surprisingly often for zero draft
    cost. No match -> repeat the last token (a cheap guess; rejection
    only costs the step its speedup)."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if min_ngram < 1 or max_ngram < min_ngram:
            raise ValueError("need 1 <= min_ngram <= max_ngram")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def _lookup(self, h: np.ndarray, k: int) -> np.ndarray:
        n = len(h)
        out = np.full((k,), h[-1], np.int32)
        for g in range(min(self.max_ngram, n - 1), self.min_ngram - 1,
                       -1):
            pat = h[n - g:]
            # most recent earlier occurrence, vectorized: windows over
            # h[:n-1] end at e <= n-1 (the suffix itself, ending at n,
            # is excluded); this runs per active slot per engine step,
            # so it must not be a per-offset Python loop over the
            # whole history
            wins = np.lib.stride_tricks.sliding_window_view(
                h[:n - 1], g)
            hits = np.nonzero((wins == pat).all(axis=1))[0]
            if len(hits):
                e = int(hits[-1]) + g  # end (exclusive) of the match
                cont = h[e:e + k]
                out[:len(cont)] = cont
                out[len(cont):] = cont[-1]
                return out
        return out

    def propose(self, histories: Sequence[Optional[np.ndarray]],
                k: int) -> np.ndarray:
        out = np.zeros((len(histories), k), np.int32)
        for i, h in enumerate(histories):
            if h is None or len(h) == 0:
                continue
            out[i] = self._lookup(np.asarray(h, np.int32), k)
        return out


class ModelDraft:
    """A small causal LM drafting greedily for the target.

    The draft runs STATELESSLY over a fixed context window holding the
    last ``window`` tokens RIGHT-padded (real tokens at positions
    0..len-1, so causal attention never sees padding before a real
    token and drafting is EXACT while the history fits the window) —
    one jitted program scans ``k`` greedy steps, each a full no-cache
    forward, so the whole proposal is one device launch per engine
    step with no draft-side KV bookkeeping. Once the history exceeds
    the window it is truncated to its tail (positions restart at 0);
    that can only lower acceptance, never correctness — the verify
    step is the sole authority on emitted tokens. The draft's vocab
    must not exceed the target's (the engine clips defensively)."""

    def __init__(self, model, window: int = 64):
        model.eval()
        self.model = model
        self.window = int(window)
        self._jits = {}
        self._state = None

    def _build(self, k: int):
        import jax

        from ..autograd.engine import no_grad
        from ..nn.decode import sample_token
        from ..nn.layer import bind_state
        from ..tensor import Tensor

        model = self.model
        w = self.window

        def raw(t):
            return t.value if isinstance(t, Tensor) else t

        def draft(state, ctx, lens):
            import jax.numpy as jnp

            # single-device trace guard (same as GPT _generate_jit): a
            # live fleet group's hybrid-mesh activation constraints
            # must not reach the draft program
            from ..distributed.mp_layers import no_sharding_constraints

            b = ctx.shape[0]

            def body(carry, _):
                c, l = carry  # noqa: E741
                with bind_state(model, state), no_grad():
                    logits = raw(model.forward(Tensor(c)))
                last = jnp.take_along_axis(
                    logits, jnp.maximum(l - 1, 0)[:, None, None],
                    axis=1)[:, 0]
                nxt, _ = sample_token(last, 0.0)
                # grow in place until the window fills, then slide
                full = (l >= w)[:, None]
                slid = jnp.concatenate(
                    [c[:, 1:], jnp.zeros((b, 1), c.dtype)], axis=1)
                c = jnp.where(full, slid, c)
                pos = jnp.minimum(l, w - 1)
                c = c.at[jnp.arange(b), pos].set(nxt)
                return (c, jnp.minimum(l + 1, w)), nxt

            with no_sharding_constraints():
                _, toks = jax.lax.scan(body, (ctx, lens), None,
                                       length=k)
            return toks.swapaxes(0, 1)  # [B, k]

        return jax.jit(draft)

    def propose(self, histories: Sequence[Optional[np.ndarray]],
                k: int) -> np.ndarray:
        from ..nn.layer import functional_state

        w = self.window
        ctx = np.zeros((len(histories), w), np.int32)
        lens = np.zeros((len(histories),), np.int32)
        for i, h in enumerate(histories):
            if h is None or len(h) == 0:
                continue
            tail = np.asarray(h, np.int32)[-w:]
            ctx[i, :len(tail)] = tail
            lens[i] = len(tail)
        if k not in self._jits:
            self._jits[k] = self._build(k)
        if self._state is None:  # draft weights are frozen post-build
            self._state = functional_state(self.model)
        return np.asarray(self._jits[k](self._state, ctx, lens),
                          np.int32)


class SelfDraft:
    """Repeat the last emitted token ``k`` times. The degenerate
    prompt-lookup draft (NGramDraft's no-match fallback, promoted to
    the whole policy): free to compute and surprisingly effective on
    runs of repeated tokens (whitespace, padding, looping greedy
    tails). Exists mostly as a bisection rung between "spec off" and
    "ngram"."""

    def propose(self, histories: Sequence[Optional[np.ndarray]],
                k: int) -> np.ndarray:
        out = np.zeros((len(histories), k), np.int32)
        for i, h in enumerate(histories):
            if h is None or len(h) == 0:
                continue
            out[i, :] = int(np.asarray(h)[-1])
        return out


class CallableDraft:
    """Adapter for a plain function ``fn(history, k) -> k tokens`` —
    tests use it to build adversarial (always-wrong) drafts that force
    rejection storms, benches to build oracle drafts."""

    def __init__(self, fn: Callable[[np.ndarray, int], Sequence[int]]):
        self.fn = fn

    def propose(self, histories: Sequence[Optional[np.ndarray]],
                k: int) -> np.ndarray:
        out = np.zeros((len(histories), k), np.int32)
        for i, h in enumerate(histories):
            if h is None or len(h) == 0:
                continue
            toks = np.asarray(self.fn(np.asarray(h, np.int32), k),
                              np.int32).reshape(-1)[:k]
            out[i, :len(toks)] = toks
            if len(toks) < k:
                out[i, len(toks):] = toks[-1] if len(toks) else 0
        return out


@dataclasses.dataclass
class SpeculativeConfig:
    """Engine-side speculative-decoding knobs.

    ``draft``: "ngram" (prompt lookup, no second model), a model layer
    (wrapped in ModelDraft), or any object with a ``propose`` method.
    ``k``: draft tokens per verify step — each step emits between 1
    and k+1 tokens. ``temperature``/``top_k``: sampling mode of the
    verify step (0.0 = the greedy serving mode, bit-identical to the
    vanilla engine; >0 uses residual-distribution resampling and is
    exact-in-distribution, not bit-pinned)."""

    k: int = 4
    draft: Any = "ngram"
    temperature: float = 0.0
    top_k: Optional[int] = None
    max_ngram: int = 3
    min_ngram: int = 1
    draft_window: int = 64
    seed: int = 0

    def __post_init__(self):
        if self.k < 1:
            raise ValueError("speculative k must be >= 1")

    def build_draft(self):
        d = self.draft
        if isinstance(d, str):
            if d == "self":
                return SelfDraft()
            if d != "ngram":
                raise ValueError(f"unknown draft source {d!r} "
                                 f"(expected 'ngram', 'self', a model "
                                 f"layer or a propose()-object)")
            return NGramDraft(self.max_ngram, self.min_ngram)
        if hasattr(d, "propose"):
            return d
        if callable(getattr(d, "forward", None)):
            return ModelDraft(d, window=self.draft_window)
        raise ValueError(f"cannot build a draft source from {d!r}")


def as_spec_config(spec) -> "SpeculativeConfig":
    """Coerce the engine's ``speculative=`` argument: a
    SpeculativeConfig passes through, an int means k with the n-gram
    draft, anything draft-shaped becomes the draft at default k."""
    if isinstance(spec, SpeculativeConfig):
        return spec
    if isinstance(spec, bool):
        raise ValueError("speculative must be a SpeculativeConfig, an "
                         "int k, or a draft source — not a bool")
    if isinstance(spec, int):
        return SpeculativeConfig(k=spec)
    return SpeculativeConfig(draft=spec)
