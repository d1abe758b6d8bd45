"""SmallThinker: a decoder whose router reads the layer's input.

The layer equations (PowerInfer/SmallThinker-21BA3B-Instruct
``config.json``; layer ``l``, input ``x``):

- ``h = RMSNorm_1(x)``; the router runs HERE, before attention:
  ``r = h W_r`` over all experts, the ``k`` largest picked, gates a
  softmax over the picked logits in float32;
- ``q, k, v = h W_q, h W_k, h W_v`` with grouped heads (query heads
  ``G j .. G j + G - 1`` read KV head ``j``); ``rope_layout[l]`` 1:
  rotary positions on q and k (rotate-half, whole head), 0: no
  positions at all; ``sliding_window_layout[l]`` 1: a query at ``p``
  sees keys ``p - window + 1 .. p``, 0: every key up to ``p``;
- ``y = x + Attn W_o``; ``u = RMSNorm_2(y)``; ``x' = y + sum_e g_e
  (relu(u W_gate^e) * (u W_up^e)) W_down^e`` over the picked experts
  (distributed/moe.py ``dropless_experts``: no capacity, no drop);
- a final RMSNorm and an untied head.

Serving surface: the one the engine calls on any decoder —
``cache_layout()``, ``decode_hidden``, ``forward(caches=...)``,
``head_params()`` — plus ``pop_step_stats()``, the routing counters of
the program being traced. Window layers keep their K/V in a ring a slot
(models/cache_layout.py); the cache holds rotated K in rotary layers.
The forward is plain ``jax.numpy`` on the parameters' values: serving
only, no autograd tape.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.container import LayerList
from ..nn.layer import Layer
from .cache_layout import LayerCache
from .gpt import PagedKVCache
from .served import (ServedDecoderLM, _raw, chunk_attention, kv_append,
                     rms_norm32, rotate)


@dataclasses.dataclass
class SmallThinkerConfig:
    """The published keys under their published names, then what a
    deployment adds."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 16384
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    rope_layout: Tuple[int, ...] = ()
    sliding_window_layout: Tuple[int, ...] = ()
    sliding_window_size: int = 4096
    tie_word_embeddings: bool = False
    # a deployment's cut: the expert ids held here as (first, count),
    # None for all; the rows of the vocabulary held, None for all
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[int] = None
    dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.rope_layout:
            self.rope_layout = tuple([0, 1, 1, 1] * n)[:n]
        if not self.sliding_window_layout:
            self.sliding_window_layout = tuple([0, 1, 1, 1] * n)[:n]
        self.rope_layout = tuple(int(v) for v in self.rope_layout)
        self.sliding_window_layout = tuple(
            int(v) for v in self.sliding_window_layout)
        if len(self.rope_layout) != n or \
                len(self.sliding_window_layout) != n:
            raise ValueError("rope_layout and sliding_window_layout give "
                             "one entry a layer")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if not (self.moe_primary_router_apply_softmax
                and self.norm_topk_prob):
            raise NotImplementedError(
                "only softmax gates renormalised over the picks")
        if self.tie_word_embeddings:
            raise NotImplementedError("the head is untied")
        if self.experts_held is not None:
            first, count = self.experts_held
            if first < 0 or count < 1 or \
                    first + count > self.moe_num_primary_experts:
                raise ValueError(f"experts_held {self.experts_held}")
            self.experts_held = (int(first), int(count))

    # the names the engine and the server read on any decoder
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def num_experts_held(self) -> int:
        return (self.moe_num_primary_experts if self.experts_held is None
                else self.experts_held[1])

    @property
    def vocab_rows(self) -> int:
        return self.vocab_held or self.vocab_size


def smallthinker_tiny(**kw):
    """One period at toy widths, for the CPU tests: a group of 2 query
    heads a KV head, a window of 8 over pages of 4."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                max_position_embeddings=128, moe_ffn_hidden_size=32,
                moe_num_primary_experts=8,
                moe_num_active_primary_experts=2, sliding_window_size=8,
                rope_theta=10000.0)
    base.update(kw)
    return SmallThinkerConfig(**base)


def smallthinker_21b_a3b(num_layers: int = 52, **kw):
    """The published configuration; ``num_layers`` cuts the depth to
    whole periods of (global, window, window, window)."""
    return SmallThinkerConfig(num_hidden_layers=num_layers, **kw)


# -- the mathematics, on raw arrays ----------------------------------------

def ring_view(cache: PagedKVCache, window: int):
    """A window layer's ring as the paged kernel reads it: the page
    table turned so that entry 0 is the page that holds the oldest key
    the newest position sees, the lengths and the bound counted from
    that page's first position. Returns ``(table, lens, kv_start)``."""
    page = cache.k_pages.shape[2]
    ring = cache.page_table.shape[1]
    lo = jnp.maximum(cache.seq_lens - window, 0)
    first = lo // page
    turn = (first[:, None] + jnp.arange(ring, dtype=jnp.int32)[None]) % ring
    table = jnp.take_along_axis(cache.page_table, turn, axis=1)
    return table, cache.seq_lens - first * page, lo - first * page


class SmallThinkerBlock(Layer):
    def __init__(self, cfg: SmallThinkerConfig, make):
        super().__init__()
        h, d = cfg.hidden_size, cfg.head_dim
        hq, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        e, f = cfg.num_experts_held, cfg.moe_ffn_hidden_size
        res = cfg.initializer_range / math.sqrt(2.0 * cfg.num_hidden_layers)
        std = cfg.initializer_range
        self.ln1 = make((h,), "norm")
        self.wq = make((h, hq * d), std)
        self.wk = make((h, kv * d), std)
        self.wv = make((h, kv * d), std)
        self.wo = make((hq * d, h), res)
        self.ln2 = make((h,), "norm")
        self.router = make((h, cfg.moe_num_primary_experts), std,
                           dtype="float32")
        self.w_gate = make((e, h, f), std)
        self.w_up = make((e, h, f), std)
        self.w_down = make((e, f, h), res)


class SmallThinkerModel(Layer):
    def __init__(self, cfg: SmallThinkerConfig, make):
        super().__init__()
        self.embed = make((cfg.vocab_rows, cfg.hidden_size),
                          cfg.initializer_range)
        self.layers = LayerList([SmallThinkerBlock(cfg, make)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = make((cfg.hidden_size,), "norm")


class SmallThinkerForCausalLM(ServedDecoderLM):
    body = SmallThinkerModel

    # -- what the engine asks -------------------------------------------------

    def cache_layout(self):
        c = self.config
        dt = jnp.dtype(c.dtype)
        return [LayerCache(c.num_key_value_heads, c.head_dim,
                           c.sliding_window_size if w else None, dt,
                           heads_major=True)
                for w in c.sliding_window_layout]

    def decode_hidden(self, input_ids, caches, prefill_lens=None,
                      prefill_chained=False):
        """Cached forward to the final hidden states: ``(hidden [B, S,
        D], new_caches)``. ``prefill_lens``: a right-padded prompt into
        FRESH slots. Without it, one token a sequence. ``caches=None``
        with ``prefill_lens``: the same forward, nothing stored."""
        from ..distributed.moe import dropless_experts, route_top_k
        from ..ops.pallas.paged_attention import paged_attention_grouped

        c = self.config
        ids = _raw(input_ids)
        b, s = ids.shape
        if prefill_chained:
            raise NotImplementedError(
                "a prefill that continues stored keys (prefix hits, "
                "chunks) with window layers")
        if prefill_lens is None and s != 1:
            raise NotImplementedError(
                "several tokens a sequence without prefill_lens")
        lens = (jnp.zeros((b,), jnp.int32) if caches is None
                else caches[0].seq_lens)
        if prefill_lens is None:
            live = jnp.broadcast_to((lens > 0)[:, None], (b, s))
        else:
            live = jnp.arange(s, dtype=jnp.int32)[None] < \
                prefill_lens[:, None]
        pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        hq, kvh, d = (c.num_attention_heads, c.num_key_value_heads,
                      c.head_dim)
        scale = 1.0 / math.sqrt(d)
        top_k = c.moe_num_active_primary_experts
        x = _raw(self.model.embed)[ids]
        dt = x.dtype
        new_caches, counts = [], []
        for i, blk in enumerate(self.model.layers):
            cache = None if caches is None else caches[i]
            window = (c.sliding_window_size
                      if c.sliding_window_layout[i] else None)
            h32 = rms_norm32(x, _raw(blk.ln1), c.rms_norm_eps)
            with jax.named_scope("pt.moe.router"):
                idx, gates = route_top_k(
                    h32.reshape(b * s, -1), _raw(blk.router), top_k)
            h = h32.astype(dt)
            q = jnp.matmul(h, _raw(blk.wq)).reshape(b, s, hq, d)
            k = jnp.matmul(h, _raw(blk.wk)).reshape(b, s, kvh, d)
            v = jnp.matmul(h, _raw(blk.wv)).reshape(b, s, kvh, d)
            if c.rope_layout[i]:
                q = rotate(q, pos, c.rope_theta)
                k = rotate(k, pos, c.rope_theta)
            with jax.named_scope("pt.attn.window" if window
                                 else "pt.attn.global"):
                nc = None if cache is None else kv_append(
                    cache, k, v, valid_len=prefill_lens,
                    ring=window is not None)
                if prefill_lens is not None:
                    att = chunk_attention(q, k, v, window, scale,
                                          valid_len=prefill_lens)
                elif window is None:
                    att = paged_attention_grouped(
                        q, nc.k_pages, nc.v_pages, nc.page_table,
                        nc.seq_lens, scale=scale)
                else:
                    table, wl, lo = ring_view(nc, window)
                    att = paged_attention_grouped(
                        q, nc.k_pages, nc.v_pages, table, wl,
                        kv_start=lo, scale=scale)
            new_caches.append(nc)
            y = x + jnp.matmul(att.reshape(b, s, hq * d), _raw(blk.wo))
            u = rms_norm32(y, _raw(blk.ln2), c.rms_norm_eps).astype(dt)
            with jax.named_scope("pt.moe.experts"):
                m, cnt = dropless_experts(
                    u.reshape(b * s, -1), idx, gates, _raw(blk.w_gate),
                    _raw(blk.w_up), _raw(blk.w_down),
                    held=c.experts_held, valid=live.reshape(-1),
                    activation="relu")
            counts.append(cnt)
            x = y + m.reshape(b, s, -1)
            if prefill_lens is not None:
                # a long prompt's expert layer holds about 1 GB of rows
                # laid out by expert: one layer's at a time
                x = jax.lax.optimization_barrier(x)
        x = rms_norm32(x, _raw(self.model.norm), c.rms_norm_eps).astype(dt)
        self._keep_stats(counts, prefill_lens is not None)
        return x, new_caches
