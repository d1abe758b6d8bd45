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
from ..tensor import Parameter, Tensor
from .cache_layout import LayerCache, ring_pages
from .gpt import PagedKVCache

_NEG_INF = -1e30


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

def _raw(t):
    return t.value if isinstance(t, Tensor) else t


def rms_norm32(x, w, eps):
    """RMSNorm in float32; returns float32."""
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rotate(x, pos, theta: float):
    """Rotary positions over the whole head, rotate-half convention:
    x [B, S, H, D], pos [B, S] -> x's dtype."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv  # [B, S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None]
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


def dense_attention(q, k, v, window: Optional[int], scale: float):
    """Causal attention over the chunk's own keys, grouped heads, keys
    bounded by the window: the fallback where the flash kernel does not
    run (the CPU, chunks shorter than a block)."""
    b, s, hq, d = q.shape
    g = hq // k.shape[2]
    qf = q.astype(jnp.float32).reshape(b, s, k.shape[2], g, d)
    sc = jnp.einsum("bqjgd,bkjd->bjgqk", qf, k.astype(jnp.float32)) * scale
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    seen = kp <= qp
    if window is not None:
        seen = seen & (kp > qp - window)
    sc = jnp.where(seen, sc, _NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bjgqk,bkjd->bqjgd", pr, v.astype(jnp.float32))
    return out.reshape(b, s, hq, d).astype(q.dtype)


def chunk_attention(q, k, v, window: Optional[int], scale: float,
                    valid_len=None):
    """Causal attention of a right-padded prompt over itself.
    ``valid_len`` ([B] int32): the prompts' true lengths, with which
    the flash kernel skips the blocks of queries past a prompt's end
    (those rows come back zero); the rows before it do not depend on
    it, and the dense fallback takes no notice of it."""
    from ..ops.pallas.flash_attention import (flash_attention_grouped,
                                              flash_attention_supported)
    if flash_attention_supported(q.shape, k.shape):
        return flash_attention_grouped(q, k, v, window=window, scale=scale,
                                       lengths=valid_len)
    return dense_attention(q, k, v, window, scale)


def kv_append(cache: PagedKVCache, k, v, valid_len=None, ring=False):
    """Write the chunk's K/V ([B, S, KVH, D]) into heads-major pools
    ([P, KVH, page, D]) and advance the lengths.

    ``ring=False``: position ``p`` goes to table entry ``p // page``
    (the allocator's pages). ``ring=True``: ``cache.page_table`` is the
    slot's ring ``[B, R]`` and position ``p`` goes to ring page ``(p //
    page) % R``; of a prompt longer than the ring only the last R
    pages' worth is written. Empty rows (length 0 at a single-token
    step), pages past a prompt's end and positions past the table go to
    the pool's scratch page.

    Both writes index the pool's LEADING axis alone, so XLA scatters in
    place into the donated pool (an index on axes 0 and 2 of a
    heads-major pool costs two layout copies of the whole pool a call):
    a single token a sequence (``valid_len`` None) writes ``B * KVH``
    rows of the pool seen as ``[P * KVH * page, D]``; a prompt
    (``valid_len`` [B], into FRESH slots: positions from 0) writes whole
    pages, and what its last page holds past ``valid_len`` is padding
    that the lengths hide until decoding overwrites it."""
    b, s, kvh, d = k.shape
    n_pool, _, page, _ = cache.k_pages.shape
    width = cache.page_table.shape[1]
    scratch = n_pool - 1

    def table_pages(entry, keep):
        if ring:
            idx = entry % width
        else:
            keep = keep & (entry < width)
            idx = jnp.minimum(entry, width - 1)
        pages = jnp.take_along_axis(cache.page_table, idx, axis=1)
        return jnp.where(keep, pages, scratch), keep

    if valid_len is None:
        pos = cache.seq_lens[:, None]  # [B, 1]
        pages, keep = table_pages(pos // page, pos > 0)
        off = jnp.where(keep, pos % page, 0)
        rows = ((pages * kvh + jnp.arange(kvh, dtype=jnp.int32)[None])
                * page + off).reshape(-1)  # [B * KVH]
        new_lens = cache.seq_lens + 1
        if not ring:
            new_lens = jnp.minimum(new_lens, width * page)

        def put(pool, val):
            flat = pool.reshape(n_pool * kvh * page, d)
            flat = flat.at[rows].set(
                val.reshape(b * kvh, d).astype(pool.dtype))
            return flat.reshape(pool.shape)
    else:
        new_lens = valid_len.astype(jnp.int32)
        n_pg = -(-s // page)
        entry = jnp.arange(n_pg, dtype=jnp.int32)[None]
        last = ((new_lens - 1) // page)[:, None]  # -1: an empty row
        keep = entry <= last
        if ring:
            keep = keep & (entry > last - width)
        else:
            new_lens = jnp.minimum(new_lens, width * page)
        pages, _ = table_pages(jnp.broadcast_to(entry, (b, n_pg)), keep)
        pages = pages.reshape(-1)

        def put(pool, val):
            val = jnp.pad(val, ((0, 0), (0, n_pg * page - s), (0, 0),
                                (0, 0)))
            val = val.reshape(b, n_pg, page, kvh, d).swapaxes(2, 3)
            return pool.at[pages].set(
                val.reshape(b * n_pg, kvh, page, d).astype(pool.dtype))

    return PagedKVCache(put(cache.k_pages, k), put(cache.v_pages, v),
                        None, None, cache.page_table, new_lens)


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


class ServedDecoderLM(Layer):
    """What the decoders served from shapes share: the parameters are
    built as shapes first, then initialised or loaded.

    ``abstract=True`` builds the parameters as shapes only
    (``jax.ShapeDtypeStruct``): nothing is initialised, and
    :meth:`load_weights` then puts the real arrays in. A serving
    process whose weights come from elsewhere (the benchmark, a
    checkpoint) never holds two copies of an 11 GB model. A subclass
    names its ``body`` (the Layer built from ``(config, make)``) and
    gives ``cache_layout`` and ``decode_hidden``."""

    body = None

    def __init__(self, config, abstract: bool = False, seed: int = 0):
        super().__init__()
        self.config = config
        wdt = jnp.dtype(config.dtype)
        specs = []

        def make(shape, init, dtype=None):
            """``init``: a standard deviation, "norm" (ones, float32)
            or ``f(key, shape) -> float32 array``."""
            dt = jnp.dtype("float32") if init == "norm" else \
                jnp.dtype(dtype or wdt)
            p = Parameter(jax.ShapeDtypeStruct(tuple(shape), dt),
                          trainable=False)
            specs.append((p, init))
            return p

        self.model = self.body(config, make)
        self.lm_head = make((config.vocab_rows, config.hidden_size),
                            config.initializer_range)
        self._specs = specs
        self._stats = None
        if not abstract:
            self.init_weights(seed)

    # -- weights ------------------------------------------------------------

    def init_weights(self, seed: int = 0) -> None:
        """N(0, std) a matrix, norms 1, what a leaf's own rule gives:
        one jitted call for the whole model."""
        specs = self._specs

        shapes = [(p.shape, p.dtype, init) for p, init in specs]

        def build(key):
            out = []
            for j, (shape, dt, init) in enumerate(shapes):
                k = jax.random.fold_in(key, j)
                if init == "norm":
                    out.append(jnp.ones(shape, dt))
                elif callable(init):
                    out.append(init(k, shape).astype(dt))
                else:
                    out.append((jax.random.normal(k, shape, jnp.float32)
                                * init).astype(dt))
            return out

        vals = jax.jit(build)(jax.random.key(int(seed)))
        for (p, _), v in zip(specs, vals):
            p.value = v

    def load_weights(self, weights: dict) -> None:
        """``{parameter name: array}``, leaf for leaf the model's own
        names, shapes and types."""
        named = dict(self.named_parameters())
        if set(named) != set(weights):
            raise ValueError(f"the model's parameters and the weights "
                             f"differ: {sorted(set(named) ^ set(weights))[:6]}")
        for name, p in named.items():
            w = weights[name]
            if tuple(w.shape) != tuple(p.shape) or w.dtype != p.dtype:
                raise ValueError(
                    f"{name}: model {tuple(p.shape)} {p.dtype}, weights "
                    f"{tuple(w.shape)} {w.dtype}")
            p.value = w

    # -- what the engine asks of any of them ----------------------------------

    def head_params(self):
        """``(weight [V, D], transpose_y, bias)`` for the streaming
        sampler: the untied head, rows by token like an embedding."""
        return self.lm_head, True, None

    def pop_step_stats(self):
        """The routing counters of the forward just traced, as int32
        scalars of the program: ``{"moe": {...}}``. A single-token step
        reports ``touched`` (distinct experts hit by live rows, summed
        over layers) and ``max_load`` (the most picks one expert got in
        a layer); a prefill ``max_over_mean_x1000`` (the fullest
        expert's picks over the mean, the worst layer, in
        thousandths)."""
        out, self._stats = self._stats, None
        return out

    def _keep_stats(self, counts, prefill: bool) -> None:
        """``counts``: picks computed per held expert, a row a layer."""
        cnt = jnp.stack(counts)  # [layers, held]
        if not prefill:
            self._stats = {"moe": {
                "touched": jnp.sum(cnt > 0).astype(jnp.int32),
                "max_load": jnp.max(cnt).astype(jnp.int32)}}
            return
        mean = jnp.maximum(jnp.sum(cnt, axis=1), 1) / cnt.shape[1]
        self._stats = {"moe": {"max_over_mean_x1000": jnp.max(
            jnp.max(cnt, axis=1) / mean * 1000.0).astype(jnp.int32)}}

    def logits(self, hidden):
        return jnp.matmul(_raw(hidden), _raw(self.lm_head).T)

    def forward(self, input_ids, caches=None, prefill_lens=None,
                prefill_chained=False):
        """Logits. With ``caches``: ``(logits, new_caches)`` through the
        cached path; without: the whole sequence at once, nothing
        stored."""
        if caches is None:
            b, s = _raw(input_ids).shape
            hidden, _ = self.decode_hidden(
                input_ids, None, prefill_lens=jnp.full((b,), s, jnp.int32))
            self._stats = None
            return self.logits(hidden)
        hidden, nc = self.decode_hidden(
            input_ids, caches, prefill_lens=prefill_lens,
            prefill_chained=prefill_chained)
        return self.logits(hidden), nc


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
