"""What the served decoders share: the parameters built as shapes and
loaded (:class:`ServedDecoderLM`), the float32 norm, rotary positions,
a prompt's causal attention over itself, the appends into a layer's
pages (keys and values by head, or one latent row a position), the
causal depthwise convolution of the state layers, and the expert layer
whose router scores every expert by a sigmoid beside one shared
expert. Plain ``jax.numpy`` on the parameters' values: serving only, no
autograd tape.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..tensor import Parameter, Tensor
from .cache_layout import LatentCache
from .gpt import PagedKVCache

_NEG_INF = -1e30
F32 = jnp.float32


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


def latent_append(cache: LatentCache, rows, valid_len=None) -> LatentCache:
    """Write the chunk's rows ([B, S, <= W]: latent, rotated key) into
    the pool ``[P, page, W]``, zeros behind them, and advance the
    lengths. A single token a sequence (``valid_len`` None) goes to
    position ``seq_lens`` of its slot; a prompt (``valid_len`` [B],
    into FRESH slots: positions from 0) writes whole pages, and what
    its last page holds past ``valid_len`` is padding that the lengths
    hide until decoding overwrites it. Empty rows (length 0 at a
    single-token step), pages past a prompt's end and positions past
    the table go to the pool's scratch page. Both writes index the
    pool's leading axis alone, so XLA scatters in place into the donated
    pool (``kv_append``)."""
    b, s, _ = rows.shape
    n_pool, page, w = cache.pages.shape
    width = cache.page_table.shape[1]
    scratch = n_pool - 1
    rows = jnp.pad(rows.astype(cache.pages.dtype),
                   ((0, 0), (0, 0), (0, w - rows.shape[-1])))
    if valid_len is None:
        pos = cache.seq_lens
        keep = (pos > 0) & (pos < width * page)
        pages = jnp.take_along_axis(
            cache.page_table, jnp.minimum(pos // page, width - 1)[:, None],
            axis=1)[:, 0]
        at = jnp.where(keep, pages * page + pos % page, scratch * page)
        pool = cache.pages.reshape(n_pool * page, w).at[at].set(
            rows[:, 0]).reshape(cache.pages.shape)
        new_lens = jnp.minimum(pos + 1, width * page)
    else:
        new_lens = jnp.minimum(valid_len.astype(jnp.int32), width * page)
        n_pg = -(-s // page)
        entry = jnp.arange(n_pg, dtype=jnp.int32)[None]
        keep = (entry <= ((new_lens - 1) // page)[:, None]) & \
            (entry < width)
        pages = jnp.take_along_axis(
            cache.page_table,
            jnp.broadcast_to(jnp.minimum(entry, width - 1), (b, n_pg)),
            axis=1)
        pages = jnp.where(keep, pages, scratch).reshape(-1)
        rows = jnp.pad(rows, ((0, 0), (0, n_pg * page - s), (0, 0)))
        pool = cache.pages.at[pages].set(
            rows.reshape(b * n_pg, page, w))
    return LatentCache(pool, cache.page_table, new_lens)


def causal_conv(x, tail, w, bias=None):
    """``out[t] = sum_i w[i] in[t - taps + 1 + i] (+ bias)`` a channel,
    ``in`` the sequence ``x`` [B, T, C] behind the ``tail`` [B, taps - 1,
    C] of what came before it (zeros at a sequence's start). Returns
    ``(out [B, T, C] float32, tail + x)``."""
    t = x.shape[1]
    seen = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(F32)
    out = sum(seen[:, i:i + t].astype(F32) * wf[i]
              for i in range(w.shape[0]))
    if bias is not None:
        out = out + bias.astype(F32)
    return out, seen


def init_router_bias(key, shape):
    return jax.random.normal(key, shape, F32) * 0.01


def sigmoid_moe(blk, y, valid, *, eps, top_k, scaling, segment, held=None,
                activation="silu", residual=True):
    """``y + MoE(RMSNorm_2(y))`` (``MoE(...)`` alone without
    ``residual``) of a layer whose router scores every
    expert by a sigmoid and which has one shared expert (``blk``: its
    ``ln2``, ``router``, ``router_bias``, ``w_gate`` / ``w_up`` /
    ``w_down`` by expert and ``ws_*`` of the shared one), and the picks
    computed per held expert. A prompt goes through ``segment``
    positions at a time: the rows laid out by expert are sized for
    EVERY pick of the rows given (none is dropped), eight times what a
    chip that holds an eighth of the experts gets of a long prompt."""
    from ..distributed.moe import (dropless_experts, gated_ffn,
                                   route_sigmoid_top_k)
    shape = y.shape
    y = y.reshape(-1, shape[-1])
    valid = valid.reshape(-1)
    out, cnt = [], 0
    for lo in range(0, y.shape[0], segment):
        ys = y[lo:lo + segment]
        if out:
            # one segment's rows at a time
            ys, _ = jax.lax.optimization_barrier((ys, out[-1]))
        u32 = rms_norm32(ys, _raw(blk.ln2), eps)
        with jax.named_scope("pt.moe.router"):
            idx, gates = route_sigmoid_top_k(
                u32, _raw(blk.router), _raw(blk.router_bias), top_k,
                scaling)
        u = u32.astype(y.dtype)
        with jax.named_scope("pt.moe.experts"):
            m, n = dropless_experts(
                u, idx, gates, _raw(blk.w_gate), _raw(blk.w_up),
                _raw(blk.w_down), held=held,
                valid=valid[lo:lo + segment], activation=activation)
        with jax.named_scope("pt.moe.shared"):
            m = m + gated_ffn(u, _raw(blk.ws_gate), _raw(blk.ws_up),
                              _raw(blk.ws_down), activation)
        out.append(ys + m if residual else m)
        cnt = cnt + n
    y = out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)
    return y.reshape(shape), cnt


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
