"""What the serving engine asks a decoder about the memory it keeps a
sequence.

``model.cache_layout()`` returns one :class:`LayerCache` a layer. The
engine builds a pool a layer from it and never reads head counts from a
config. A layer names a KIND of memory: keys and values by position
(``state`` None), or a state of fixed size a sequence (``state`` set: a
recurrent layer). A key-value layer with ``window=None`` keeps every
position, in pages the engine's allocator hands out and one page table
addresses (as GPT's layers all do); a layer with a window keeps the
last ``window`` positions, in a ring of :func:`ring_pages` pages a slot that lives in
the layer's own pool and needs no allocator: slot ``r`` owns pool pages
``r * R .. r * R + R - 1`` and position ``p`` lies in ring page
``(p // page) % R``.

``heads_major`` says how a page lies in the pool: ``False`` is
``[P, page, KVH, D]`` (GPT's, one KV head a query head, read on the
VPU); ``True`` is ``[P, KVH, page, D]``, which grouped heads want: each
KV head's page is a ``[page, D]`` tile for the MXU
(ops/pallas/paged_attention.py ``paged_attention_grouped``).

A state layer keeps ``state`` = (heads, key size, value size) float32 a
sequence and, where it has a causal convolution, the last ``conv`` =
(taps - 1, channels) inputs of it in ``dtype``: row ``r`` of both pools
belongs to slot ``r`` and the row behind the slots is scratch
(:class:`StateCache`). No page table addresses it, its size does not
grow with the sequence, and it is UPDATED where keys are appended: a
step computed twice moves it twice, and what it was at an earlier
position is gone. What has to restore, share, rewind or re-enter a
sequence's memory is therefore refused for such a layer
(``UnsupportedCacheLayout``) until it keeps snapshots.

A latent layer (``latent`` = (rank, rope): multi-head latent attention)
keeps every position in the allocator's pages, under the one page
table, but what a position leaves is ONE row for all heads: the
normed latent of ``rank`` values and the rotated key of ``rope``
shared by the heads. It has one pool and no V pool, ``[P, page,
latent_width]``: a page is at once the keys and the values of the
absorbed decode (ops/pallas/paged_attention.py
``paged_attention_latent``). A latent entry with ``pooled`` = n > 1
keeps ONE row for n consecutive positions (a page holds ``page / n``
rows, under the same page table): the pooled keys of a sparse
attention's indexer (models/glm5_next.py). A row is stored
``latent_width`` wide, ``rank + rope`` rounded up to whole lane tiles
of 128 with zeros behind: the chip's tiling of a pool's minor axis
would pad it so in memory anyway, and the kernel then reads whole
tiles. The page codecs
(prefix cache, spill, handoff), int8 scales, the verify path and a
mesh's head sharding all read ``[page, H, D]`` pairs: refused for this
layout like the others.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerCache:
    kv_heads: int
    head_dim: int
    window: Optional[int]  # None: every position is kept
    dtype: Any
    heads_major: bool = False
    state: Optional[Tuple[int, int, int]] = None  # a state layer's
    conv: Optional[Tuple[int, int]] = None
    latent: Optional[Tuple[int, int]] = None  # a latent layer's
    pooled: int = 1  # positions a latent row stands for

    @property
    def plain(self) -> bool:
        """GPT's layout: what every engine option was written for."""
        return self.window is None and not self.heads_major \
            and self.state is None and self.latent is None

    @property
    def latent_width(self) -> int:
        """Values a position's row is stored as: whole lane tiles."""
        return -(-sum(self.latent) // 128) * 128


class StateCache(NamedTuple):
    """A state layer's memory as the model's forward sees it: the two
    pools whole (``state`` [slots + 1, H, d_k, d_v] float32, ``tail``
    [slots + 1, taps - 1, channels]), the pool row of each batch row
    (``rows`` [B]) and the lengths stored so far (``seq_lens`` [B]: 0
    is a parked slot, whose row a single-token step leaves alone)."""
    state: Any
    tail: Any
    rows: Any
    seq_lens: Any


class LatentCache(NamedTuple):
    """A latent layer's memory as the model's forward sees it: the one
    pool (``pages`` [P + 1, page, width]: a position's row is its
    latent, the shared rotated key, zeros up to ``width``), the
    allocator's page table ``[B, max_pages]`` and the lengths stored so
    far (``seq_lens`` [B]: 0 is a parked slot)."""
    pages: Any
    page_table: Any
    seq_lens: Any


def create_state_pools(lc: LayerCache, slots: int):
    """``(state, tail)`` of one state layer: a row a slot and the
    scratch row behind them, zero-filled."""
    taps, channels = lc.conv or (0, 0)
    return (jnp.zeros((slots + 1,) + tuple(lc.state), jnp.float32),
            jnp.zeros((slots + 1, taps, channels), lc.dtype))


def create_pools(lc: LayerCache, pages: int, page_size: int,
                 max_pages: int = 1, quantized: bool = False,
                 kv_sharding=None):
    """``(k_pages, v_pages, k_scale, v_scale)`` of one layer: ``pages``
    pages and the scratch page behind them, zero-filled. A latent layer
    has the first alone."""
    if lc.latent is not None:
        return (jnp.zeros((pages + 1, page_size // lc.pooled,
                           lc.latent_width), lc.dtype), None, None, None)
    if lc.heads_major:
        shape = (pages + 1, lc.kv_heads, page_size, lc.head_dim)
        return (jnp.zeros(shape, lc.dtype), jnp.zeros(shape, lc.dtype),
                None, None)
    from .gpt import paged_cache_create
    c = paged_cache_create(1, pages, page_size, lc.kv_heads, lc.head_dim,
                           lc.dtype, max_pages, quantized=quantized,
                           kv_sharding=kv_sharding)
    return c.k_pages, c.v_pages, c.k_scale, c.v_scale


def ring_pages(window: int, page_size: int) -> int:
    """Pages a window layer holds a sequence: ``ceil(window / page)``
    for the window, one more where the window starts inside a page, and
    the page being filled."""
    return -(-int(window) // int(page_size)) + 2


def ring_table(rows, ring: int):
    """``[B, ring]`` pool pages of the rings of slots ``rows`` ([B])."""
    return rows[:, None].astype(jnp.int32) * ring + \
        jnp.arange(ring, dtype=jnp.int32)[None]


class UnsupportedCacheLayout(ValueError):
    """An engine option that a model's cache layout cannot serve yet."""
