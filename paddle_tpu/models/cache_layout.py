"""What the serving engine asks a decoder about its KV cache.

``model.cache_layout()`` returns one :class:`LayerCache` a layer. The
engine builds a pool a layer from it and never reads head counts from a
config: a layer with ``window=None`` keeps every position, in pages the
engine's allocator hands out and one page table addresses (as GPT's
layers all do); a layer with a window keeps the last ``window``
positions, in a ring of :func:`ring_pages` pages a slot that lives in
the layer's own pool and needs no allocator: slot ``r`` owns pool pages
``r * R .. r * R + R - 1`` and position ``p`` lies in ring page
``(p // page) % R``.

``heads_major`` says how a page lies in the pool: ``False`` is
``[P, page, KVH, D]`` (GPT's, one KV head a query head, read on the
VPU); ``True`` is ``[P, KVH, page, D]``, which grouped heads want: each
KV head's page is a ``[page, D]`` tile for the MXU
(ops/pallas/paged_attention.py ``paged_attention_grouped``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerCache:
    kv_heads: int
    head_dim: int
    window: Optional[int]  # None: every position is kept
    dtype: Any
    heads_major: bool = False

    @property
    def plain(self) -> bool:
        """GPT's layout: what every engine option was written for."""
        return self.window is None and not self.heads_major


def create_pools(lc: LayerCache, pages: int, page_size: int,
                 max_pages: int = 1, quantized: bool = False,
                 kv_sharding=None):
    """``(k_pages, v_pages, k_scale, v_scale)`` of one layer: ``pages``
    pages and the scratch page behind them, zero-filled."""
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
