"""GLM-4 MoE Lite: multi-head latent attention over a cache of one row
a position, a leading dense layer, routed experts with a shared one.

The layer equations (zai-org/GLM-4.7-Flash ``config.json``,
``model_type: glm4_moe_lite``; DeepSeek-V2/V3's, key for key; layer
``l``, input ``x`` [T, hidden], ``H`` heads):

- ``h = RMSNorm_1(x)``; ``y = x + MLA(h)``; ``u = RMSNorm_2(y)``; ``x' =
  y + FFN_l(u)``; a final RMSNorm and an untied head.
- queries: ``c_q = RMSNorm_q(h W_qa)`` (rank ``q_lora_rank``), ``q = c_q
  W_qb``, a head's ``q_i = [q_i^nope | q_i^rope]``, the second part
  rotated (rotate-half over its ``qk_rope_head_dim``).
- latent: ``[c_raw | k_raw^rope] = h W_kva``; ``c = RMSNorm_kv(c_raw)``
  (``kv_lora_rank``); ``k^rope`` rotated: ONE rotary key for all heads.
  **What position t leaves in the cache is the row ``[c | k^rope]``.**
- expanded form (the definition, and a prompt's path): a head's
  ``k_i^nope = c W_UK,i`` and ``v_i = c W_UV,i`` (the two column blocks
  of head ``i`` in the published ``W_kvb``), ``k_i = [k_i^nope |
  k^rope]``, causal softmax of ``q_i . k_i / sqrt(nope + rope)``, ``o_i
  = sum p v_i``, ``MLA = concat_i(o_i) W_o``.
- absorbed form (a decoded token's path; equal by associativity):
  ``q~_i = q_i^nope W_UK,i^T`` (``kv_lora_rank`` long), the score at
  position ``s`` is ``(q~_i . c_s + q_i^rope . k_s^rope) / sqrt(nope +
  rope)``, ``o~_i = sum p_s c_s``, ``o_i = o~_i W_UV,i``: the kernel
  (ops/pallas/paged_attention.py ``paged_attention_latent``) sees the
  rows ``[q~_i | q_i^rope]`` and the cache rows alone, and a page is
  the keys and the values of every head.
- ``FFN_l``, ``l < first_k_dense_replace``: ``(silu(u W_gate) * (u
  W_up)) W_down`` of ``intermediate_size``; else ``s = sigmoid(u W_r)``
  over all experts, the ``num_experts_per_tok`` largest ``s + b``
  picked (``n_group`` 1: no group limit), gates the picked ``s``
  normalised to 1 times ``routed_scaling_factor``, the picked experts
  (``dropless_experts``) plus one shared expert (models/solar_open2.py
  ``sigmoid_moe``).

``W_UK`` and ``W_UV`` are the model's own leaves ``w_uk`` [H, rank,
nope] and ``w_uv`` [H, rank, v]: ``W_kvb`` re-laid by head once, at
load (:func:`split_kv_b`), and read by both forms, so no second copy
of it exists. The multi-token-prediction module
(``num_nextn_predict_layers``) takes no part in the next-token logits
and is not built.

Serving surface: the one the engine calls on any decoder. A layer's
memory is a :class:`~.cache_layout.LatentCache`. A prompt attends to
its own positions alone (fresh slots: no prefix hit, no chunk), in the
expanded form through the flash kernel; the absorbed form over pages
is the single-token step's. Plain ``jax.numpy`` on the parameters'
values: serving only, no autograd tape.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn.container import LayerList
from ..nn.layer import Layer
from .cache_layout import LatentCache, LayerCache
from .served import (ServedDecoderLM, _raw, chunk_attention,
                     init_router_bias, latent_append, rms_norm32, rotate,
                     sigmoid_moe)


@dataclasses.dataclass
class Glm4MoeLiteConfig:
    """The published keys under their published names, then what a
    deployment adds."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    num_key_value_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    hidden_act: str = "silu"
    attention_bias: bool = False
    rope_theta: float = 1e6
    rope_scaling: Optional[dict] = None
    partial_rotary_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 202752
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 1  # a training head: not built
    # positions of a prompt that go through a feed-forward layer at a
    # time
    prefill_segment: int = 8192
    dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        unsupported = [
            ("rope_scaling", self.rope_scaling is not None),
            ("a rotary part of the rotary key",
             float(self.partial_rotary_factor) != 1.0),
            ("attention_bias", self.attention_bias),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("expert groups", self.n_group != 1 or self.topk_group != 1),
            ("gates not normalised over the picks",
             not self.norm_topk_prob),
            ("other than one shared expert", self.n_shared_experts != 1),
            ("an activation other than silu", self.hidden_act != "silu"),
            ("a router other than noaux_tc",
             self.topk_method != "noaux_tc"),
            ("grouped latent heads",
             self.num_key_value_heads != self.num_attention_heads),
            ("values of another size than the keys (the prompt's "
             "attention kernel has one head size)",
             self.v_head_dim != self.qk_head_dim)]
        for what, asked in unsupported:
            if asked:
                raise NotImplementedError(what)

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
    def vocab_rows(self) -> int:
        return self.vocab_size

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def glm4_moe_lite_tiny(**kw):
    """A dense layer and two expert layers at toy widths, for the CPU
    tests: 4 heads of 16 + 8 against values of 24, a latent of 32, 8
    experts top-2."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=8,
                num_experts_per_tok=2, max_position_embeddings=256,
                rope_theta=10000.0, prefill_segment=16)
    base.update(kw)
    return Glm4MoeLiteConfig(**base)


def glm4_7_flash(num_layers: int = 47, **kw):
    """The published configuration; ``num_layers`` cuts the depth (the
    first ``first_k_dense_replace`` layers held stay dense)."""
    return Glm4MoeLiteConfig(num_hidden_layers=num_layers, **kw)


def split_kv_b(w_kvb, heads: int, nope: int, v: int):
    """The published ``W_kvb`` ``[rank, H * (nope + v)]`` (a head's
    columns: its ``nope`` key columns, then its ``v`` value columns) as
    the model's two leaves ``(w_uk [H, rank, nope], w_uv [H, rank,
    v])``."""
    rank = w_kvb.shape[0]
    by_head = w_kvb.reshape(rank, heads, nope + v).swapaxes(0, 1)
    return by_head[:, :, :nope], by_head[:, :, nope:]


class Glm4MoeLiteBlock(Layer):
    def __init__(self, cfg: Glm4MoeLiteConfig, make, dense: bool):
        super().__init__()
        h, n = cfg.hidden_size, cfg.num_attention_heads
        rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        nope, v = cfg.qk_nope_head_dim, cfg.v_head_dim
        res = cfg.initializer_range / math.sqrt(2.0 * cfg.num_hidden_layers)
        std = cfg.initializer_range
        self.ln1 = make((h,), "norm")
        self.wq_a = make((h, cfg.q_lora_rank), std)
        self.q_norm = make((cfg.q_lora_rank,), "norm")
        self.wq_b = make((cfg.q_lora_rank, n * (nope + rope)), std)
        self.wkv_a = make((h, rank + rope), std)
        self.kv_norm = make((rank,), "norm")
        self.w_uk = make((n, rank, nope), std)
        self.w_uv = make((n, rank, v), std)
        self.wo = make((n * v, h), res)
        self.ln2 = make((h,), "norm")
        if dense:
            f = cfg.intermediate_size
            self.wd_gate = make((h, f), std)
            self.wd_up = make((h, f), std)
            self.wd_down = make((f, h), res)
            return
        e, f = cfg.n_routed_experts, cfg.moe_intermediate_size
        self.router = make((h, e), std, dtype="float32")
        self.router_bias = make((e,), init_router_bias, dtype="float32")
        self.w_gate = make((e, h, f), std)
        self.w_up = make((e, h, f), std)
        self.w_down = make((e, f, h), res)
        self.ws_gate = make((h, f), std)
        self.ws_up = make((h, f), std)
        self.ws_down = make((f, h), res)


class Glm4MoeLiteModel(Layer):
    def __init__(self, cfg: Glm4MoeLiteConfig, make):
        super().__init__()
        self.embed = make((cfg.vocab_rows, cfg.hidden_size),
                          cfg.initializer_range)
        self.layers = LayerList([
            Glm4MoeLiteBlock(cfg, make, i < cfg.first_k_dense_replace)
            for i in range(cfg.num_hidden_layers)])
        self.norm = make((cfg.hidden_size,), "norm")


class Glm4MoeLiteForCausalLM(ServedDecoderLM):
    body = Glm4MoeLiteModel

    # -- what the engine asks -------------------------------------------------

    def cache_layout(self):
        c = self.config
        lc = LayerCache(c.num_attention_heads, c.qk_head_dim, None,
                        jnp.dtype(c.dtype),
                        latent=(c.kv_lora_rank, c.qk_rope_head_dim))
        return [lc] * c.num_hidden_layers

    # -- the mixer ------------------------------------------------------------

    def _queries(self, blk, h, pos):
        """``(q^nope [B, S, H, nope], rotated q^rope [B, S, H, rope])``."""
        c = self.config
        b, s, _ = h.shape
        cq = rms_norm32(jnp.matmul(h, _raw(blk.wq_a)), _raw(blk.q_norm),
                        c.rms_norm_eps).astype(h.dtype)
        q = jnp.matmul(cq, _raw(blk.wq_b)).reshape(
            b, s, c.num_attention_heads, c.qk_head_dim)
        return (q[..., :c.qk_nope_head_dim],
                rotate(q[..., c.qk_nope_head_dim:], pos, c.rope_theta))

    def _latent_rows(self, blk, h, pos):
        """What the positions leave in the cache: ``[c | k^rope]``
        [B, S, rank + rope]."""
        c = self.config
        kv = jnp.matmul(h, _raw(blk.wkv_a))
        lat = rms_norm32(kv[..., :c.kv_lora_rank], _raw(blk.kv_norm),
                         c.rms_norm_eps).astype(h.dtype)
        k_rope = rotate(kv[..., None, c.kv_lora_rank:], pos,
                        c.rope_theta)[:, :, 0]
        return jnp.concatenate([lat, k_rope], axis=-1)

    def _mla(self, blk, h, cache, pos, prefill_lens):
        from ..ops.pallas.paged_attention import paged_attention_latent
        c = self.config
        b, s, _ = h.shape
        n, rank = c.num_attention_heads, c.kv_lora_rank
        scale = 1.0 / math.sqrt(c.qk_head_dim)
        q_nope, q_rope = self._queries(blk, h, pos)
        rows = self._latent_rows(blk, h, pos)
        nc = None if cache is None else latent_append(
            cache, rows, valid_len=prefill_lens)
        if prefill_lens is not None:
            # expanded: a prompt sees its own positions alone
            lat, k_rope = rows[..., :rank], rows[..., None, rank:]
            k = jnp.concatenate([
                jnp.einsum("bsr,hrn->bshn", lat, _raw(blk.w_uk)),
                jnp.broadcast_to(k_rope, (b, s, n, k_rope.shape[-1]))],
                axis=-1)
            v = jnp.einsum("bsr,hrv->bshv", lat, _raw(blk.w_uv))
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            o = chunk_attention(q, k, v, None, scale,
                                valid_len=prefill_lens)
        else:
            # absorbed: the slot's pages are keys and values at once
            qa = jnp.concatenate([
                jnp.einsum("bhn,hrn->bhr", q_nope[:, 0], _raw(blk.w_uk)),
                q_rope[:, 0]], axis=-1)
            lens = jnp.where(cache.seq_lens > 0, nc.seq_lens, 0)
            oa = paged_attention_latent(qa, nc.pages, nc.page_table, lens,
                                        rank, scale)
            o = jnp.einsum("bhr,hrv->bhv", oa, _raw(blk.w_uv))[:, None]
        return jnp.matmul(o.reshape(b, s, -1), _raw(blk.wo)), nc

    # -- the dense layer ------------------------------------------------------

    def _dense_ffn(self, blk, y):
        """``y + FFN(RMSNorm_2(y))``, ``prefill_segment`` positions at a
        time (a long prompt's gate and up would stand whole)."""
        from ..distributed.moe import gated_ffn
        c = self.config
        shape = y.shape
        y = y.reshape(-1, shape[-1])
        out = []
        for lo in range(0, y.shape[0], c.prefill_segment):
            ys = y[lo:lo + c.prefill_segment]
            if out:
                ys, _ = jax.lax.optimization_barrier((ys, out[-1]))
            u = rms_norm32(ys, _raw(blk.ln2), c.rms_norm_eps).astype(y.dtype)
            with jax.named_scope("pt.ffn.dense"):
                out.append(ys + gated_ffn(u, _raw(blk.wd_gate),
                                          _raw(blk.wd_up),
                                          _raw(blk.wd_down), "silu"))
        y = out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)
        return y.reshape(shape)

    # -- the forward ----------------------------------------------------------

    def decode_hidden(self, input_ids, caches, prefill_lens=None,
                      prefill_chained=False):
        """Cached forward to the final hidden states: ``(hidden [B, S,
        D], new_caches)``. ``prefill_lens``: a right-padded prompt into
        FRESH slots. Without it, one token a sequence. ``caches=None``
        with ``prefill_lens``: the same forward, nothing stored."""
        c = self.config
        ids = _raw(input_ids)
        b, s = ids.shape
        if prefill_chained:
            raise NotImplementedError(
                "a prefill that attends to stored pages (prefix hits, "
                "chunks): the absorbed form for a prompt")
        if prefill_lens is None and s != 1:
            raise NotImplementedError(
                "several tokens a sequence without prefill_lens")
        if prefill_lens is None:
            pos = caches[0].seq_lens[:, None]
            valid = pos > 0
        else:
            prefill_lens = prefill_lens.astype(jnp.int32)
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                   (b, s))
            valid = pos < prefill_lens[:, None]
        x = _raw(self.model.embed)[ids]
        dt = x.dtype
        new_caches, counts = [], []
        for i, blk in enumerate(self.model.layers):
            cache = None if caches is None else caches[i]
            h = rms_norm32(x, _raw(blk.ln1), c.rms_norm_eps).astype(dt)
            with jax.named_scope("pt.attn.latent"):
                mix, nc = self._mla(blk, h, cache, pos, prefill_lens)
            new_caches.append(nc)
            if i < c.first_k_dense_replace:
                x = self._dense_ffn(blk, x + mix)
            else:
                x, cnt = sigmoid_moe(
                    blk, x + mix, valid, eps=c.rms_norm_eps,
                    top_k=c.num_experts_per_tok,
                    scaling=c.routed_scaling_factor,
                    segment=c.prefill_segment)
                counts.append(cnt)
            if prefill_lens is not None:
                # a long prompt's layers one at a time
                x = jax.lax.optimization_barrier(x)
        x = rms_norm32(x, _raw(self.model.norm), c.rms_norm_eps).astype(dt)
        if counts:
            self._keep_stats(counts, prefill_lens is not None)
        return x, new_caches
