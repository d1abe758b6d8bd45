"""Falcon-H1: a Mamba-2 state-space branch beside attention in every
block, both reading the same normed input, their outputs summed into the
residual; µP multipliers on every branch.

The layer equations (tiiuae/Falcon-H1-34B-Instruct ``config.json``,
``model_type: falcon_h1``; the SSM branch is Mamba-2's, Dao & Gu 2024,
arXiv:2405.21060; block ``l``, input ``x`` [T, hidden]; ``m_*`` the
config's multipliers):

- ``h = RMSNorm_in(x)``.
- attention: ``a = h m_attn_in``; ``q = a W_q`` (``num_attention_heads``
  of ``head_dim``), ``k = (a W_k) m_key``, ``v = a W_v``
  (``num_key_value_heads``); rotary, rotate-half over the whole head at
  ``rope_theta``; causal softmax of ``q k^T / sqrt(d)``, query head
  ``i`` reading KV head ``i // (heads / KV heads)``; ``A_out = concat
  W_o m_attn_out``.
- SSM (the ``mamba_*`` keys): ``u = h m_ssm_in``; ``[z | x | B | C | dt]
  = u W_in``, each slice times its ``ssm_multipliers`` entry in that
  order; ``[x | B | C] = silu(conv(x | B | C) + b_conv)``, ``conv`` causal
  and depthwise over ``mamba_d_conv`` taps (``out[t] = sum_i w[i] in[t -
  taps + 1 + i]``, zeros before the sequence); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` a head; a head's state ``S [d_state,
  d_head]`` float32 moves by ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``
  with its group's B and C (``mamba_n_groups``, head ``h`` in group ``h
  // (heads / groups)``), ``y_t = S_t^T C_t + D x_t`` (ops/pallas/ssd.py);
  ``y = RMSNorm_grouped(y * silu(z))`` over ``mamba_n_groups`` groups,
  the gate before the norm (``mamba_norm_before_gate`` false);
  ``S_out = y W_out m_ssm_out``.
- ``x_1 = x + A_out + S_out``; ``x' = x_1 + (silu((g W_gate) m_gate) *
  (g W_up)) W_down m_down``, ``g = RMSNorm_ff(x_1)`` (``mlp_multipliers``).
- the embedding ``E[id] m_embed``; the logits ``RMSNorm_final(x_L) W_head
  m_head``. No bias anywhere but the convolution's.

Where a multiplier meets a linear map it is applied to the map's output
in float32, except ``m_head``, which is folded into the final norm's
weight (the engine's sampler multiplies the hidden state by the head
alone), and ``m_ssm_in`` with the five slice multipliers, one vector on
the in-projection's output. None is dropped.

Serving surface: the one the engine calls on any decoder. A block keeps
two memories, so :meth:`cache_layout` gives two entries a block: the
attention's keys and values in the allocator's pages (heads-major, as
grouped heads want) and the SSM's :class:`~.cache_layout.StateCache`
(the state and the convolution's last inputs, a row a slot, updated in
place at a single-token step and written from zero by a prompt). A long
prompt runs through the SSM branch and the MLP ``prefill_segment``
positions at a time, the state handed on. Plain ``jax.numpy`` on the
parameters' values: serving only, no autograd tape.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.container import LayerList
from ..nn.layer import Layer
from .cache_layout import LayerCache, StateCache
from .served import (ServedDecoderLM, _raw, causal_conv, chunk_attention,
                     kv_append, rms_norm32, rotate)
from .solar_open2 import init_a_log, init_conv, init_dt_bias

F32 = jnp.float32


@dataclasses.dataclass
class FalconH1Config:
    """The published keys under their published names, then what a
    deployment adds."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    attn_layer_indices: Optional[Tuple[int, ...]] = None
    embedding_multiplier: float = 5.656854249492381
    key_multiplier: float = 0.011048543456039804
    lm_head_multiplier: float = 0.0078125
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 128
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_expand: int = 2
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_norm_before_gate: bool = False
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_use_mlp: bool = True
    max_position_embeddings: int = 262144
    mlp_bias: bool = False
    mlp_expansion_factor: int = 8
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    projectors_bias: bool = False
    rms_norm_eps: float = 1e-5
    rope_scaling: Optional[dict] = None
    rope_theta: float = 1e11
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    tie_word_embeddings: bool = False
    # positions of a prompt that go through the SSM branch and the MLP
    # at a time
    prefill_segment: int = 8192
    dtype: str = "float32"

    def __post_init__(self):
        # a config file writes 1e11 as the integer 100000000000, which
        # no int32 holds: the scalars the forward multiplies by are floats
        for key in ("rope_theta", "attention_in_multiplier",
                    "attention_out_multiplier", "embedding_multiplier",
                    "key_multiplier", "lm_head_multiplier",
                    "ssm_in_multiplier", "ssm_out_multiplier"):
            setattr(self, key, float(getattr(self, key)))
        self.mlp_multipliers = tuple(float(m) for m in self.mlp_multipliers)
        self.ssm_multipliers = tuple(float(m) for m in self.ssm_multipliers)
        unsupported = [
            ("a bias on a projection", self.attention_bias
             or self.mamba_proj_bias or self.mlp_bias
             or self.projectors_bias),
            ("a convolution without a bias", not self.mamba_conv_bias),
            ("the gate after the SSM norm", self.mamba_norm_before_gate),
            ("an SSM output without its norm", not self.mamba_rms_norm),
            ("a block without its MLP", not self.mamba_use_mlp),
            ("attention in some blocks alone",
             self.attn_layer_indices is not None),
            ("rope_scaling", self.rope_scaling is not None),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("an activation other than silu", self.hidden_act != "silu")]
        for what, asked in unsupported:
            if asked:
                raise NotImplementedError(what)
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if self.mamba_n_heads % self.mamba_n_groups or \
                self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("SSM heads x head size must be mamba_d_ssm, "
                             "heads a multiple of groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("five ssm_multipliers (z, x, B, C, dt) and "
                             "two mlp_multipliers (gate, down)")

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
    def initializer_range(self) -> float:
        """The head's std (ServedDecoderLM makes the head with it):
        N(0, 1/sqrt(hidden)), under the head multiplier."""
        return self.hidden_size ** -0.5

    @property
    def conv_channels(self) -> int:
        """x, B and C: what the convolution sees."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_width(self) -> int:
        """z, x, B, C and dt: the in-projection's outputs."""
        return self.mamba_d_ssm + self.conv_channels + self.mamba_n_heads


def falcon_h1_tiny(**kw):
    """Two blocks at toy widths, for the CPU tests: 4 query heads over 2
    KV heads of 16, 4 SSM heads of 8 in 2 groups, a state of 16."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, mamba_d_ssm=32,
                mamba_d_head=8, mamba_n_heads=4, mamba_n_groups=2,
                mamba_d_state=16, mamba_chunk_size=16,
                max_position_embeddings=256, rope_theta=10000.0,
                prefill_segment=16)
    base.update(kw)
    return FalconH1Config(**base)


def falcon_h1_34b(num_layers: int = 72, **kw):
    """The published configuration; ``num_layers`` cuts the depth."""
    return FalconH1Config(num_hidden_layers=num_layers, **kw)


def leaf_stds(cfg: FalconH1Config) -> dict:
    """A matrix's std: ``1/sqrt(fan_in)`` over the constant multipliers
    that scale its output on the way to the residual, so that each
    branch adds at unit scale and every multiplier is seen; the slice
    multipliers of the in-projection are left to act."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    hd = cfg.num_attention_heads * cfg.head_dim
    m_gate, m_down = cfg.mlp_multipliers
    return {
        "embed": 1.0 / cfg.embedding_multiplier,
        "wq": 1.0 / (cfg.attention_in_multiplier * math.sqrt(h)),
        "wk": 1.0 / (cfg.attention_in_multiplier * cfg.key_multiplier
                     * math.sqrt(h)),
        "wv": 1.0 / (cfg.attention_in_multiplier * math.sqrt(h)),
        "wo": 1.0 / (cfg.attention_out_multiplier * math.sqrt(hd)),
        "w_in": 1.0 / (cfg.ssm_in_multiplier * math.sqrt(h)),
        "w_out": 1.0 / (cfg.ssm_out_multiplier * math.sqrt(cfg.mamba_d_ssm)),
        "w_gate": 1.0 / (m_gate * math.sqrt(h)),
        "w_up": 1.0 / math.sqrt(h),
        "w_down": 1.0 / (m_down * math.sqrt(f)),
    }


def init_conv_bias(taps: int):
    """``U(-1/sqrt(taps), 1/sqrt(taps))``: a depthwise convolution's
    usual start, as its taps'."""
    bound = 1.0 / math.sqrt(taps)
    return lambda key, shape: jax.random.uniform(key, shape, F32, -bound,
                                                 bound)


def init_d_skip(key, shape):
    """``D = 1``: Mamba-2's."""
    return jnp.ones(shape, F32)


class FalconH1Block(Layer):
    def __init__(self, cfg: FalconH1Config, make):
        super().__init__()
        h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
        hq, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        std = leaf_stds(cfg)
        self.ln_in = make((h,), "norm")
        self.wq = make((h, hq * d), std["wq"])
        self.wk = make((h, kv * d), std["wk"])
        self.wv = make((h, kv * d), std["wv"])
        self.wo = make((hq * d, h), std["wo"])
        n = cfg.mamba_n_heads
        self.w_in = make((h, cfg.in_width), std["w_in"])
        self.conv = make((cfg.mamba_d_conv, cfg.conv_channels), init_conv)
        self.conv_bias = make((cfg.conv_channels,),
                              init_conv_bias(cfg.mamba_d_conv))
        self.dt_bias = make((n,), init_dt_bias, dtype="float32")
        self.a_log = make((n,), init_a_log, dtype="float32")
        self.d_skip = make((n,), init_d_skip, dtype="float32")
        self.ssm_norm = make((cfg.mamba_d_ssm,), "norm")
        self.w_out = make((cfg.mamba_d_ssm, h), std["w_out"])
        self.ln_ff = make((h,), "norm")
        self.w_gate = make((h, f), std["w_gate"])
        self.w_up = make((h, f), std["w_up"])
        self.w_down = make((f, h), std["w_down"])


class FalconH1Model(Layer):
    def __init__(self, cfg: FalconH1Config, make):
        super().__init__()
        self.embed = make((cfg.vocab_rows, cfg.hidden_size),
                          leaf_stds(cfg)["embed"])
        self.layers = LayerList([FalconH1Block(cfg, make)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = make((cfg.hidden_size,), "norm")


def gated_group_norm(y, z, w, groups: int, eps: float):
    """``RMSNorm_grouped(y * silu(z)) * w`` over ``groups`` equal groups
    of the last axis, in float32."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    lead = g.shape[:-1]
    g = g.reshape(lead + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(lead + (-1,)) * w.astype(F32)


class FalconH1ForCausalLM(ServedDecoderLM):
    body = FalconH1Model

    # -- what the engine asks -------------------------------------------------

    def cache_layout(self):
        """Two entries a block: its keys and values, then its state."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        n, p = c.mamba_n_heads, c.mamba_d_head
        kv = LayerCache(c.num_key_value_heads, c.head_dim, None, dt,
                        heads_major=True)
        state = LayerCache(n, p, None, dt, state=(n, c.mamba_d_state, p),
                           conv=(c.mamba_d_conv - 1, c.conv_channels))
        return [kv, state] * c.num_hidden_layers

    # -- the attention branch -------------------------------------------------

    def _attention(self, blk, h, cache, pos, prefill_lens):
        from ..ops.pallas.paged_attention import paged_attention_grouped
        c = self.config
        b, s, _ = h.shape
        hq, kvh, d = (c.num_attention_heads, c.num_key_value_heads,
                      c.head_dim)
        a = (h.astype(F32) * c.attention_in_multiplier).astype(h.dtype)
        q = jnp.matmul(a, _raw(blk.wq)).reshape(b, s, hq, d)
        k = (jnp.matmul(a, _raw(blk.wk), preferred_element_type=F32)
             * c.key_multiplier).astype(h.dtype).reshape(b, s, kvh, d)
        v = jnp.matmul(a, _raw(blk.wv)).reshape(b, s, kvh, d)
        q, k = rotate(q, pos, c.rope_theta), rotate(k, pos, c.rope_theta)
        scale = 1.0 / math.sqrt(d)
        nc = None if cache is None else kv_append(
            cache, k, v, valid_len=prefill_lens)
        if prefill_lens is not None:
            att = chunk_attention(q, k, v, None, scale,
                                  valid_len=prefill_lens)
        else:
            att = paged_attention_grouped(
                q, nc.k_pages, nc.v_pages, nc.page_table, nc.seq_lens,
                scale=scale)
        out = jnp.matmul(att.reshape(b, s, hq * d), _raw(blk.wo),
                         preferred_element_type=F32)
        return out * c.attention_out_multiplier, nc

    # -- the SSM branch -------------------------------------------------------

    def _in_scale(self):
        """``m_ssm_in`` times each slice's multiplier: one vector over
        the in-projection's outputs."""
        c = self.config
        widths = (c.mamba_d_ssm, c.mamba_d_ssm,
                  c.mamba_n_groups * c.mamba_d_state,
                  c.mamba_n_groups * c.mamba_d_state, c.mamba_n_heads)
        return jnp.concatenate([
            jnp.full((w,), m * c.ssm_in_multiplier, F32)
            for w, m in zip(widths, c.ssm_multipliers)])

    def _ssm_inputs(self, blk, h, tail):
        """From the normed input ``h`` [B, T, hidden] and the
        convolution's tail: ``(z f32, x, B, C in h's dtype, dt f32, what
        the convolution has seen)``."""
        c = self.config
        proj = jnp.matmul(h, _raw(blk.w_in),
                          preferred_element_type=F32) * self._in_scale()
        d_ssm = c.mamba_d_ssm
        z = proj[..., :d_ssm]
        xbc = proj[..., d_ssm:d_ssm + c.conv_channels].astype(h.dtype)
        conv, seen = causal_conv(xbc, tail, _raw(blk.conv),
                                 _raw(blk.conv_bias))
        xbc = jax.nn.silu(conv).astype(h.dtype)
        gn = c.mamba_n_groups * c.mamba_d_state
        x, bm, cm = (xbc[..., :d_ssm], xbc[..., d_ssm:d_ssm + gn],
                     xbc[..., d_ssm + gn:])
        dt = jax.nn.softplus(proj[..., d_ssm + c.conv_channels:]
                             + _raw(blk.dt_bias))
        return z, x, bm, cm, dt, seen

    def _ssm_output(self, blk, x, y, z):
        """``RMSNorm_grouped((y + D x) * silu(z)) W_out m_ssm_out``, f32."""
        c = self.config
        lead = y.shape[:-1]
        n, p = c.mamba_n_heads, c.mamba_d_head
        y = (y.astype(F32).reshape(lead + (n, p))
             + _raw(blk.d_skip)[:, None] * x.astype(F32).reshape(
                 lead + (n, p))).reshape(lead + (n * p,))
        y = gated_group_norm(y, z, _raw(blk.ssm_norm), c.mamba_n_groups,
                             c.rms_norm_eps)
        return jnp.matmul(y.astype(x.dtype), _raw(blk.w_out),
                          preferred_element_type=F32) * c.ssm_out_multiplier

    def _ssm_token(self, blk, h, cache: StateCache, live):
        """One token a sequence: the slot's state moved in place, a
        parked slot's row left alone."""
        from ..ops.pallas.ssd import ssd_decode
        c = self.config
        b = h.shape[0]
        n, g, p, ns = (c.mamba_n_heads, c.mamba_n_groups, c.mamba_d_head,
                       c.mamba_d_state)
        rows = cache.rows
        z, x, bm, cm, dt, seen = self._ssm_inputs(blk, h, cache.tail[rows])
        y, state = ssd_decode(
            x.reshape(b, n, p), dt.reshape(b, n), -jnp.exp(_raw(blk.a_log)),
            bm.reshape(b, g, ns), cm.reshape(b, g, ns), cache.state, rows,
            live)
        at = jnp.where(live, rows, cache.tail.shape[0] - 1)
        tail = cache.tail.at[at].set(seen[:, 1:])
        nc = StateCache(state, tail, rows, cache.seq_lens + 1)
        return self._ssm_output(blk, x, y.reshape(b, 1, n * p), z), nc

    def _ssm_prompt(self, blk, h, cache: Optional[StateCache], lens):
        """Right-padded prompts into fresh slots: the chunked scan a
        segment at a time, the final state and the convolution's tail
        left in each slot's row."""
        from ..ops.pallas.ssd import ssd_chunk_fwd
        c = self.config
        n, p, taps = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_conv
        b, s, _ = h.shape
        state = jnp.zeros((b, n, c.mamba_d_state, p), F32)
        tail = jnp.zeros((b, taps - 1, c.conv_channels), h.dtype)
        a = -jnp.exp(_raw(blk.a_log))
        out = []
        for lo in range(0, s, c.prefill_segment):
            hs = h[:, lo:lo + c.prefill_segment]
            if lo:
                # one segment's projections at a time: the next one's
                # wait for this one's state
                hs, state = jax.lax.optimization_barrier((hs, state))
            left = jnp.clip(lens - lo, 0, hs.shape[1])
            z, x, bm, cm, dt, seen = self._ssm_inputs(blk, hs, tail)
            y, state = ssd_chunk_fwd(x, dt, a, bm, cm, state, left,
                                     groups=c.mamba_n_groups,
                                     chunk=c.mamba_chunk_size)
            # the inputs at the segment's last taps - 1 true positions
            # (the tail as it came in, where the segment holds none)
            tail = jax.vmap(lambda v, at: jax.lax.dynamic_slice_in_dim(
                v, at, taps - 1, 0))(seen, left)
            out.append(self._ssm_output(blk, x, y, z))
        nc = None
        if cache is not None:
            at = jnp.where(lens > 0, cache.rows, cache.state.shape[0] - 1)
            nc = StateCache(cache.state.at[at].set(state),
                            cache.tail.at[at].set(tail), cache.rows,
                            lens.astype(jnp.int32))
        return jnp.concatenate(out, axis=1), nc

    def _chunks(self, s: int, lens):
        """``ssd_prefill_chunks``: chunks the scan visits against the
        chunks the prompts' true lengths need (one layer's)."""
        c = self.config
        seg = min(s, c.prefill_segment)
        chunk = min(c.mamba_chunk_size, -(-seg // 8) * 8)
        scanned = sum(-(-min(seg, s - lo) // chunk)
                      for lo in range(0, s, seg)) * lens.shape[0]
        need = jnp.sum(-(-lens // chunk))
        return {"ssd_prefill_chunks": {
            "scanned": jnp.asarray(scanned, jnp.int32),
            "needed": need.astype(jnp.int32)}}

    # -- the MLP ----------------------------------------------------------------

    def _mlp(self, blk, x):
        """``x + MLP(RMSNorm_ff(x))``, ``prefill_segment`` positions at a
        time (a long prompt's gate and up would stand whole)."""
        from ..distributed.moe import gated_ffn
        c = self.config
        m_gate, m_down = c.mlp_multipliers
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        out = []
        for lo in range(0, x.shape[0], c.prefill_segment):
            xs = x[lo:lo + c.prefill_segment]
            if out:
                xs, _ = jax.lax.optimization_barrier((xs, out[-1]))
            u = rms_norm32(xs, _raw(blk.ln_ff), c.rms_norm_eps).astype(
                x.dtype)
            with jax.named_scope("pt.ffn.dense"):
                m = gated_ffn(u, _raw(blk.w_gate), _raw(blk.w_up),
                              _raw(blk.w_down), "silu", gate_scale=m_gate)
            out.append((xs.astype(F32) + m.astype(F32) * m_down)
                       .astype(x.dtype))
        x = out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)
        return x.reshape(shape)

    # -- the forward ----------------------------------------------------------

    def decode_hidden(self, input_ids, caches, prefill_lens=None,
                      prefill_chained=False):
        """Cached forward to the final hidden states, the head's
        multiplier in them: ``(hidden [B, S, D], new_caches)``.
        ``prefill_lens``: a right-padded prompt into FRESH slots. Without
        it, one token a sequence. ``caches=None`` with ``prefill_lens``:
        the same forward, nothing stored. ``caches``: two a block (keys
        and values, then the state)."""
        c = self.config
        ids = _raw(input_ids)
        b, s = ids.shape
        if prefill_chained:
            raise NotImplementedError(
                "a prefill that continues a stored sequence (prefix "
                "hits, chunks) over a state")
        if prefill_lens is None and s != 1:
            raise NotImplementedError(
                "several tokens a sequence without prefill_lens")
        if prefill_lens is None:
            pos = caches[0].seq_lens[:, None]
            live = pos[:, 0] > 0
        else:
            prefill_lens = prefill_lens.astype(jnp.int32)
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                   (b, s))
        emb = _raw(self.model.embed)
        dt = emb.dtype
        x = (emb[ids].astype(F32) * c.embedding_multiplier).astype(dt)
        new_caches = []
        for i, blk in enumerate(self.model.layers):
            kv, st = (None, None) if caches is None else \
                (caches[2 * i], caches[2 * i + 1])
            h = rms_norm32(x, _raw(blk.ln_in), c.rms_norm_eps).astype(dt)
            with jax.named_scope("pt.attn.global"):
                att, kv = self._attention(blk, h, kv, pos, prefill_lens)
            with jax.named_scope("pt.attn.ssd"):
                if prefill_lens is None:
                    ssm, st = self._ssm_token(blk, h, st, live)
                else:
                    ssm, st = self._ssm_prompt(blk, h, st, prefill_lens)
            new_caches += [kv, st]
            x = self._mlp(blk, (x.astype(F32) + att + ssm).astype(dt))
            if prefill_lens is not None:
                # a long prompt's layers one at a time
                x = jax.lax.optimization_barrier(x)
        # the head's multiplier in the final norm's weight: the logits
        # are this hidden state times the head alone
        x = rms_norm32(x, _raw(self.model.norm) * c.lm_head_multiplier,
                       c.rms_norm_eps).astype(dt)
        self._stats = None if prefill_lens is None else \
            self._chunks(s, prefill_lens)
        return x, new_caches
