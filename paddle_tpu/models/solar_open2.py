"""Solar Open 2: gated delta-rule (KDA) layers with a state a sequence,
one gated softmax layer without positions in four, routed experts with
a shared one.

The layer equations (upstage/Solar-Open2-250B ``config.json``,
``model_type: solar_open2``; the KDA layer is Kimi Linear's,
arXiv:2510.26692; layer ``l``, input ``x`` [T, hidden]):

- ``h = RMSNorm_1(x)``; ``y = x + Mix_l(h)``; ``u = RMSNorm_2(y)``;
  ``x' = y + MoE(u)``; a final RMSNorm and an untied head.
- ``l`` in ``gqa_layers`` (every fourth): softmax attention with grouped
  heads, no positions (``use_rope`` false), no window: ``q, k, v = h
  W_q, h W_k, h W_v``, causal softmax of ``q k^T / sqrt(d)``, ``Mix =
  (Attn * sigmoid(h W_gate)) W_o`` (``use_gqa_gate``).
- the other layers: KDA. ``q, k, v = silu(conv(h W_qkv))``, ``conv`` a
  causal depthwise convolution of ``short_conv_kernel_size`` taps
  (``out[t] = sum_i w[i] in[t - taps + 1 + i]``, zeros before the
  sequence); a head's q and k L2-normalised, q scaled by ``d_k^-1/2``;
  log-decay a key channel ``g = -exp(A_h) softplus(W_f_up (W_f_down h)
  + b_dt)``; ``beta = 2 sigmoid(h W_beta)`` (``kda_allow_neg_eigval``);
  the state ``S [d_k, d_v]`` float32 a head moves by ``S = (I - beta k
  k^T) Diag(exp g) S + beta k v^T`` and ``o = S^T q``
  (ops/pallas/kda.py); ``Mix = (RMSNorm_head(o) * sigmoid(W_g_up (W_g_down
  h))) W_o``.
- ``MoE(u)``: ``s = sigmoid(u W_r)`` over all experts, the
  ``num_experts_per_tok`` largest ``s + b`` picked, gates the picked
  ``s`` normalised to 1 times ``routed_scaling_factor``
  (distributed/moe.py ``route_sigmoid_top_k``); ``FFN_e(u) = (silu(u
  W_gate^e) * (u W_up^e)) W_down^e`` over the picks (``dropless_experts``)
  plus one shared expert of the same width, always on.

Serving surface: the one the engine calls on any decoder. A KDA layer's
memory is a :class:`~.cache_layout.StateCache`: the state and the
convolution's last inputs, a row a slot, updated in place at a
single-token step and written from zero by a prompt. A long prompt runs
through a KDA layer ``prefill_segment`` positions at a time, the state
handed on, so that its projections never stand in memory whole. Plain
``jax.numpy`` on the parameters' values: serving only, no autograd
tape.
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
from .smallthinker import (ServedDecoderLM, _raw, chunk_attention, kv_append,
                           rms_norm32)

F32 = jnp.float32


@dataclasses.dataclass
class SolarOpen2Config:
    """The published keys under their published names, then what a
    deployment adds."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 10240  # a dense layer's: none is one here
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    use_rope: bool = False
    gqa_interval: int = 3
    gqa_layers: Tuple[int, ...] = ()
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    linear_attn_config: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False
    # a deployment's cut: the expert ids held here as (first, count),
    # None for all; the rows of the vocabulary held, None for all
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[int] = None
    # positions of a prompt that go through a KDA layer at a time
    prefill_segment: int = 8192
    dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.gqa_layers:
            self.gqa_layers = tuple(range(0, n, self.gqa_interval + 1))
        self.gqa_layers = tuple(int(i) for i in self.gqa_layers if i < n)
        lin = dict(self.linear_attn_config or {})
        lin.setdefault("short_conv_kernel_size", 4)
        lin.setdefault("head_dim", self.head_dim)
        lin.setdefault("num_heads", self.num_attention_heads)
        self.linear_attn_config = lin
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        unsupported = [
            ("use_rope", self.use_rope), ("a dense layer",
                                          self.first_k_dense_replace),
            ("kda_use_full_proj", self.kda_use_full_proj),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("gates not normalised over the picks",
             not self.norm_topk_prob),
            ("other than one shared expert", self.n_shared_experts != 1),
            ("an ungated softmax layer", not self.use_gqa_gate),
            ("beta in (0, 1)", not self.kda_allow_neg_eigval)]
        for what, asked in unsupported:
            if asked:
                raise NotImplementedError(what)
        if self.experts_held is not None:
            first, count = self.experts_held
            if first < 0 or count < 1 or \
                    first + count > self.n_routed_experts:
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
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held[1])

    @property
    def vocab_rows(self) -> int:
        return self.vocab_held or self.vocab_size

    @property
    def kda_heads(self) -> int:
        return int(self.linear_attn_config["num_heads"])

    @property
    def kda_dim(self) -> int:
        return int(self.linear_attn_config["head_dim"])

    @property
    def conv_taps(self) -> int:
        return int(self.linear_attn_config["short_conv_kernel_size"])


def solar_open2_tiny(**kw):
    """One period at toy widths, for the CPU tests: 16 experts (8
    shares of 2) top-4, a group of 2 query heads a KV head, 4 KDA heads
    of 16."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                max_position_embeddings=256, moe_intermediate_size=32,
                n_routed_experts=16, num_experts_per_tok=4,
                linear_attn_config={"short_conv_kernel_size": 4,
                                    "head_dim": 16, "num_heads": 4},
                prefill_segment=16)
    base.update(kw)
    return SolarOpen2Config(**base)


def solar_open2_250b(num_layers: int = 48, **kw):
    """The published configuration; ``num_layers`` cuts the depth to
    whole periods of (softmax, KDA, KDA, KDA)."""
    return SolarOpen2Config(num_hidden_layers=num_layers, **kw)


# -- the leaves' own initial values --------------------------------------------

def init_a_log(key, shape):
    """``A = log U(1, 16)``: the family's."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))


def init_dt_bias(key, shape):
    """The inverse softplus of ``U(1e-3, 0.1)``: the family's."""
    dt = jax.random.uniform(key, shape, F32, 1e-3, 0.1)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_conv(key, shape):
    """``U(-1/sqrt(taps), 1/sqrt(taps))``: a depthwise convolution's
    usual start."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, F32, -bound, bound)


def init_router_bias(key, shape):
    return jax.random.normal(key, shape, F32) * 0.01


class SolarOpen2Block(Layer):
    def __init__(self, cfg: SolarOpen2Config, make, softmax: bool):
        super().__init__()
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        e = cfg.num_experts_held
        res = cfg.initializer_range / math.sqrt(2.0 * cfg.num_hidden_layers)
        std = cfg.initializer_range
        self.ln1 = make((h,), "norm")
        if softmax:
            hq, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
            self.wq = make((h, hq * d), std)
            self.wk = make((h, kv * d), std)
            self.wv = make((h, kv * d), std)
            self.w_ogate = make((h, hq * d), std)
            self.wo = make((hq * d, h), res)
        else:
            n, d = cfg.kda_heads, cfg.kda_dim
            self.wqkv = make((h, 3 * n * d), std)
            self.conv = make((cfg.conv_taps, 3 * n * d), init_conv)
            self.wf_down = make((h, d), std)
            self.wf_up = make((d, n * d), std)
            self.a_log = make((n,), init_a_log, dtype="float32")
            self.dt_bias = make((n * d,), init_dt_bias, dtype="float32")
            self.w_beta = make((h, n), std)
            self.wg_down = make((h, d), std)
            self.wg_up = make((d, n * d), std)
            self.o_norm = make((d,), "norm")
            self.wo = make((n * d, h), res)
        self.ln2 = make((h,), "norm")
        self.router = make((h, cfg.n_routed_experts), std, dtype="float32")
        self.router_bias = make((cfg.n_routed_experts,), init_router_bias,
                                dtype="float32")
        self.w_gate = make((e, h, f), std)
        self.w_up = make((e, h, f), std)
        self.w_down = make((e, f, h), res)
        self.ws_gate = make((h, f), std)
        self.ws_up = make((h, f), std)
        self.ws_down = make((f, h), res)


class SolarOpen2Model(Layer):
    def __init__(self, cfg: SolarOpen2Config, make):
        super().__init__()
        self.embed = make((cfg.vocab_rows, cfg.hidden_size),
                          cfg.initializer_range)
        self.layers = LayerList([
            SolarOpen2Block(cfg, make, i in cfg.gqa_layers)
            for i in range(cfg.num_hidden_layers)])
        self.norm = make((cfg.hidden_size,), "norm")


def causal_conv(x, tail, w):
    """``out[t] = sum_i w[i] in[t - taps + 1 + i]`` a channel, ``in``
    the sequence ``x`` [B, T, C] behind the ``tail`` [B, taps - 1, C] of
    what came before it (zeros at a sequence's start). Returns ``(out
    [B, T, C] float32, tail + x)``."""
    t = x.shape[1]
    seen = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(F32)
    out = sum(seen[:, i:i + t].astype(F32) * wf[i]
              for i in range(w.shape[0]))
    return out, seen


def sigmoid_moe(blk, y, valid, *, eps, top_k, scaling, segment, held=None):
    """``y + MoE(RMSNorm_2(y))`` of a layer whose router scores every
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
                valid=valid[lo:lo + segment], activation="silu")
        with jax.named_scope("pt.moe.shared"):
            m = m + gated_ffn(u, _raw(blk.ws_gate), _raw(blk.ws_up),
                              _raw(blk.ws_down), "silu")
        out.append(ys + m)
        cnt = cnt + n
    y = out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)
    return y.reshape(shape), cnt


class SolarOpen2ForCausalLM(ServedDecoderLM):
    body = SolarOpen2Model

    # -- what the engine asks -------------------------------------------------

    def cache_layout(self):
        c = self.config
        dt = jnp.dtype(c.dtype)
        n, d = c.kda_heads, c.kda_dim
        return [LayerCache(c.num_key_value_heads, c.head_dim, None, dt,
                           heads_major=True) if i in c.gqa_layers
                else LayerCache(n, d, None, dt, state=(n, d, d),
                                conv=(c.conv_taps - 1, 3 * n * d))
                for i in range(c.num_hidden_layers)]

    # -- the two mixers -------------------------------------------------------

    def _softmax_mix(self, blk, h, cache, prefill_lens):
        from ..ops.pallas.paged_attention import paged_attention_grouped
        c = self.config
        b, s, _ = h.shape
        hq, kvh, d = (c.num_attention_heads, c.num_key_value_heads,
                      c.head_dim)
        scale = 1.0 / math.sqrt(d)
        q = jnp.matmul(h, _raw(blk.wq)).reshape(b, s, hq, d)
        k = jnp.matmul(h, _raw(blk.wk)).reshape(b, s, kvh, d)
        v = jnp.matmul(h, _raw(blk.wv)).reshape(b, s, kvh, d)
        gate = jnp.matmul(h, _raw(blk.w_ogate))
        with jax.named_scope("pt.attn.global"):
            nc = None if cache is None else kv_append(
                cache, k, v, valid_len=prefill_lens)
            if prefill_lens is not None:
                att = chunk_attention(q, k, v, None, scale,
                                      valid_len=prefill_lens)
            else:
                att = paged_attention_grouped(
                    q, nc.k_pages, nc.v_pages, nc.page_table, nc.seq_lens,
                    scale=scale)
        att = (att.reshape(b, s, hq * d).astype(F32)
               * jax.nn.sigmoid(gate.astype(F32))).astype(h.dtype)
        return jnp.matmul(att, _raw(blk.wo)), nc

    def _kda_inputs(self, blk, h, tail):
        """From the normed input ``h`` [B, T, hidden] and the
        convolution's tail: ``(q, k, v [B, T, H * d], g [B, T, H * d]
        f32, beta [B, T, H] f32, what the convolution has seen)``."""
        c = self.config
        n, d = c.kda_heads, c.kda_dim
        conv, seen = causal_conv(jnp.matmul(h, _raw(blk.wqkv)), tail,
                                 _raw(blk.conv))
        q, k, v = jnp.split(jax.nn.silu(conv).astype(h.dtype), 3, axis=-1)
        low = jnp.matmul(h, _raw(blk.wf_down), preferred_element_type=F32)
        dt = jnp.matmul(low, _raw(blk.wf_up).astype(F32),
                        precision="highest") + _raw(blk.dt_bias)
        g = -jnp.repeat(jnp.exp(_raw(blk.a_log)), d) * jax.nn.softplus(dt)
        beta = 2.0 * jax.nn.sigmoid(jnp.matmul(
            h, _raw(blk.w_beta), preferred_element_type=F32))
        return q, k, v, g, beta, seen

    def _kda_output(self, blk, h, o):
        """``(RMSNorm_head(o) * sigmoid(W_g_up (W_g_down h))) W_o``."""
        c = self.config
        n, d = c.kda_heads, c.kda_dim
        lead = o.shape[:-1]
        o = rms_norm32(o.reshape(lead + (n, d)), _raw(blk.o_norm),
                       c.rms_norm_eps).reshape(lead + (n * d,))
        low = jnp.matmul(h, _raw(blk.wg_down))
        gate = jax.nn.sigmoid(jnp.matmul(low, _raw(blk.wg_up),
                                         preferred_element_type=F32))
        return jnp.matmul((o * gate).astype(h.dtype), _raw(blk.wo))

    def _kda_token(self, blk, h, cache: StateCache, live):
        """One token a sequence: the slot's state moved in place, a
        parked slot's row left alone."""
        from ..ops.pallas.kda import kda_decode
        c = self.config
        n, d = c.kda_heads, c.kda_dim
        b = h.shape[0]
        rows = cache.rows
        q, k, v, g, beta, seen = self._kda_inputs(blk, h, cache.tail[rows])
        with jax.named_scope("pt.attn.kda"):
            o, state = kda_decode(
                q.reshape(b, n, d), k.reshape(b, n, d), v.reshape(b, n, d),
                g.reshape(b, n, d), beta.reshape(b, n), cache.state, rows,
                live)
        at = jnp.where(live, rows, cache.tail.shape[0] - 1)
        tail = cache.tail.at[at].set(seen[:, 1:])
        nc = StateCache(state, tail, rows, cache.seq_lens + 1)
        return self._kda_output(blk, h, o.reshape(b, 1, n * d)), nc

    def _kda_prompt(self, blk, h, cache: Optional[StateCache], lens):
        """Right-padded prompts into fresh slots: the chunked scan a
        segment at a time, the final state and the convolution's tail
        left in each slot's row."""
        from ..ops.pallas.kda import kda_chunk_fwd
        c = self.config
        n, d, taps = c.kda_heads, c.kda_dim, c.conv_taps
        b, s, _ = h.shape
        state = jnp.zeros((b, n, d, d), F32)
        tail = jnp.zeros((b, taps - 1, 3 * n * d), h.dtype)
        out = []
        for lo in range(0, s, c.prefill_segment):
            hs = h[:, lo:lo + c.prefill_segment]
            if lo:
                # one segment's projections at a time: the next one's
                # wait for this one's state
                hs, state = jax.lax.optimization_barrier((hs, state))
            left = jnp.clip(lens - lo, 0, hs.shape[1])
            q, k, v, g, beta, seen = self._kda_inputs(blk, hs, tail)
            with jax.named_scope("pt.attn.kda"):
                o, state = kda_chunk_fwd(q, k, v, g, beta, state, left,
                                         heads=n)
            # the inputs at the segment's last taps - 1 true positions
            # (the tail as it came in, where the segment holds none)
            tail = jax.vmap(lambda x, at: jax.lax.dynamic_slice_in_dim(
                x, at, taps - 1, 0))(seen, left)
            out.append(self._kda_output(blk, hs, o))
        nc = None
        if cache is not None:
            at = jnp.where(lens > 0, cache.rows, cache.state.shape[0] - 1)
            nc = StateCache(cache.state.at[at].set(state),
                            cache.tail.at[at].set(tail), cache.rows,
                            lens.astype(jnp.int32))
        return jnp.concatenate(out, axis=1), nc

    # -- the expert layer -----------------------------------------------------

    def _moe(self, blk, y, valid):
        c = self.config
        return sigmoid_moe(blk, y, valid, eps=c.rms_norm_eps,
                           top_k=c.num_experts_per_tok,
                           scaling=c.routed_scaling_factor,
                           segment=c.prefill_segment, held=c.experts_held)

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
                "a prefill that continues a stored sequence (prefix "
                "hits, chunks) over a state layer")
        if prefill_lens is None and s != 1:
            raise NotImplementedError(
                "several tokens a sequence without prefill_lens")
        if prefill_lens is None:
            live = caches[0].seq_lens > 0
            valid = live[:, None]
        else:
            prefill_lens = prefill_lens.astype(jnp.int32)
            valid = jnp.arange(s, dtype=jnp.int32)[None] < \
                prefill_lens[:, None]
        x = _raw(self.model.embed)[ids]
        dt = x.dtype
        new_caches, counts = [], []
        for i, blk in enumerate(self.model.layers):
            cache = None if caches is None else caches[i]
            h = rms_norm32(x, _raw(blk.ln1), c.rms_norm_eps).astype(dt)
            if i in c.gqa_layers:
                mix, nc = self._softmax_mix(blk, h, cache, prefill_lens)
            elif prefill_lens is None:
                mix, nc = self._kda_token(blk, h, cache, live)
            else:
                mix, nc = self._kda_prompt(blk, h, cache, prefill_lens)
            new_caches.append(nc)
            x, cnt = self._moe(blk, x + mix, valid)
            counts.append(cnt)
            if prefill_lens is not None:
                # a long prompt's layers one at a time
                x = jax.lax.optimization_barrier(x)
        x = rms_norm32(x, _raw(self.model.norm), c.rms_norm_eps).astype(dt)
        self._keep_stats(counts, prefill_lens is not None)
        return x, new_caches
