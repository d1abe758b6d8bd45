"""GLM-5.3-Flash: a residual of four streams mixed by Sinkhorn-normalised
hyper-connections, KDA state layers three in four, learned sparse
attention over a latent cache in the fourth, routed experts with a
shared one and a clamped SwiGLU.

The layer equations (zai-org/GLM-5.3-Flash ``config.json``,
``model_type: glm5_next_text``; ``X`` [T, n, hidden] the residual of
``n = hc_mult`` streams; readings the config does not pin are marked
assumed):

- **mHC** (DeepSeek-AI, arXiv:2512.24880) around each of a layer's two
  sublayers ``F`` (the mixer, then the feed-forward; ``F`` includes its
  own pre-norm): ``xbar = RMSNorm(vec X)`` (n * hidden wide, a learned
  weight, ``rms_norm_eps``: assumed); ``[p | q | r] = xbar Phi`` (n, n
  and n * n columns); ``H_pre = sigmoid(a_pre p + b_pre)``; ``H_post =
  2 sigmoid(a_post q + b_post)``; ``H_res = Sinkhorn(exp(a_res mat(r) +
  b_res))``, rows then columns normalised ``hc_sinkhorn_iters`` times
  with ``hc_eps`` in each denominator; ``X <- H_res X + H_post^T F(H_pre
  X)``. The embedding enters as n copies; the logits are
  ``RMSNorm_final(sum_i X_i) W_head`` (the streams summed, as
  Hyper-Connections do: assumed). ``x Phi``, the H and Sinkhorn are
  float32.
- **KDA** (``layer_types`` ``linear_attention``): Solar Open 2's layer
  (models/solar_open2.py) with the decay bounded below by
  ``gate_lower_bound``: ``g = lb sigmoid(exp(A_h) (W_f_up W_f_down h +
  b_dt))`` (flash-linear-attention's KDA gate with a lower bound:
  assumed), and ``beta = sigmoid(h W_beta)``.
- **DSA** (``deepseek_sparse_attention``; DeepSeek-V3.2's sparse
  attention, no positions: ``qk_rope_head_dim`` 0): ``c_q =
  RMSNorm_q(h W_qa)``, ``q = c_q W_qb`` (heads of ``qk_nope_head_dim``);
  ``c = RMSNorm_kv(h W_kva)``, the row a position leaves in the cache;
  ``k_i = c W_UK,i``, ``v_i = c W_UV,i``, scale ``qk_head_dim^-1/2``.
  The indexer: ``q^I = c_q W^I_q`` (``index_n_heads`` of
  ``index_head_dim``), ``k^I = LayerNorm(h W^I_k)`` (weight and bias),
  ``w = h W^I_w index_n_heads^-1/2 index_head_dim^-1/2``; a block's
  pooled key is the mean of the ``index_kpool`` keys in it (assumed);
  a query at ``t`` scores the complete blocks before its own, ``I_b =
  sum_j w_j relu(q^I_j . kbar_b)``, keeps those scored at least its
  ``index_topk``-th best (``index_topk`` counts blocks: assumed) and
  always its own block's positions up to ``t``
  (``index_kpool_always_select_tail``); the softmax runs over the
  selected positions alone. The decoded token's form is the absorbed
  one (ops/pallas/sparse_attention.py).
- **feed-forward**: ``mlp_layer_types`` ``dense``: ``(act(u W_gate) * (u
  W_up)) W_down`` of ``intermediate_size``; ``sparse``: a sigmoid over
  all experts in float32, the ``num_experts_per_tok`` largest ``s + b``
  picked, gates ``routed_scaling_factor s_e / sum picked s``, plus one
  shared expert. Every SwiGLU is ``silu(min(a, L)) * clip(b, -L, L)``
  (``swiglu_limit`` L: gpt-oss's clamp without its alpha and +1,
  assumed).

Serving surface: the one the engine calls on any decoder. A KDA layer's
memory is a :class:`~.cache_layout.StateCache`; a DSA layer gives the
engine TWO latent entries under the one page table: the latent rows
(``rank`` 512, no rotary part) and the indexer's pooled keys, one row
a block of ``index_kpool`` positions (``LayerCache.pooled``). A block's
row is the sum of ``k^I / pool`` over its positions so far: a prompt
writes whole blocks, and a decoded token adds its share to its block's
row (the first position of a block sets it). ``decode_hidden`` reads
``caches`` in the layout's order. A long prompt runs ``prefill_segment``
positions at a time through every layer, the four streams carried
segment by segment. The multi-token-prediction module and the vision
tower take no part in the next-token logits and are not built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.container import LayerList
from ..nn.layer import Layer
from .cache_layout import LatentCache, LayerCache, StateCache
from .served import (ServedDecoderLM, _raw, causal_conv, init_router_bias,
                     latent_append, rms_norm32, sigmoid_moe)
from .solar_open2 import init_a_log, init_conv, init_dt_bias

F32 = jnp.float32
KDA, DSA = "linear_attention", "deepseek_sparse_attention"


@dataclasses.dataclass
class Glm5NextConfig:
    """The published keys under their published names, then what a
    deployment adds."""
    vocab_size: int = 154880
    hidden_size: int = 4096
    num_hidden_layers: int = 45
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 256
    qk_rope_head_dim: int = 0
    qk_head_dim: int = 256
    v_head_dim: int = 256
    mla_use_nope: bool = True
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_kpool: int = 4
    index_kpool_compress: bool = True
    index_kpool_always_select_tail: bool = True
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc: bool = True
    linear_attn_config: Optional[dict] = None
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 288
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    first_k_dense_replace: int = 3
    swiglu_limit: float = 10.0
    hidden_act: str = "silu"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 1  # the MTP module: not built
    # a deployment's cut: the published layers held, in order (None:
    # the first num_hidden_layers); the expert ids held as (first,
    # count); a slice of the vocabulary is a smaller vocab_size
    layer_ids: Optional[Tuple[int, ...]] = None
    experts_held: Optional[Tuple[int, int]] = None
    # positions of a prompt that go through a layer at a time
    prefill_segment: int = 8192
    dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.layer_ids is None:
            self.layer_ids = tuple(range(self.num_hidden_layers))
        self.layer_ids = tuple(int(i) for i in self.layer_ids)
        if len(self.layer_ids) != self.num_hidden_layers:
            raise ValueError("layer_ids gives one published id a layer held")
        top = max(self.layer_ids) + 1
        if not self.layer_types:
            self.layer_types = tuple(DSA if i % 4 == 3 else KDA
                                     for i in range(top))
        if not self.mlp_layer_types:
            self.mlp_layer_types = tuple(
                "dense" if i < self.first_k_dense_replace else "sparse"
                for i in range(top))
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        lin = dict(self.linear_attn_config or {})
        lin.setdefault("num_heads", 64)
        lin.setdefault("head_dim", 128)
        lin.setdefault("short_conv_kernel_size", 4)
        lin.setdefault("gate_lower_bound", -5)
        self.linear_attn_config = lin
        unsupported = [
            ("rotary parts", self.qk_rope_head_dim != 0
             or self.qk_head_dim != self.qk_nope_head_dim),
            ("attention_bias", self.attention_bias),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("expert groups", self.n_group != 1 or self.topk_group != 1),
            ("gates not normalised over the picks", not self.norm_topk_prob),
            ("other than one shared expert", self.n_shared_experts != 1),
            ("an activation other than silu", self.hidden_act != "silu"),
            ("a router other than sigmoid noaux_tc",
             self.scoring_func != "sigmoid"
             or self.topk_method != "noaux_tc"),
            ("grouped latent heads",
             self.num_key_value_heads != self.num_attention_heads),
            ("a residual of one stream", not self.mhc),
            ("keys pooled other than by block", not self.index_kpool_compress),
            ("no tail block", not self.index_kpool_always_select_tail)]
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
    def vocab_rows(self) -> int:
        return self.vocab_size

    @property
    def num_experts_held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held[1])

    @property
    def kda_heads(self) -> int:
        return int(self.linear_attn_config["num_heads"])

    @property
    def kda_dim(self) -> int:
        return int(self.linear_attn_config["head_dim"])

    @property
    def conv_taps(self) -> int:
        return int(self.linear_attn_config["short_conv_kernel_size"])

    @property
    def activation(self) -> str:
        """The clamped SwiGLU (ops/pallas/grouped_matmul.py ``glu``)."""
        return f"silu_limit{float(self.swiglu_limit):g}"

    def kind(self, j: int) -> Tuple[str, str]:
        """``(mixer, feed-forward)`` of the ``j``-th layer held."""
        i = self.layer_ids[j]
        return self.layer_types[i], self.mlp_layer_types[i]


def glm5_next_tiny(**kw):
    """The cell's five layers (ids 0, 3, 4, 5, 6: a dense KDA layer,
    then DSA, KDA, KDA, KDA) at toy widths, for the CPU tests: 4 heads
    of 16 over a latent of 32, an indexer of 4 heads of 16 keeping 4
    blocks of 4 (selection is real past 20 positions), 16 experts
    top-4, four streams."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                layer_ids=(0, 3, 4, 5, 6), num_attention_heads=4,
                num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_head_dim=16, v_head_dim=16,
                index_n_heads=4,
                index_head_dim=16, index_topk=4, intermediate_size=96,
                moe_intermediate_size=32, n_routed_experts=16,
                num_experts_per_tok=4, max_position_embeddings=256,
                linear_attn_config={"num_heads": 4, "head_dim": 16,
                                    "short_conv_kernel_size": 4,
                                    "gate_lower_bound": -5},
                prefill_segment=16, initializer_range=0.125)
    base.update(kw)
    return Glm5NextConfig(**base)


def glm5_3_flash(layer_ids=(0, 3, 4, 5, 6), **kw):
    """The published configuration holding the layers ``layer_ids``."""
    return Glm5NextConfig(num_hidden_layers=len(layer_ids),
                          layer_ids=tuple(layer_ids), **kw)


# -- the leaves' own initial values -------------------------------------------

def init_hc_alpha(key, shape):
    """The input-dependent parts' scales a_pre, a_post, a_res: 0.1."""
    return jnp.full(shape, 0.1, F32)


def init_hc_bias(key, shape):
    """b_pre, b_post, b_res: N(0, 1), every H far from its trivial
    value."""
    return jax.random.normal(key, shape, F32)


class Glm5NextBlock(Layer):
    def __init__(self, cfg: Glm5NextConfig, make, mixer: str, mlp: str):
        super().__init__()
        h, n = cfg.hidden_size, cfg.hc_mult
        res = cfg.initializer_range / math.sqrt(2.0 * cfg.num_hidden_layers)
        std = cfg.initializer_range

        for s in ("a", "f"):
            setattr(self, f"hc_norm_{s}", make((n * h,), "norm"))
            setattr(self, f"hc_phi_{s}", make((n * h, n * (n + 2)), std,
                                              dtype="float32"))
            setattr(self, f"hc_alpha_{s}", make((3,), init_hc_alpha,
                                                dtype="float32"))
            setattr(self, f"hc_bias_{s}", make((n * (n + 2),), init_hc_bias,
                                               dtype="float32"))
        self.ln1 = make((h,), "norm")
        if mixer == KDA:
            k, d = cfg.kda_heads, cfg.kda_dim
            self.wqkv = make((h, 3 * k * d), std)
            self.conv = make((cfg.conv_taps, 3 * k * d), init_conv)
            self.wf_down = make((h, d), std)
            self.wf_up = make((d, k * d), std)
            self.a_log = make((k,), init_a_log, dtype="float32")
            self.dt_bias = make((k * d,), init_dt_bias, dtype="float32")
            self.w_beta = make((h, k), std)
            self.wg_down = make((h, d), std)
            self.wg_up = make((d, k * d), std)
            self.o_norm = make((d,), "norm")
            self.wo = make((k * d, h), res)
        else:
            heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
            nope, v = cfg.qk_nope_head_dim, cfg.v_head_dim
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            self.wq_a = make((h, cfg.q_lora_rank), std)
            self.q_norm = make((cfg.q_lora_rank,), "norm")
            self.wq_b = make((cfg.q_lora_rank, heads * nope), std)
            self.wkv_a = make((h, rank), std)
            self.kv_norm = make((rank,), "norm")
            self.w_uk = make((heads, rank, nope), std)
            self.w_uv = make((heads, rank, v), std)
            self.wo = make((heads * v, h), res)
            self.wi_q = make((cfg.q_lora_rank, hi * di), std)
            self.wi_k = make((h, di), std)
            self.ki_norm = make((di,), "norm")
            self.ki_bias = make((di,), 0.0, dtype="float32")
            self.wi_w = make((h, hi), std)
        self.ln2 = make((h,), "norm")
        if mlp == "dense":
            f = cfg.intermediate_size
            self.wd_gate = make((h, f), std)
            self.wd_up = make((h, f), std)
            self.wd_down = make((f, h), res)
            return
        e, f = cfg.num_experts_held, cfg.moe_intermediate_size
        self.router = make((h, cfg.n_routed_experts), std, dtype="float32")
        self.router_bias = make((cfg.n_routed_experts,), init_router_bias,
                                dtype="float32")
        self.w_gate = make((e, h, f), std)
        self.w_up = make((e, h, f), std)
        self.w_down = make((e, f, h), res)
        self.ws_gate = make((h, f), std)
        self.ws_up = make((h, f), std)
        self.ws_down = make((f, h), res)


class Glm5NextModel(Layer):
    def __init__(self, cfg: Glm5NextConfig, make):
        super().__init__()
        self.embed = make((cfg.vocab_rows, cfg.hidden_size),
                          cfg.initializer_range)
        self.layers = LayerList([
            Glm5NextBlock(cfg, make, *cfg.kind(j))
            for j in range(cfg.num_hidden_layers)])
        self.norm = make((cfg.hidden_size,), "norm")


# -- the residual of several streams ------------------------------------------

def hc_maps(blk, s: str, xs, eps: float, hc_eps: float, iters: int):
    """The mappings of sublayer ``s`` (``a``: the mixer, ``f``: the
    feed-forward) at the streams ``xs`` [B, S, n, D]: ``(H_pre X [B, S,
    D] in xs' dtype, H_post [B, S, n], H_res [B, S, n, n])``, float32.
    ``RMSNorm(vec X) Phi`` is ``r X (w Phi)`` with ``r`` the position's
    inverse root mean square, the norm's weight folded into ``Phi``. In
    bfloat16 streams ``X`` is exact, and ``w Phi`` goes in as two
    bfloat16 parts (its rounding and the rest): a float32 product with
    no float32 copy of the streams."""
    b, t, n, d = xs.shape
    flat = xs.reshape(b, t, n * d)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(flat.astype(F32)), axis=-1,
                               keepdims=True) + eps)
    phi = _raw(getattr(blk, f"hc_phi_{s}")) * \
        _raw(getattr(blk, f"hc_norm_{s}"))[:, None]
    if xs.dtype == F32:
        z = jnp.matmul(flat, phi, precision="highest")
    else:
        hi = phi.astype(xs.dtype)
        lo = (phi - hi.astype(F32)).astype(xs.dtype)
        z = jnp.matmul(flat, hi, preferred_element_type=F32) + \
            jnp.matmul(flat, lo, preferred_element_type=F32)
    z = z * r
    a = _raw(getattr(blk, f"hc_alpha_{s}"))
    bias = _raw(getattr(blk, f"hc_bias_{s}"))
    z = jnp.concatenate([a[0] * z[..., :n], a[1] * z[..., n:2 * n],
                         a[2] * z[..., 2 * n:]], axis=-1) + bias
    pre = jax.nn.sigmoid(z[..., :n])
    post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    m = jnp.exp(z[..., 2 * n:].reshape(b, t, n, n))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + hc_eps)
    h = sum(pre[..., j, None] * xs[:, :, j].astype(F32) for j in range(n))
    return h.astype(xs.dtype), post, m


def hc_merge(xs, post, res, out):
    """``H_res X + H_post^T out``: xs [B, S, n, D], out [B, S, D]; an
    elementwise sum over the n streams (no n x n matmul a position)."""
    n = xs.shape[2]
    x32 = xs.astype(F32)
    new = sum(res[..., j, None] * x32[:, :, None, j] for j in range(n))
    return (new + post[..., None] * out.astype(F32)[:, :, None]).astype(
        xs.dtype)


def layer_norm32(x, w, bias, eps: float = 1e-5):
    x = x.astype(F32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) + bias


class Glm5NextForCausalLM(ServedDecoderLM):
    body = Glm5NextModel

    # -- what the engine asks -------------------------------------------------

    def cache_layout(self):
        c = self.config
        dt = jnp.dtype(c.dtype)
        k, d = c.kda_heads, c.kda_dim
        out = []
        for j in range(c.num_hidden_layers):
            if c.kind(j)[0] == KDA:
                out.append(LayerCache(k, d, None, dt, state=(k, d, d),
                                      conv=(c.conv_taps - 1, 3 * k * d)))
                continue
            out.append(LayerCache(c.num_attention_heads, c.qk_head_dim, None,
                                  dt, latent=(c.kv_lora_rank, 0)))
            out.append(LayerCache(c.index_n_heads, c.index_head_dim, None, dt,
                                  latent=(c.index_head_dim, 0),
                                  pooled=c.index_kpool))
        return out

    # -- KDA ------------------------------------------------------------------

    def _kda_inputs(self, blk, h, tail):
        """``(q, k, v, g, beta, what the convolution has seen)`` of the
        normed input ``h`` [B, T, hidden]."""
        c = self.config
        d = c.kda_dim
        conv, seen = causal_conv(jnp.matmul(h, _raw(blk.wqkv)), tail,
                                 _raw(blk.conv))
        q, k, v = jnp.split(jax.nn.silu(conv).astype(h.dtype), 3, axis=-1)
        low = jnp.matmul(h, _raw(blk.wf_down), preferred_element_type=F32)
        dt = jnp.matmul(low, _raw(blk.wf_up).astype(F32),
                        precision="highest") + _raw(blk.dt_bias)
        lower = float(c.linear_attn_config["gate_lower_bound"])
        g = lower * jax.nn.sigmoid(
            jnp.repeat(jnp.exp(_raw(blk.a_log)), d) * dt)
        beta = jax.nn.sigmoid(jnp.matmul(h, _raw(blk.w_beta),
                                         preferred_element_type=F32))
        return q, k, v, g, beta, seen

    def _kda_output(self, blk, h, o):
        c = self.config
        k, d = c.kda_heads, c.kda_dim
        lead = o.shape[:-1]
        o = rms_norm32(o.reshape(lead + (k, d)), _raw(blk.o_norm),
                       c.rms_norm_eps).reshape(lead + (k * d,))
        gate = jax.nn.sigmoid(jnp.matmul(
            jnp.matmul(h, _raw(blk.wg_down)), _raw(blk.wg_up),
            preferred_element_type=F32))
        return jnp.matmul((o * gate).astype(h.dtype), _raw(blk.wo))

    def _kda_token(self, blk, h, cache: StateCache, live):
        from ..ops.pallas.kda import kda_decode
        c = self.config
        k, d = c.kda_heads, c.kda_dim
        b = h.shape[0]
        rows = cache.rows
        q, kk, v, g, beta, seen = self._kda_inputs(blk, h, cache.tail[rows])
        with jax.named_scope("pt.attn.kda"):
            o, state = kda_decode(
                q.reshape(b, k, d), kk.reshape(b, k, d), v.reshape(b, k, d),
                g.reshape(b, k, d), beta.reshape(b, k), cache.state, rows,
                live)
        at = jnp.where(live, rows, cache.tail.shape[0] - 1)
        nc = StateCache(state, cache.tail.at[at].set(seen[:, 1:]), rows,
                        cache.seq_lens + 1)
        return self._kda_output(blk, h, o.reshape(b, 1, k * d)), nc

    def _kda_segment(self, blk, h, state, tail, left):
        """One segment of right-padded prompts from ``state`` and the
        convolution's ``tail``: ``(out, state, tail)``."""
        from ..ops.pallas.kda import kda_chunk_fwd
        c = self.config
        taps = c.conv_taps
        q, k, v, g, beta, seen = self._kda_inputs(blk, h, tail)
        with jax.named_scope("pt.attn.kda"):
            o, state = kda_chunk_fwd(q, k, v, g, beta, state, left,
                                     heads=c.kda_heads)
        tail = jax.vmap(lambda x, at: jax.lax.dynamic_slice_in_dim(
            x, at, taps - 1, 0))(seen, left)
        return self._kda_output(blk, h, o), state, tail

    # -- DSA ------------------------------------------------------------------

    def _dsa_keys(self, blk, h):
        """``(latent rows [B, T, rank], k^I [B, T, Di])`` of the normed
        input: what a position leaves in the two pools."""
        c = self.config
        lat = rms_norm32(jnp.matmul(h, _raw(blk.wkv_a)), _raw(blk.kv_norm),
                         c.rms_norm_eps).astype(h.dtype)
        ki = layer_norm32(jnp.matmul(h, _raw(blk.wi_k)), _raw(blk.ki_norm),
                          _raw(blk.ki_bias)).astype(h.dtype)
        return lat, ki

    def _dsa_queries(self, blk, h):
        """``(q [B, T, H, nope], q^I [B, T, Hi, Di], w [B, T, Hi] f32)``
        of the normed input."""
        c = self.config
        b, t, _ = h.shape
        hi, di = c.index_n_heads, c.index_head_dim
        cq = rms_norm32(jnp.matmul(h, _raw(blk.wq_a)), _raw(blk.q_norm),
                        c.rms_norm_eps).astype(h.dtype)
        q = jnp.matmul(cq, _raw(blk.wq_b)).reshape(
            b, t, c.num_attention_heads, c.qk_nope_head_dim)
        qi = jnp.matmul(cq, _raw(blk.wi_q)).reshape(b, t, hi, di)
        w = jnp.matmul(h, _raw(blk.wi_w), preferred_element_type=F32) * \
            (hi * di) ** -0.5
        return q, qi, w

    def _dsa_out(self, blk, oa, h):
        """``concat_i(o~_i W_UV,i) W_o`` of the absorbed outputs ``oa``
        [B, T, H, rank]."""
        b, t = oa.shape[:2]
        o = jnp.einsum("bthr,hrv->bthv", oa, _raw(blk.w_uv))
        return jnp.matmul(o.reshape(b, t, -1).astype(h.dtype), _raw(blk.wo))

    def _dsa_token(self, blk, h, lat: LatentCache, idx: LatentCache, live):
        """One token a sequence: its row appended, its share of its
        block's pooled key added, attention over what its indexer
        selects."""
        from ..ops.pallas.paged_attention import paged_attention_latent
        from ..ops.pallas.sparse_attention import (index_scores_paged,
                                                   kth_largest, select_blocks)
        c = self.config
        pool, rank = c.index_kpool, c.kv_lora_rank
        rows, ki = self._dsa_keys(blk, h)
        q, qi, w = self._dsa_queries(blk, h)
        pos = lat.seq_lens  # the token's position
        nl = latent_append(lat, rows)
        n_pool, per_page, di = idx.pages.shape
        mp = idx.page_table.shape[1]
        blk_id = pos // pool
        page = jnp.take_along_axis(
            idx.page_table, jnp.minimum(blk_id // per_page, mp - 1)[:, None],
            axis=1)[:, 0]
        keep = live & (blk_id < mp * per_page)
        at = jnp.where(keep, page * per_page + blk_id % per_page,
                       (n_pool - 1) * per_page)
        flat = idx.pages.reshape(n_pool * per_page, di)
        old = jnp.where((pos % pool == 0)[:, None], 0.0,
                        flat[at].astype(F32))
        share = ki[:, 0].astype(F32) / pool
        new = old + jnp.pad(share, ((0, 0), (0, di - share.shape[-1])))
        ni = LatentCache(flat.at[at].set(new.astype(flat.dtype)).reshape(
            idx.pages.shape), idx.page_table, nl.seq_lens)
        with jax.named_scope("pt.attn.index"):
            scores = index_scores_paged(qi[:, 0], w[:, 0], ni.pages,
                                        idx.page_table, pos, pool)
            tau = kth_largest(scores, c.index_topk)
            picked = select_blocks(scores, tau, pos, pool)
        sel = jnp.repeat(picked, pool, axis=1)
        with jax.named_scope("pt.attn.sparse"):
            qa = jnp.einsum("bhn,hrn->bhr", q[:, 0], _raw(blk.w_uk))
            lens = jnp.where(live, nl.seq_lens, 0)
            oa = paged_attention_latent(qa, nl.pages, nl.page_table, lens,
                                        rank, c.qk_head_dim ** -0.5, sel=sel)
        seen = sel & (jnp.arange(sel.shape[1], dtype=jnp.int32)[None]
                      <= pos[:, None])
        counts = (jnp.sum(jnp.where(live[:, None], seen, False)),
                  jnp.sum(jnp.where(live, pos + 1, 0)))
        return self._dsa_out(blk, oa[:, None], h), nl, ni, counts

    def _dsa_prompt(self, blk, hs, lat, idx, lens):
        """Right-padded prompts into fresh slots, ``hs`` the segments of
        the normed input: the rows and pooled keys of every position
        first, then each segment's queries over them."""
        from ..ops.pallas.sparse_attention import (index_scores, kth_largest,
                                                   prefill_tile_pairs,
                                                   select_blocks,
                                                   sparse_prefill_latent)
        c = self.config
        pool = c.index_kpool
        keys = [self._dsa_keys(blk, h) for h in hs]
        rows = jnp.concatenate([k[0] for k in keys], axis=1)
        ki = jnp.concatenate([k[1] for k in keys], axis=1)
        b, t, di = ki.shape
        valid = jnp.arange(t, dtype=jnp.int32)[None] < lens[:, None]
        ki = jnp.where(valid[..., None], ki.astype(F32), 0.0)
        ki = jnp.pad(ki, ((0, 0), (0, -t % pool), (0, 0)))
        kbar = (ki.reshape(b, -1, pool, di).sum(2) / pool).astype(rows.dtype)
        nl = ni = None
        if lat is not None:
            nl = latent_append(lat, rows, valid_len=lens)
            ni = latent_append(idx, kbar, valid_len=-(-lens // pool))
            ni = LatentCache(ni.pages, ni.page_table, nl.seq_lens)
        out, selected, causal, run, grid, lo = [], 0, 0, 0, 0, 0
        for h in hs:
            q, qi, w = self._dsa_queries(blk, h)
            s = q.shape[1]
            qpos = lo + jnp.arange(s, dtype=jnp.int32)
            with jax.named_scope("pt.attn.index"):
                scores = index_scores(qi, w, kbar, lo, pool)
                tau = kth_largest(scores, c.index_topk)
            with jax.named_scope("pt.attn.sparse"):
                qa = jnp.einsum("bshn,hrn->bhsr", q, _raw(blk.w_uk))
                oa = sparse_prefill_latent(qa, rows, scores, tau, lo, pool,
                                           c.qk_head_dim ** -0.5,
                                           lengths=lens)
            out.append(self._dsa_out(blk, oa.swapaxes(1, 2), h))
            picked = select_blocks(scores, tau, jnp.broadcast_to(qpos, (b, s)),
                                   pool)
            own = qpos // pool
            before = picked & (jnp.arange(scores.shape[-1])[None, None]
                               < own[None, :, None])
            live = qpos[None] < lens[:, None]
            selected = selected + jnp.sum(jnp.where(
                live, pool * jnp.sum(before, -1) + qpos % pool + 1, 0))
            causal = causal + jnp.sum(jnp.where(live, qpos + 1, 0))
            tiles = prefill_tile_pairs(lens, lo, s)
            run, grid = run + tiles[0], grid + tiles[1]
            lo += s
        return out, nl, ni, (selected, causal, run, grid)

    # -- the feed-forward -----------------------------------------------------

    def _ffn(self, blk, mlp, h, valid):
        """``F(h)`` of the feed-forward sublayer, its norm included, and
        the picks per held expert (None: a dense layer)."""
        c = self.config
        if mlp == "dense":
            from ..distributed.moe import gated_ffn
            u = rms_norm32(h, _raw(blk.ln2), c.rms_norm_eps).astype(h.dtype)
            with jax.named_scope("pt.ffn.dense"):
                return gated_ffn(u, _raw(blk.wd_gate), _raw(blk.wd_up),
                                 _raw(blk.wd_down), c.activation), None
        return sigmoid_moe(blk, h, valid, eps=c.rms_norm_eps,
                           top_k=c.num_experts_per_tok,
                           scaling=c.routed_scaling_factor,
                           segment=h.shape[0] * h.shape[1],
                           held=c.experts_held, activation=c.activation,
                           residual=False)

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
                "a prefill that continues a stored sequence (prefix hits, "
                "chunks): the state layers' snapshots and the sparse "
                "absorbed form over stored pages")
        if prefill_lens is None and s != 1:
            raise NotImplementedError(
                "several tokens a sequence without prefill_lens")
        prompt = prefill_lens is not None
        if prompt:
            prefill_lens = prefill_lens.astype(jnp.int32)
            valid = jnp.arange(s, dtype=jnp.int32)[None] < \
                prefill_lens[:, None]
        else:
            live = caches[0].seq_lens > 0
            valid = live[:, None]
        seg = c.prefill_segment if prompt else 1
        x = _raw(self.model.embed)[ids]
        dt = x.dtype
        n = c.hc_mult
        streams = jnp.broadcast_to(x[:, :, None], (b, s, n, x.shape[-1]))
        segs = [streams[:, lo:lo + seg] for lo in range(0, s, seg)]
        vsegs = [valid[:, lo:lo + seg] for lo in range(0, s, seg)]
        maps = dict(eps=c.rms_norm_eps, hc_eps=c.hc_eps,
                    iters=c.hc_sinkhorn_iters)
        new_caches, counts, dsa = [], [], []
        at = 0
        for j, blk in enumerate(self.model.layers):
            mixer, mlp = c.kind(j)
            with jax.named_scope("pt.residual.hc"):
                got = [hc_maps(blk, "a", xs, **maps) for xs in segs]
            hs = [rms_norm32(g[0], _raw(blk.ln1), c.rms_norm_eps).astype(dt)
                  for g in got]
            if mixer == KDA:
                cache = None if caches is None else caches[at]
                at += 1
                if not prompt:
                    mix, nc = self._kda_token(blk, hs[0], cache, live)
                    outs = [mix]
                else:
                    k, d = c.kda_heads, c.kda_dim
                    state = jnp.zeros((b, k, d, d), F32)
                    tail = jnp.zeros((b, c.conv_taps - 1, 3 * k * d), dt)
                    outs = []
                    for i, h in enumerate(hs):
                        if i:
                            h, state = jax.lax.optimization_barrier(
                                (h, state))
                        left = jnp.clip(prefill_lens - i * seg, 0,
                                        h.shape[1])
                        o, state, tail = self._kda_segment(blk, h, state,
                                                           tail, left)
                        outs.append(o)
                    nc = None
                    if cache is not None:
                        rows = jnp.where(prefill_lens > 0, cache.rows,
                                         cache.state.shape[0] - 1)
                        nc = StateCache(cache.state.at[rows].set(state),
                                        cache.tail.at[rows].set(tail),
                                        cache.rows, prefill_lens)
                new_caches.append(nc)
            else:
                lat = idx = None
                if caches is not None:
                    lat, idx = caches[at], caches[at + 1]
                at += 2
                if not prompt:
                    mix, nl, ni, cnt = self._dsa_token(blk, hs[0], lat, idx,
                                                       live)
                    outs = [mix]
                else:
                    outs, nl, ni, cnt = self._dsa_prompt(blk, hs, lat, idx,
                                                         prefill_lens)
                new_caches += [nl, ni]
                dsa.append(cnt)
            with jax.named_scope("pt.residual.hc"):
                segs = [hc_merge(xs, g[1], g[2], o)
                        for xs, g, o in zip(segs, got, outs)]
            total = 0
            for i in range(len(segs)):
                xs = segs[i]
                if i:
                    xs, _ = jax.lax.optimization_barrier((xs, segs[i - 1]))
                with jax.named_scope("pt.residual.hc"):
                    h, post, res = hc_maps(blk, "f", xs, **maps)
                o, cnt = self._ffn(blk, mlp, h, vsegs[i])
                if cnt is not None:
                    total = total + cnt
                with jax.named_scope("pt.residual.hc"):
                    segs[i] = hc_merge(xs, post, res, o)
            if mlp != "dense":
                counts.append(total)
            if prompt:
                # a long prompt's layers one at a time
                segs = list(jax.lax.optimization_barrier(tuple(segs)))
        # the streams summed a segment at a time
        x = [jnp.sum(xs.astype(F32), axis=2).astype(dt) for xs in segs]
        x = jnp.concatenate(x, axis=1) if len(x) > 1 else x[0]
        x = rms_norm32(x, _raw(self.model.norm), c.rms_norm_eps).astype(dt)
        if counts:
            self._keep_stats(counts, prompt)
        if dsa:
            sums = [sum(v).astype(jnp.int32) for v in zip(*dsa)]
            stats = {"dsa_positions": dict(zip(("selected", "causal"),
                                               sums[:2]))}
            if prompt:
                # the prompt kernel's (query tile, key tile) pairs: run
                # at the true lengths, and what the bucket's whole
                # causal square would run
                stats["dsa_prefill_tiles"] = dict(zip(("run", "grid"),
                                                      sums[2:]))
            self._stats = dict(self._stats or {}, **stats)
        return x, new_caches
