"""The plain reference of the GLM-4 MoE Lite configurations: the layer
equations of zai-org/GLM-4.7-Flash's ``config.json`` (``model_type:
glm4_moe_lite``; DeepSeek-V2/V3's multi-head latent attention and
router) in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision; no kernels, no cache, no batching, nothing
imported from the program.

Layer ``l``, input ``x`` [T, hidden], ``H`` heads: ``h = RMSNorm_1(x)``;
``y = x + MLA(h)``; ``u = RMSNorm_2(y)``; ``x' = y + FFN_l(u)``; a final
RMSNorm and an untied head.

- ``MLA(h)``, THE EXPANDED FORM ONLY (this file never absorbs ``W_kvb``
  into the query or the output: the program's decode path is checked
  against the definition): ``c_q = RMSNorm_q(h W_qa)``, ``q = c_q W_qb``,
  a head's ``q_i = [q_i^nope | q_i^rope]``; ``[c_raw | k_raw^rope] = h
  W_kva``, ``c = RMSNorm_kv(c_raw)``; ``[k_i^nope | v_i] = c W_kvb`` a
  head; RoPE (rotate-half, ``rope_theta``, every one of the
  ``qk_rope_head_dim`` dims) on ``q_i^rope`` and on the ONE ``k^rope``
  all heads share; ``k_i = [k_i^nope | k^rope]``; causal softmax of
  ``q_i . k_i / sqrt(nope + rope)``; ``MLA = concat_i(sum p v_i) W_o``.
  ``W_kvb`` ``[rank, H * (nope + v)]`` is put together from the
  weights' two leaves ``w_uk`` [H, rank, nope] and ``w_uv`` [H, rank,
  v], its column blocks by head.
- ``FFN_l``, ``l < first_k_dense_replace``: ``(silu(u W_gate) * (u
  W_up)) W_down``; else ``s = sigmoid(u W_r)`` over all experts, the
  ``k`` largest ``s + b`` picked, gates ``s_e / sum of the picked s``
  times ``routed_scaling_factor``; a plain loop over the experts, each
  on the tokens that picked it, plus the shared expert on every token.

What the config does not give is the configuration's ``assumed`` list.
It runs layer by layer, attention a block of queries at a time, an
expert at a time, the head over the sampled positions alone, so that
34 k positions fit beside the weights.

Routing is discontinuous: where the last pick's selection score and the
first left-out expert's lie closer than ``delta`` in any layer,
rounding the activations to bf16 may pick the other expert
legitimately. ``served_token_gaps`` returns the smallest such margin of
every sampled position (``margins``).

``lowp`` puts the nearest lower precision in the matmuls (operands
rounded to float8_e4m3 with a per-tensor scale; norms, router and
softmax stay in float32 as the configuration states): the control,
never the reference. ``fault`` plants a fault a serving path can have,
for the readings of the limits: ``rope_k_off`` (the shared key left
un-rotated), ``kv_norm_off`` (the latent without its norm),
``scale_192`` (the softmax scaled by ``nope^-1/2``), ``scaling_one``
(gates without ``routed_scaling_factor``), ``shared_off`` (no shared
expert), ``top3`` (one pick fewer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("rope_k_off", "kv_norm_off", "scale_192", "scaling_one",
          "shared_off", "top3")
Q_BLOCK = 128  # queries a block of attention
ROWS = 256     # sampled positions a block of the head


def _q8(x):
    """Round to float8_e4m3 (per-tensor scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, b, lowp):
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision="highest")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, pos, theta):
    """Rotate-half over the last axis: x [..., S, d] at positions
    ``pos`` [S]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv[None]  # [S, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def sizes_of(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
            "top_k": cfg["num_experts_per_tok"],
            "experts": cfg["n_routed_experts"],
            "scaling": float(cfg["routed_scaling_factor"]),
            "dense": cfg["first_k_dense_replace"],
            "layers": cfg["num_hidden_layers"]}


# -- the mixer -----------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rope", "v", "theta", "eps", "lowp", "fault"))
def mla_layer(p, x, *, heads, rank, nope, rope, v, theta, eps, lowp, fault):
    """``y = x + MLA(RMSNorm_1(x))`` for the whole sequence ``x`` [S,
    hidden] (S a multiple of the block), the expanded form, a block of
    queries at a time."""
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, p["ln1"], eps)
    kv = _mm(h, p["wkv_a"], lowp)
    c = kv[:, :rank]
    if fault != "kv_norm_off":
        c = _rms(c, p["kv_norm"], eps)
    k_rope = kv[:, rank:]
    if fault != "rope_k_off":
        k_rope = _rope(k_rope, pos, theta)
    w_kvb = jnp.concatenate([p["w_uk"], p["w_uv"]], -1).swapaxes(0, 1) \
        .reshape(rank, heads * (nope + v))
    exp = _mm(c, w_kvb, lowp).reshape(s, heads, nope + v)
    k = jnp.concatenate([
        exp[..., :nope],
        jnp.broadcast_to(k_rope[:, None], (s, heads, rope))], -1)
    val = exp[..., nope:]
    scale = (nope if fault == "scale_192" else nope + rope) ** -0.5
    blk = min(Q_BLOCK, s)

    def rows(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, blk, 0)
        at = start + jnp.arange(blk)
        cq = _rms(_mm(hb, p["wq_a"], lowp), p["q_norm"], eps)
        q = _mm(cq, p["wq_b"], lowp).reshape(blk, heads, nope + rope)
        q = jnp.concatenate([
            q[..., :nope],
            _rope(q[..., nope:].swapaxes(0, 1), at, theta).swapaxes(0, 1)],
            -1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * scale
        seen = pos[None] <= at[:, None]
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khv->qhv", pr, val, precision="highest")
        return _mm(o.reshape(blk, heads * v), p["wo"], lowp)

    mix = jax.lax.map(rows, jnp.arange(0, s, blk)).reshape(s, -1)
    return x + mix


# -- the feed-forward layers ---------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def dense_ffn(p, y, eps, lowp):
    u = _rms(y, p["ln2"], eps)
    mid = jax.nn.silu(_mm(u, p["wd_gate"], lowp)) * _mm(u, p["wd_up"], lowp)
    return y + _mm(mid, p["wd_down"], lowp)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scaling"))
def route(y, ln2, w_router, bias, eps, top_k, scaling):
    """``(u, picks, gates, margin)``: the router in float32 whatever the
    matmuls' precision; ``margin``: the last pick's selection score
    minus the first left-out expert's."""
    u = _rms(y, ln2, eps)
    s = jax.nn.sigmoid(jnp.matmul(u, w_router, precision="highest"))
    sel, idx = jax.lax.top_k(s + bias, top_k + 1)
    picked = jnp.take_along_axis(s, idx[:, :top_k], axis=-1)
    gates = picked / jnp.sum(picked, -1, keepdims=True) * scaling
    return u, idx[:, :top_k], gates, sel[:, top_k - 1] - sel[:, top_k]


@functools.partial(jax.jit, static_argnames=("lowp",))
def expert(u_rows, gate, wg, wu, wd, lowp):
    """One expert on the rows that picked it, times their gates."""
    mid = jax.nn.silu(_mm(u_rows, wg, lowp)) * _mm(u_rows, wu, lowp)
    return _mm(mid, wd, lowp) * gate[:, None]


def experts(p, y, u, idx, gates, n_experts, lowp, shared: bool):
    """``y + the experts' parts + the shared expert``: a loop over the
    experts, each on the tokens that picked it (their count padded to a
    power of two, so that a few shapes serve every expert)."""
    idx, gates = np.asarray(idx), np.asarray(gates)
    out = y
    if shared:
        out = out + expert(u, jnp.ones((u.shape[0],), F32),
                           p["ws_gate"], p["ws_up"], p["ws_down"], lowp)
    for j in range(n_experts):
        rows, pick = np.nonzero(idx == j)
        if rows.size == 0:
            continue
        n = max(8, 1 << int(rows.size - 1).bit_length())
        at = np.zeros((n,), np.int32)
        at[:rows.size] = rows
        g = np.zeros((n,), np.float32)  # padding rows: gate 0
        g[:rows.size] = gates[rows, pick]
        part = expert(u[at], jnp.asarray(g), p["w_gate"][j].astype(F32),
                      p["w_up"][j].astype(F32), p["w_down"][j].astype(F32),
                      lowp)
        out = out.at[at].add(part)
    return out


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _layer(params, i):
    """Layer ``i``'s leaves by short name, in float32 but the routed
    experts', which are cast one expert at a time."""
    lead = f"model.layers.{i}."
    return {k[len(lead):]: (v if k[len(lead):] in EXPERT_LEAVES
                            else v.astype(F32))
            for k, v in params.items() if k.startswith(lead)}


def hidden_states(cfg: dict, params: dict, ids, lowp: bool = False,
                  fault: str | None = None) -> tuple:
    """The final hidden states ``[S, H]`` (before the last norm) of one
    sequence of token ids, and for every position the smallest margin,
    over the expert layers, between its last pick's selection score and
    the first expert's left out."""
    sz = sizes_of(cfg)
    n = len(ids)
    blk = min(Q_BLOCK, n)
    ids = np.pad(np.asarray(ids), (0, -(-n // blk) * blk - n))
    x = params["model.embed"][ids].astype(F32)
    margin = None
    top_k = sz["top_k"] - (1 if fault == "top3" else 0)
    scaling = 1.0 if fault == "scaling_one" else sz["scaling"]
    for i in range(sz["layers"]):
        p = _layer(params, i)
        y = mla_layer(p, x, heads=sz["heads"], rank=sz["rank"],
                      nope=sz["nope"], rope=sz["rope"], v=sz["v"],
                      theta=sz["theta"], eps=sz["eps"], lowp=lowp,
                      fault=fault)
        if i < sz["dense"]:
            x = dense_ffn(p, y, sz["eps"], lowp)
            continue
        u, idx, gates, m = route(y, p["ln2"], p["router"], p["router_bias"],
                                 sz["eps"], top_k, scaling)
        margin = m if margin is None else jnp.minimum(margin, m)
        x = experts(p, y, u, idx, gates, sz["experts"], lowp,
                    shared=fault != "shared_off")
    if margin is None:
        margin = jnp.full((x.shape[0],), jnp.inf, F32)
    return x[:n], margin[:n]


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def head_logits(x, norm_w, head_w, eps, lowp=False):
    """Logits of the rows ``x`` [N, H]: the last norm, the untied head
    ``[V, H]``."""
    return _mm(_rms(x, norm_w, eps), head_w.T, lowp)


def forward_logits(cfg: dict, params: dict, ids, fault=None):
    """Logits ``[S, V]`` of one whole sequence (the tests' size)."""
    x, _ = hidden_states(cfg, params, ids, fault=fault)
    return head_logits(x, params["model.norm"].astype(F32),
                       params["lm_head"].astype(F32), cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _gap_rows(x, tok, norm_w, head_w, eps):
    """The reference's best logit minus its logit of ``tok``, a row."""
    ref = head_logits(x, norm_w, head_w, eps)
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _low_tokens(x_low, norm_w, head_w, eps):
    """The token the lower precision puts first, a row."""
    return jnp.argmax(head_logits(x_low, norm_w, head_w, eps, True), -1)


def padded_size(n: int) -> int:
    """A power of two from 256 up to 4096, whole blocks of 4096
    beyond: a dozen shapes in all, whatever the lengths."""
    if n <= 4096:
        return max(256, 1 << (n - 1).bit_length())
    return -(-n // 4096) * 4096


def served_token_gaps(cfg: dict, params: dict, sequences: list,
                      prompt_lens: list, control: bool = False,
                      fault: str | None = None) -> dict:
    """For each sequence (prompt + the tokens that were served), at
    every position that produced a served token: the gap of the served
    token below the reference's best logit (``gaps``), the smallest
    router margin of that position (``margins``) and, with ``control``,
    the gap of the token the lower precision puts first
    (``control_gaps``)."""
    eps = cfg["rms_norm_eps"]
    # the head's weights go in as arguments: closed over, they would be
    # compiled into each program as a constant
    norm_w = params["model.norm"].astype(F32)
    head_w = params["lm_head"].astype(F32)

    def gap_rows(x, tok):
        return _gap_rows(x, tok, norm_w, head_w, eps)

    def low_tokens(x_low):
        return _low_tokens(x_low, norm_w, head_w, eps)

    def by_rows(fn, *arrays):
        """``fn`` over blocks of ROWS positions (a block of logits at a
        time)."""
        n = arrays[0].shape[0]
        pad = -(-n // ROWS) * ROWS - n
        arrays = [jnp.pad(jnp.asarray(a), ((0, pad),) + ((0, 0),) *
                          (np.ndim(a) - 1)) for a in arrays]
        return np.concatenate([
            np.asarray(fn(*(a[i:i + ROWS] for a in arrays)))
            for i in range(0, n + pad, ROWS)])[:n]

    gaps, margins, lows = [], [], []
    for seq, plen in zip(sequences, prompt_lens):
        n = len(seq)
        ids = np.zeros((padded_size(n),), np.int32)
        ids[:n] = seq
        x, margin = hidden_states(cfg, params, ids, fault=fault)
        at = slice(plen - 1, n - 1)
        served = np.asarray(seq[plen:], np.int32)
        gaps.append(by_rows(gap_rows, x[at], served))
        margins.append(np.asarray(margin)[at])
        if control:
            x_low, _ = hidden_states(cfg, params, ids, lowp=True)
            low_tok = by_rows(low_tokens, x_low[at])
            lows.append(by_rows(gap_rows, x[at], low_tok))
    return {"gaps": gaps, "margins": margins, "control_gaps": lows}
