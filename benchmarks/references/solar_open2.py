"""The plain reference of the Solar Open 2 configurations: the layer
equations of upstage/Solar-Open2-250B's ``config.json`` (the KDA layer
is Kimi Linear's, arXiv:2510.26692) in straightforward ``jax.numpy``,
float32, matmuls at ``highest`` precision; no kernels, no cache, no
batching, nothing imported from the program.

Layer ``l``, input ``x`` [T, hidden]: ``h = RMSNorm_1(x)``; ``y = x +
Mix_l(h)``; ``u = RMSNorm_2(y)``; ``x' = y + MoE(u)``; a final RMSNorm
and an untied head.

- ``l`` in ``gqa_layers``: ``q, k, v = h W_q, h W_k, h W_v`` (query
  heads ``G j .. G j + G - 1`` read KV head ``j``), causal softmax of
  ``q k^T / sqrt(d)``, no positions, no window; ``Mix = (Attn *
  sigmoid(h W_gate)) W_o``.
- else KDA: ``q, k, v = silu(conv(h W_qkv))`` with ``conv[t, c] = sum_i
  w[i, c] in[t - taps + 1 + i, c]`` (zeros before the sequence); a
  head's q and k L2-normalised (``x / sqrt(sum x^2 + 1e-6)``), q times
  ``d_k^-1/2``; ``g_t = -exp(A_h) softplus(W_f_up (W_f_down h_t) +
  b_dt)``; ``beta_t = 2 sigmoid(h_t W_beta)``; the state ``S [d_k,
  d_v]`` a head, zero at the start, TOKEN BY TOKEN under ``lax.scan``:
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t``; ``Mix = (RMSNorm_head(o) * sigmoid(W_g_up
  (W_g_down h))) W_o``.
- ``MoE(u)``: ``s = sigmoid(u W_r)`` over all experts, the ``k``
  largest ``s + b`` picked, gates ``s_e / sum of the picked s`` times
  ``routed_scaling_factor``; ``FFN_e(u) = (silu(u W_gate^e) * (u
  W_up^e)) W_down^e``: a plain loop over the experts held, each on the
  tokens that picked it, plus the shared expert on every token.

What the config does not give is the configuration's ``assumed`` list.
It runs layer by layer, a KDA layer ``ROWS_KDA`` positions at a time
(the state and the convolution's last inputs handed on), attention a
block of queries at a time, an expert at a time, the head over the
sampled positions alone, so that 34 k positions fit beside the weights.
Given ``experts_held`` it computes the same share of every layer that a
chip holding those experts computes.

Routing is discontinuous: where the 8th and the 9th selection score of
a position lie closer than ``delta`` in any layer, rounding the
activations to bf16 may pick the other expert legitimately.
``served_token_gaps`` returns the smallest such margin of every sampled
position (``margins``); the driver leaves the positions under ``delta``
out of the mean gap and reports their share.

``lowp`` puts the nearest lower precision in the matmuls (operands
rounded to float8_e4m3 with a per-tensor scale; router, decay, beta and
state stay in float32 as the configuration states): the control, never
the reference. ``fault`` plants a fault a serving path can have, for
the readings of the limits: ``no_decay`` (alpha = 1), ``beta_one``
(beta without the factor 2), ``conv_tap`` (the convolution one position
late: it never sees the current token), ``gate_off`` (the softmax
layer's gate left out), ``shared_off`` (no shared expert), ``top7``
(one pick fewer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("no_decay", "beta_one", "conv_tap", "gate_off", "shared_off",
          "top7")
Q_BLOCK = 128    # queries a block of attention
ROWS_KDA = 2048  # positions a block of a KDA layer
ROWS = 256       # sampled positions a block of the head


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


def sizes_of(cfg: dict) -> dict:
    """The published keys. ``n_routed_experts`` counts the experts held
    where the file is a cut; ``published`` then has the router's width
    and ``experts_held`` says which they are."""
    held = cfg.get("experts_held")
    n_all = cfg["n_routed_experts"] if held is None \
        else cfg["published"]["n_routed_experts"]
    held = (0, n_all) if held is None else (int(held[0]), int(held[1]))
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    return {"heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "lin_heads": lin["num_heads"], "lin_d": lin["head_dim"],
            "taps": lin["short_conv_kernel_size"],
            "eps": cfg["rms_norm_eps"],
            "top_k": cfg["num_experts_per_tok"], "experts": n_all,
            "held": held, "layers": n,
            "scaling": float(cfg["routed_scaling_factor"]),
            "softmax": [i for i in cfg["gqa_layers"] if i < n]}


# -- the softmax layer ---------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "heads", "kv", "d", "eps", "lowp", "gated"))
def softmax_layer(p, x, *, heads, kv, d, eps, lowp, gated):
    """``y = x + (Attn * sigmoid(h W_gate)) W_o`` for the whole sequence
    ``x`` [S, hidden], a block of queries at a time."""
    s = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    k = _mm(h, p["wk"], lowp).reshape(s, kv, d)
    v = _mm(h, p["wv"], lowp).reshape(s, kv, d)
    g = heads // kv
    blk = min(Q_BLOCK, s)
    kpos = jnp.arange(s)[None]

    def rows(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, blk, 0)
        qb = _mm(hb, p["wq"], lowp).reshape(blk, kv, g, d)
        sc = jnp.einsum("qjgd,kjd->jgqk", qb, k,
                        precision="highest") / (d ** 0.5)
        seen = kpos <= (start + jnp.arange(blk))[:, None]
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("jgqk,kjd->qjgd", pr, v,
                       precision="highest").reshape(blk, heads * d)
        if gated:
            a = a * jax.nn.sigmoid(_mm(hb, p["w_ogate"], lowp))
        return _mm(a, p["wo"], lowp)

    mix = jax.lax.map(rows, jnp.arange(0, s, blk)).reshape(s, -1)
    return x + mix


# -- the KDA layer -------------------------------------------------------------

def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "taps", "eps", "lowp", "fault"))
def kda_rows(p, x, seen, state, *, n, d, taps, eps, lowp, fault):
    """A block of consecutive positions ``x`` [R, hidden] of one
    sequence through a KDA layer: ``(y, seen', state')``. ``seen``
    [taps, 3 n d]: the convolution's inputs at the ``taps`` positions
    before the block (zeros before the sequence; the equations need
    ``taps - 1`` of them, the planted fault the one before);
    ``state`` [n, d, d]: every head's state before the block."""
    r = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    seen = jnp.concatenate([seen, _mm(h, p["wqkv"], lowp)], axis=0)
    late = 0 if fault == "conv_tap" else 1
    conv = sum(seen[late + i:late + i + r] * p["conv"][i]
               for i in range(taps))
    q, k, v = jnp.split(jax.nn.silu(conv), 3, axis=-1)
    q = _l2norm(q.reshape(r, n, d)) * (d ** -0.5)
    k = _l2norm(k.reshape(r, n, d))
    v = v.reshape(r, n, d)
    # decay and beta in float32 whatever the matmuls' precision
    dt = jnp.matmul(jnp.matmul(h, p["wf_down"], precision="highest"),
                    p["wf_up"], precision="highest") + p["dt_bias"]
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(dt).reshape(r, n, d)
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    beta = (1.0 if fault == "beta_one" else 2.0) * jax.nn.sigmoid(
        jnp.matmul(h, p["w_beta"], precision="highest"))  # [R, n]

    def token(s, xs):
        qt, kt, vt, gt, bt = xs  # [n, d], beta [n]
        s = s * jnp.exp(gt)[:, :, None]
        u = bt[:, None] * (vt - jnp.einsum("nk,nkv->nv", kt, s,
                                           precision="highest"))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("nk,nkv->nv", qt, s, precision="highest")

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    o = _rms(o, p["o_norm"], eps).reshape(r, n * d)
    gate = jax.nn.sigmoid(_mm(_mm(h, p["wg_down"], lowp), p["wg_up"], lowp))
    return x + _mm(o * gate, p["wo"], lowp), seen[r:], state


def kda_layer(p, x, sz, lowp, fault):
    n, d, taps = sz["lin_heads"], sz["lin_d"], sz["taps"]
    seen = jnp.zeros((taps, 3 * n * d), F32)
    state = jnp.zeros((n, d, d), F32)
    out = []
    for lo in range(0, x.shape[0], ROWS_KDA):
        y, seen, state = kda_rows(
            p, x[lo:lo + ROWS_KDA], seen, state, n=n, d=d, taps=taps,
            eps=sz["eps"], lowp=lowp, fault=fault)
        out.append(y)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)


# -- the expert layer ----------------------------------------------------------

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


def experts(p, y, u, idx, gates, sz, lowp, shared: bool):
    """``y + the held experts' parts + the shared expert``: a loop over
    the experts held, each on the tokens that picked it (their count
    padded to a power of two, so that a few shapes serve every
    expert)."""
    idx, gates = np.asarray(idx), np.asarray(gates)
    first, count = sz["held"]
    out = y
    if shared:
        out = out + expert(u, jnp.ones((u.shape[0],), F32),
                           p["ws_gate"], p["ws_up"], p["ws_down"], lowp)
    for j in range(count):
        rows, pick = np.nonzero(idx == first + j)
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
    over the layers, between its last pick's selection score and the
    first expert's left out."""
    sz = sizes_of(cfg)
    x = params["model.embed"][np.asarray(ids)].astype(F32)
    margin = None
    top_k = sz["top_k"] - (1 if fault == "top7" else 0)
    for i in range(sz["layers"]):
        p = _layer(params, i)
        if i in sz["softmax"]:
            y = softmax_layer(p, x, heads=sz["heads"], kv=sz["kv"],
                              d=sz["d"], eps=sz["eps"], lowp=lowp,
                              gated=fault != "gate_off")
        else:
            y = kda_layer(p, x, sz, lowp, fault)
        u, idx, gates, m = route(y, p["ln2"], p["router"], p["router_bias"],
                                 sz["eps"], top_k, sz["scaling"])
        margin = m if margin is None else jnp.minimum(margin, m)
        x = experts(p, y, u, idx, gates, sz, lowp,
                    shared=fault != "shared_off")
    return x, margin


def moe_layer(cfg: dict, params: dict, i: int, y):
    """Layer ``i``'s ``MoE(RMSNorm_2(y))`` alone, the residual left
    out: what the shares of a deployment add up to (the tests')."""
    sz = sizes_of(cfg)
    p = _layer(params, i)
    u, idx, gates, _ = route(y, p["ln2"], p["router"], p["router_bias"],
                             sz["eps"], sz["top_k"], sz["scaling"])
    return experts(p, jnp.zeros_like(y), u, idx, gates, sz, False, True)


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
    """A power of two from 256 up to two KDA blocks, whole pairs of
    blocks beyond: a dozen shapes in all, whatever the lengths."""
    if n <= 2 * ROWS_KDA:
        return max(256, 1 << (n - 1).bit_length())
    return -(-n // (2 * ROWS_KDA)) * 2 * ROWS_KDA


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
