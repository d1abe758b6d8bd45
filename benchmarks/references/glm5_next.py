"""The plain reference of the GLM-5.3-Flash configurations: the layer
equations of zai-org/GLM-5.3-Flash's ``config.json`` (``model_type:
glm5_next_text``) in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision; no kernels, no cache, no batching, nothing
imported from the program.

The residual ``X`` [S, n, hidden] is ``n = hc_mult`` streams, the
embedding in each. Around each of a layer's two sublayers ``F`` (the
mixer with its norm ``ln1``, the feed-forward with ``ln2``):

    xbar = RMSNorm(vec X) (weight hc_norm);  [p | q | r] = xbar Phi
    H_pre = sigmoid(a0 p + b_pre);  H_post = 2 sigmoid(a1 q + b_post)
    H_res = Sinkhorn_iters(exp(a2 mat(r) + b_res))  (rows, then columns)
    X = H_res X + H_post^T F(H_pre X)

and the logits are ``RMSNorm(sum_i X_i) W_head``.

- KDA (``linear_attention``): ``q, k, v = silu(conv(h W_qkv))``; a head's
  q and k L2-normalised (``x / sqrt(sum x^2 + 1e-6)``), q times ``d^-1/2``;
  ``g = lb sigmoid(exp(A_h) (W_f_up W_f_down h + b_dt))``; ``beta =
  sigmoid(h W_beta)``; the state TOKEN BY TOKEN under ``lax.scan``: ``S =
  (I - beta k k^T) Diag(exp g) S + beta k v^T``, ``o = S^T q``; ``Mix =
  (RMSNorm_head(o) * sigmoid(W_g_up W_g_down h)) W_o``.
- DSA (``deepseek_sparse_attention``): ``c_q = RMSNorm(h W_qa)``, ``q =
  c_q W_qb``; ``c = RMSNorm(h W_kva)``; the EXPANDED form ``k_i = c
  W_UK,i``, ``v_i = c W_UV,i``, scale ``qk_head_dim^-1/2``; the indexer
  ``q^I = c_q W^I_q``, ``k^I = LayerNorm(h W^I_k)``, ``w = h W^I_w (Hi
  Di)^-1/2``, the pooled key of block ``b`` the mean of ``k^I`` over its
  ``pool`` positions, the score of a complete block before the query's
  own ``sum_j w_j relu(q^I_j . kbar_b)``; a query at ``t`` attends to the
  positions of the blocks scored at least its ``index_topk``-th best
  (``lax.top_k``; ties kept: every block at the threshold) and of its
  own block up to ``t``; with ``index_topk`` complete blocks or fewer,
  to everything before it.
- feed-forward: ``dense`` ``(act(u W_gate) * (u W_up)) W_down``; ``sparse``
  the sigmoid router over every expert in float32, the ``k`` largest ``s
  + b`` picked, gates ``scaling s_e / sum of the picked s``, a loop over
  the experts held, each on the tokens that picked it, and the shared
  expert. ``act(a) * b`` is ``silu(min(a, L)) * clip(b, -L, L)``,
  ``swiglu_limit`` L (at the seeded weights it never binds).

It runs a sublayer at a time over the whole sequence, so that a 36 k
sequence fits on one chip beside the weights: the streams stay on the
device, updated in place a block of rows at a time; the weights stay
in their stored types and each jitted part casts what it reads to
float32 while it runs; the KDA recurrence, the dense MLP and the shared
expert ``ROWS_KDA`` positions at a time; attention a block of queries
and a group of heads at a time, each group's part of ``W_o`` added as it
is done; the head over the sampled positions alone.

``lowp`` puts the nearest lower precision in the matmuls (float8_e4m3
operands, per-tensor scale; norms, router, mHC, the indexer, decay,
beta and state stay in float32): the control, never the reference.
``fault`` removes one thing, for the readings of the limits: ``dense``
(no selection), ``window`` (the last ``index_topk`` blocks instead of
the best), ``relu_off`` (the indexer without its ReLU), ``tail_off``
(the own block left out where others are selected), ``unpooled`` (the
best ``pool * index_topk`` positions by per-position scores),
``sinkhorn_1`` (one iteration), ``res_identity`` (``H_res = I``),
``post_unit`` (``H_post`` without its factor 2), ``kda_softplus``
(Solar's decay ``-exp(A) softplus(.)``), ``shared_off`` (no shared
expert).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("dense", "window", "relu_off", "tail_off", "unpooled",
          "sinkhorn_1", "res_identity", "post_unit", "kda_softplus",
          "shared_off")
KDA, DSA = "linear_attention", "deepseek_sparse_attention"
Q_BLOCK = 64      # queries a block of attention
HEAD_GROUP = 8    # heads whose keys and values are expanded at a time
ROWS_KDA = 2048   # positions a block of a KDA layer and of the dense MLP
ROWS = 1024       # rows a block of the streams' update and of the head


def _q8(x):
    """Round to float8_e4m3 (per-tensor scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, b, lowp):
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision="highest")


def _hi(a, b):
    return jnp.matmul(a, b, precision="highest")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _glu(a, b, limit):
    return jax.nn.silu(jnp.minimum(a, limit)) * jnp.clip(b, -limit, limit)


def _f32(p):
    """A layer's leaves in float32, inside a jitted function: only the
    leaves it reads are made, and only while it runs."""
    return {k: v.astype(F32) for k, v in p.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(buf, rows, lo):
    """Rows ``lo ..`` of ``buf`` become ``rows``, in place."""
    return jax.lax.dynamic_update_slice_in_dim(buf, rows.astype(buf.dtype),
                                               lo, 0)


def _by_blocks(fn, h, rows, width):
    """``fn`` over the rows of ``h`` a block at a time, into one float32
    buffer ``[S, width]``."""
    out = jnp.zeros((h.shape[0], width), F32)
    for lo in range(0, h.shape[0], rows):
        out = _put(out, fn(h[lo:lo + rows]), lo)
    return out


def sizes_of(cfg: dict) -> dict:
    """The published keys. ``n_routed_experts`` counts the experts held
    where the file is a cut; ``published`` then has the router's
    width. ``layer_ids``: the published layers held."""
    held = cfg.get("experts_held")
    n_all = cfg["n_routed_experts"] if held is None \
        else cfg["published"]["n_routed_experts"]
    held = (0, n_all) if held is None else (int(held[0]), int(held[1]))
    lin = cfg["linear_attn_config"]
    ids = cfg.get("layer_ids") or list(range(cfg["num_hidden_layers"]))
    return {"heads": cfg["num_attention_heads"],
            "rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "v": cfg["v_head_dim"], "qk": cfg["qk_head_dim"],
            "ihead": cfg["index_n_heads"], "idim": cfg["index_head_dim"],
            "topk": cfg["index_topk"], "pool": cfg["index_kpool"],
            "n": cfg["hc_mult"], "iters": cfg["hc_sinkhorn_iters"],
            "hc_eps": float(cfg["hc_eps"]), "eps": cfg["rms_norm_eps"],
            "lin_heads": lin["num_heads"], "lin_d": lin["head_dim"],
            "taps": lin["short_conv_kernel_size"],
            "lower": float(lin["gate_lower_bound"]),
            "top_k": cfg["num_experts_per_tok"], "experts": n_all,
            "held": held, "scaling": float(cfg["routed_scaling_factor"]),
            "limit": float(cfg["swiglu_limit"]),
            "kinds": [(cfg["layer_types"][i], cfg["mlp_layer_types"][i])
                      for i in ids]}


# -- the residual of n streams ------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "eps", "hc_eps", "iters",
                                             "fault"))
def hc_maps(xb, norm_w, phi, alpha, bias, *, n, eps, hc_eps, iters, fault):
    """A block of positions ``xb`` [R, n, D]: ``(H_pre X [R, D], H_post
    [R, n], H_res [R, n, n])``."""
    r = xb.shape[0]
    z = _hi(_rms(xb.reshape(r, -1), norm_w, eps), phi)
    pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + bias[:n])
    post = jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
    if fault != "post_unit":
        post = 2.0 * post
    m = jnp.exp(alpha[2] * z[:, 2 * n:] + bias[2 * n:]).reshape(r, n, n)
    for _ in range(1 if fault == "sinkhorn_1" else iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + hc_eps)
    if fault == "res_identity":
        m = jnp.broadcast_to(jnp.eye(n, dtype=F32), (r, n, n))
    return jnp.einsum("rn,rnd->rd", pre, xb, precision="highest"), post, m


@functools.partial(jax.jit, donate_argnums=(0,))
def hc_merge(x, out, post, res, lo):
    """Rows ``lo .. lo + R - 1`` of the streams ``x`` [S, n, D] become
    ``H_res X + H_post^T out``, in place."""
    r = out.shape[0]
    xb = jax.lax.dynamic_slice_in_dim(x, lo, r, 0)
    new = jnp.einsum("rij,rjd->rid", res, xb, precision="highest") + \
        post[:, :, None] * out[:, None]
    return jax.lax.dynamic_update_slice_in_dim(x, new, lo, 0)


def sublayer(x, p, s, sz, fault, fn):
    """``X <- H_res X + H_post^T fn(H_pre X)`` over the whole sequence,
    the maps a block of rows at a time."""
    rows = min(ROWS, x.shape[0])
    hin = jnp.zeros((x.shape[0], x.shape[2]), F32)
    maps = []
    for lo in range(0, x.shape[0], rows):
        h, post, res = hc_maps(
            x[lo:lo + rows], p[f"hc_norm_{s}"], p[f"hc_phi_{s}"],
            p[f"hc_alpha_{s}"], p[f"hc_bias_{s}"], n=sz["n"], eps=sz["eps"],
            hc_eps=sz["hc_eps"], iters=sz["iters"], fault=fault)
        hin = _put(hin, h, lo)
        maps.append((post, res))
    out = fn(hin)
    del hin
    for i, (post, res) in enumerate(maps):
        x = hc_merge(x, out[i * rows:(i + 1) * rows], post, res, i * rows)
    return x


# -- KDA ----------------------------------------------------------------------

def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=(
    "n", "d", "taps", "eps", "lower", "lowp", "fault"))
def kda_rows(p, hin, seen, state, *, n, d, taps, eps, lower, lowp, fault):
    """A block of consecutive positions' ``H_pre X`` [R, D] through the
    KDA mixer: ``(out, seen', state')``; ``seen`` [taps - 1, 3 n d]: the
    convolution's inputs before the block."""
    p = _f32(p)
    r = hin.shape[0]
    h = _rms(hin, p["ln1"], eps)
    seen = jnp.concatenate([seen, _mm(h, p["wqkv"], lowp)], axis=0)
    conv = sum(seen[i:i + r] * p["conv"][i] for i in range(taps))
    q, k, v = jnp.split(jax.nn.silu(conv), 3, axis=-1)
    q = _l2norm(q.reshape(r, n, d)) * (d ** -0.5)
    k = _l2norm(k.reshape(r, n, d))
    v = v.reshape(r, n, d)
    dt = _hi(_hi(h, p["wf_down"]), p["wf_up"]) + p["dt_bias"]
    a = jnp.exp(p["a_log"])[:, None]
    dt = dt.reshape(r, n, d)
    if fault == "kda_softplus":
        g = -a * jax.nn.softplus(dt)
    else:
        g = lower * jax.nn.sigmoid(a * dt)
    beta = jax.nn.sigmoid(_hi(h, p["w_beta"]))  # [R, n]

    def token(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[:, :, None]
        u = bt[:, None] * (vt - jnp.einsum("nk,nkv->nv", kt, s,
                                           precision="highest"))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("nk,nkv->nv", qt, s, precision="highest")

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    o = _rms(o, p["o_norm"], eps).reshape(r, n * d)
    gate = jax.nn.sigmoid(_mm(_mm(h, p["wg_down"], lowp), p["wg_up"], lowp))
    return _mm(o * gate, p["wo"], lowp), seen[r:], state


def kda_mix(p, hin, sz, lowp, fault):
    n, d, taps = sz["lin_heads"], sz["lin_d"], sz["taps"]
    seen = jnp.zeros((taps - 1, 3 * n * d), F32)
    state = jnp.zeros((n, d, d), F32)
    out = jnp.zeros(hin.shape, F32)
    for lo in range(0, hin.shape[0], ROWS_KDA):
        o, seen, state = kda_rows(
            p, hin[lo:lo + ROWS_KDA], seen, state, n=n, d=d, taps=taps,
            eps=sz["eps"], lower=sz["lower"], lowp=lowp, fault=fault)
        out = _put(out, o, lo)
    return out


# -- DSA ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def dsa_keys(p, hin, *, eps, lowp):
    """The sequence's latent ``c`` and indexer keys ``k^I`` (float32,
    LayerNorm with eps 1e-5)."""
    p = _f32(p)
    h = _rms(hin, p["ln1"], eps)
    c = _rms(_mm(h, p["wkv_a"], lowp), p["kv_norm"], eps)
    ki = _hi(h, p["wi_k"])
    mu = jnp.mean(ki, -1, keepdims=True)
    var = jnp.mean(jnp.square(ki - mu), -1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(var + 1e-5) * p["ki_norm"] + p["ki_bias"]
    return c, ki


@functools.partial(jax.jit, static_argnames=("heads", "nope", "v", "lowp"))
def expand(p, c, g0, *, heads, nope, v, lowp):
    """Keys and values of heads ``g0 .. g0 + heads - 1``: ``c W_UK,i``,
    ``c W_UV,i`` [S, heads, .]."""
    p = _f32(p)
    uk = jax.lax.dynamic_slice_in_dim(p["w_uk"], g0, heads, 0)
    uv = jax.lax.dynamic_slice_in_dim(p["w_uv"], g0, heads, 0)
    if lowp:
        c, uk, uv = _q8(c), _q8(uk), _q8(uv)
    return (jnp.einsum("sr,hrn->shn", c, uk, precision="highest"),
            jnp.einsum("sr,hrv->shv", c, uv, precision="highest"))


@functools.partial(jax.jit, static_argnames=(
    "ihead", "idim", "topk", "pool", "eps", "lowp", "fault"))
def dsa_select(p, hin, ki, start, *, ihead, idim, topk, pool, eps, lowp,
               fault):
    """For the queries ``start .. start + Q - 1`` (their inputs ``hin``):
    the positions each attends to, bool [Q, S], and the query
    projections ``c_q`` [Q, q_lora]."""
    p = _f32(p)
    hb = _rms(hin, p["ln1"], eps)
    qn, s = hb.shape[0], ki.shape[0]
    at = start + jnp.arange(qn)
    cq = _rms(_mm(hb, p["wq_a"], lowp), p["q_norm"], eps)
    qi = _hi(cq, p["wi_q"]).reshape(qn, ihead, idim)
    w = _hi(hb, p["wi_w"]) * (ihead * idim) ** -0.5
    kpos = jnp.arange(s)
    if fault == "unpooled":
        dots = jnp.einsum("qhd,kd->qhk", qi, ki, precision="highest")
        sc = jnp.einsum("qh,qhk->qk", w, jnp.maximum(dots, 0.0))
        own_lo = (at // pool * pool)[:, None]
        cand = kpos[None] < own_lo
        sc = jnp.where(cand, sc, -jnp.inf)
        kth = jax.lax.top_k(sc, min(topk * pool, s))[0][:, -1:]
        many = jnp.sum(cand, -1, keepdims=True) > topk * pool
        picked = cand & ((sc >= kth) | ~many)
        tail = (kpos[None] >= own_lo) & (kpos[None] <= at[:, None])
        return picked | tail, cq
    nb = -(-s // pool)  # the last block may be incomplete: never scored
    kbar = jnp.mean(jnp.pad(ki, ((0, nb * pool - s), (0, 0)))
                    .reshape(nb, pool, -1), axis=1)
    dots = jnp.einsum("qhd,bd->qhb", qi, kbar, precision="highest")
    if fault != "relu_off":
        dots = jnp.maximum(dots, 0.0)
    sc = jnp.einsum("qh,qhb->qb", w, dots)
    blk = jnp.arange(nb)[None]
    own = (at // pool)[:, None]
    cand = blk < own
    many = jnp.sum(cand, -1, keepdims=True) > topk
    if fault == "dense":
        chosen = cand
    elif fault == "window":
        chosen = cand & (blk >= own - topk)
    else:
        sc = jnp.where(cand, sc, -jnp.inf)
        kth = jax.lax.top_k(sc, min(topk, nb))[0][:, -1:]
        chosen = cand & ((sc >= kth) | ~many)
    tail = blk == own
    if fault == "tail_off":
        tail = tail & ~jnp.any(cand, -1, keepdims=True)
    blocks = chosen | tail
    return jnp.repeat(blocks, pool, axis=1)[:, :s] & \
        (kpos[None] <= at[:, None]), cq


@functools.partial(jax.jit, static_argnames=("heads", "nope", "scale",
                                             "lowp"))
def dsa_attend(p, cq, seen, k, v, g0, *, heads, nope, scale, lowp):
    """Heads ``g0 .. g0 + heads - 1`` of a block of queries: softmax over
    the positions ``seen`` of ``q_i . k_i * scale``, times ``v_i``."""
    p = _f32(p)
    qn = cq.shape[0]
    wq = jax.lax.dynamic_slice_in_dim(
        p["wq_b"].reshape(p["wq_b"].shape[0], -1, nope), g0, heads, 1)
    q = _mm(cq, wq.reshape(wq.shape[0], -1), lowp).reshape(qn, heads, nope)
    sc = jnp.einsum("qhn,khn->hqk", q, k, precision="highest") * scale
    pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khv->qhv", pr, v, precision="highest")


@functools.partial(jax.jit, static_argnames=("heads", "v", "lowp"))
def dsa_out(p, o, g0, *, heads, v, lowp):
    """Heads ``g0 .. g0 + heads - 1``'s part of ``concat_i(o_i) W_o``."""
    wo = jax.lax.dynamic_slice_in_dim(p["wo"], g0 * v, heads * v, 0)
    return _mm(o.reshape(o.shape[0], -1), wo.astype(F32), lowp)


def dsa_mix(p, hin, sz, lowp, fault):
    s = hin.shape[0]
    heads, g = sz["heads"], min(HEAD_GROUP, sz["heads"])
    c, ki = dsa_keys(p, hin, eps=sz["eps"], lowp=lowp)
    qb = min(Q_BLOCK, s)

    def attend(lo, k, v, g0):
        # the selection again for each group of heads: [Q, S] at a time
        seen, cq = dsa_select(p, hin[lo:lo + qb], ki, lo, ihead=sz["ihead"],
                              idim=sz["idim"], topk=sz["topk"],
                              pool=sz["pool"], eps=sz["eps"], lowp=lowp,
                              fault=fault)
        return dsa_attend(p, cq, seen, k, v, g0, heads=g, nope=sz["nope"],
                          scale=sz["qk"] ** -0.5, lowp=lowp)

    out = jnp.zeros(hin.shape, F32)
    for g0 in range(0, heads, g):
        k, v = expand(p, c, g0, heads=g, nope=sz["nope"], v=sz["v"],
                      lowp=lowp)
        o = jnp.zeros((s, g, sz["v"]), F32)
        for lo in range(0, s, qb):
            o = _put(o, attend(lo, k, v, g0), lo)
        del k, v
        out = out + dsa_out(p, o, g0, heads=g, v=sz["v"], lowp=lowp)
    return out


# -- the feed-forward ---------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "limit", "lowp"))
def dense_ffn(p, hin, *, eps, limit, lowp):
    p = _f32(p)
    u = _rms(hin, p["ln2"], eps)
    return _mm(_glu(_mm(u, p["wd_gate"], lowp), _mm(u, p["wd_up"], lowp),
                    limit), p["wd_down"], lowp)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scaling"))
def route(hin, ln2, w_router, bias, eps, top_k, scaling):
    """``(u, picks, gates, margin)``: the router in float32 whatever the
    matmuls' precision; ``margin``: the last pick's selection score minus
    the first left-out expert's."""
    u = _rms(hin, ln2, eps)
    s = jax.nn.sigmoid(_hi(u, w_router))
    sel, idx = jax.lax.top_k(s + bias, top_k + 1)
    picked = jnp.take_along_axis(s, idx[:, :top_k], axis=-1)
    gates = picked / jnp.sum(picked, -1, keepdims=True) * scaling
    return u, idx[:, :top_k], gates, sel[:, top_k - 1] - sel[:, top_k]


@functools.partial(jax.jit, static_argnames=("limit", "lowp"))
def expert(u_rows, gate, wg, wu, wd, limit, lowp):
    """One expert on the rows that picked it, times their gates."""
    wg, wu, wd = wg.astype(F32), wu.astype(F32), wd.astype(F32)
    mid = _glu(_mm(u_rows, wg, lowp), _mm(u_rows, wu, lowp), limit)
    return _mm(mid, wd, lowp) * gate[:, None]


def experts(p, u, idx, gates, sz, lowp, shared: bool):
    """The held experts' parts and the shared expert, [S, D]."""
    idx, gates = np.asarray(idx), np.asarray(gates)
    first, count = sz["held"]
    out = jnp.zeros_like(u)
    if shared:
        out = _by_blocks(lambda b: expert(
            b, jnp.ones((b.shape[0],), F32), p["ws_gate"], p["ws_up"],
            p["ws_down"], sz["limit"], lowp), u, ROWS_KDA, u.shape[1])
    for j in range(count):
        rows, pick = np.nonzero(idx == first + j)
        if rows.size == 0:
            continue
        n = max(8, 1 << int(rows.size - 1).bit_length())
        at = np.zeros((n,), np.int32)
        at[:rows.size] = rows
        g = np.zeros((n,), np.float32)  # padding rows: gate 0
        g[:rows.size] = gates[rows, pick]
        part = expert(u[at], jnp.asarray(g), p["w_gate"][j], p["w_up"][j],
                      p["w_down"][j], sz["limit"], lowp)
        out = _add_rows(out, jnp.asarray(at), part)
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_rows(buf, at, rows):
    return buf.at[at].add(rows)


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _layer(params, i):
    """Layer ``i``'s leaves by short name, in their stored types: each
    jitted part casts what it reads to float32 while it runs."""
    lead = f"model.layers.{i}."
    return {k[len(lead):]: v for k, v in params.items()
            if k.startswith(lead)}


def hidden_states(cfg: dict, params: dict, ids, lowp: bool = False,
                  fault: str | None = None) -> tuple:
    """The streams summed ``[S, H]`` (before the last norm) of one
    sequence of token ids, and for every position the smallest router
    margin over the expert layers."""
    sz = sizes_of(cfg)
    e = params["model.embed"][np.asarray(ids)].astype(F32)
    x = jnp.array(jnp.broadcast_to(e[:, None],
                                   (e.shape[0], sz["n"], e.shape[1])))
    del e
    margin = None
    for i, (mixer, mlp) in enumerate(sz["kinds"]):
        p = _layer(params, i)
        mix = kda_mix if mixer == KDA else dsa_mix
        x = sublayer(x, p, "a", sz, fault,
                     lambda h: mix(p, h, sz, lowp, fault))
        if mlp == "dense":
            # a block of rows at a time: the [S, 12288] float32
            # intermediates of a whole long sequence would not fit
            x = sublayer(x, p, "f", sz, fault, lambda h: _by_blocks(
                lambda b: dense_ffn(p, b, eps=sz["eps"], limit=sz["limit"],
                                    lowp=lowp), h, ROWS_KDA, h.shape[1]))
            continue

        def moe(h):
            nonlocal margin
            u, idx, gates, m = route(h, p["ln2"], p["router"],
                                     p["router_bias"], sz["eps"],
                                     sz["top_k"], sz["scaling"])
            margin = m if margin is None else jnp.minimum(margin, m)
            return experts(p, u, idx, gates, sz, lowp,
                           shared=fault != "shared_off")
        x = sublayer(x, p, "f", sz, fault, moe)
    return jnp.sum(x, axis=1), margin


def moe_layer(cfg: dict, params: dict, i: int, h):
    """Layer ``i``'s ``F(h)`` of the feed-forward sublayer alone: what
    the shares of a deployment add up to (the tests')."""
    sz = sizes_of(cfg)
    p = _layer(params, i)
    u, idx, gates, _ = route(h, p["ln2"], p["router"], p["router_bias"],
                             sz["eps"], sz["top_k"], sz["scaling"])
    return experts(p, u, idx, gates, sz, False, True)


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def head_logits(x, norm_w, head_w, eps, lowp=False):
    """Logits of the rows ``x`` [N, H]: the last norm, the untied head."""
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
    norm_w = params["model.norm"].astype(F32)
    head_w = params["lm_head"].astype(F32)

    def by_rows(fn, *arrays):
        n = arrays[0].shape[0]
        pad = -(-n // ROWS) * ROWS - n
        arrays = [jnp.pad(jnp.asarray(a), ((0, pad),) + ((0, 0),) *
                          (np.ndim(a) - 1)) for a in arrays]
        return np.concatenate([
            np.asarray(fn(*(a[i:i + ROWS] for a in arrays)))
            for i in range(0, n + pad, ROWS)])[:n]

    def gap_rows(x, tok):
        return _gap_rows(x, tok, norm_w, head_w, eps)

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
            low_tok = by_rows(lambda r: _low_tokens(r, norm_w, head_w, eps),
                              x_low[at])
            lows.append(by_rows(gap_rows, x[at], low_tok))
    return {"gaps": gaps, "margins": margins, "control_gaps": lows}
