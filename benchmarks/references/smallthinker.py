"""The plain reference of the SmallThinker configurations: the layer
equations of PowerInfer/SmallThinker-21BA3B-Instruct's ``config.json``
in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision; no kernels, no cache, nothing imported from the program.

Layer ``l``, input ``x``:

- ``h = RMSNorm_1(x)``; the router reads ``h``, BEFORE attention: ``r =
  h W_r`` over all experts, the ``k`` largest picked, gates a softmax
  over the picked logits (``moe_primary_router_apply_softmax`` with
  ``norm_topk_prob``: a softmax over all, renormalised over the picks);
- ``q, k, v = h W_q, h W_k, h W_v``; query heads ``G j .. G j + G - 1``
  read KV head ``j``; ``rope_layout[l]`` 1: rotary positions on q and k
  (rotate-half, the whole head, theta from the config), 0: no positions
  at all; ``sliding_window_layout[l]`` 1: a query at ``p`` sees keys ``p
  - window + 1 .. p``, 0: every key up to ``p``; scale 1/sqrt(head);
- ``y = x + Attn W_o``; ``u = RMSNorm_2(y)``; ``x' = y + sum_e g_e
  (relu(u W_gate^e) * (u W_up^e)) W_down^e`` over the picked experts: a
  plain loop over the experts held, each on the tokens that picked it;
- a final RMSNorm and an untied head.

What the config does not give is the configuration's ``assumed`` list.
It runs layer by layer and expert by expert, attention in blocks of
queries, the head over the sampled positions alone, so that the float32
copy of a 12-layer cut (22 GB whole) never stands in memory at once.
Given ``experts_held`` it computes the same share of every layer that a
chip holding those experts computes.

Routing is discontinuous: where the 6th and the 7th router logit of a
position lie closer than ``delta`` in any layer, rounding the
activations to bf16 may pick the other expert legitimately.
``served_token_gaps`` returns the smallest such margin of every sampled
position (``margins``); the driver leaves the positions under ``delta``
out of the mean gap and reports their share.

``lowp`` puts the nearest lower precision in the matmuls (operands
rounded to float8_e4m3 with a per-tensor scale; the router stays in
float32 as the configuration states): the control, never the reference.
``fault`` plants a fault a serving path can have, for the readings of
the limits: ``window_page`` (window layers see one page of keys more),
``rope_global`` (rotary applied to the global layers too), ``top5``
(one pick fewer than the config's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("window_page", "rope_global", "top5")
Q_BLOCK = 512   # queries a block of attention
ROWS = 256      # sampled positions a block of the head


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


def _rotary(x, theta):
    """x [S, H, D], positions 0..S-1, rotate-half over the whole head."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def sizes_of(cfg: dict) -> dict:
    """The published keys, and which experts are held: ``(first,
    count)`` under ``experts_held``, all of them without."""
    held = cfg.get("experts_held") or (0, cfg["moe_num_primary_experts"])
    return {"heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "window": cfg["sliding_window_size"],
            "top_k": cfg["moe_num_active_primary_experts"],
            "experts": cfg["moe_num_primary_experts"],
            "held": (int(held[0]), int(held[1])),
            "layers": cfg["num_hidden_layers"]}


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv", "d", "eps", "theta", "window", "rope", "lowp"))
def attention(p, x, *, heads, kv, d, eps, theta, window, rope, lowp):
    """``(y, h)`` of one layer up to its expert part: ``y = x + Attn
    W_o`` and the normed input ``h`` that the router reads. x: [S, H]."""
    s = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    q = _mm(h, p["wq"], lowp).reshape(s, heads, d)
    k = _mm(h, p["wk"], lowp).reshape(s, kv, d)
    v = _mm(h, p["wv"], lowp).reshape(s, kv, d)
    if rope:
        q, k = _rotary(q, theta), _rotary(k, theta)
    g = heads // kv
    blk = min(Q_BLOCK, s)
    kpos = jnp.arange(s)[None]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, 0)
        qb = qb.reshape(blk, kv, g, d)
        sc = jnp.einsum("qjgd,kjd->jgqk", qb, k,
                        precision="highest") / (d ** 0.5)
        qpos = (start + jnp.arange(blk))[:, None]
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (kpos > qpos - window)
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("jgqk,kjd->qjgd", pr, v,
                          precision="highest").reshape(blk, heads * d)

    a = jax.lax.map(rows, jnp.arange(0, s, blk)).reshape(s, heads * d)
    return x + _mm(a, p["wo"], lowp), h


@functools.partial(jax.jit, static_argnames=("top_k",))
def route(h, w_router, top_k):
    """Picks, gates and the margin between the last pick and the first
    expert left out, for every position: the router in float32 whatever
    the matmuls' precision."""
    r = jnp.matmul(h, w_router, precision="highest")
    vals, idx = jax.lax.top_k(r, top_k + 1)
    return (idx[:, :top_k], jax.nn.softmax(vals[:, :top_k], axis=-1),
            vals[:, top_k - 1] - vals[:, top_k])


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm2(y, w, eps):
    return _rms(y, w, eps)


@functools.partial(jax.jit, static_argnames=("lowp",))
def expert(u_rows, gate, wg, wu, wd, lowp):
    """One expert on the rows that picked it, times their gates."""
    mid = jnp.maximum(_mm(u_rows, wg, lowp), 0.0) * _mm(u_rows, wu, lowp)
    return _mm(mid, wd, lowp) * gate[:, None]


def experts(p, y, idx, gates, sz, lowp):
    """``y + sum of the held experts' parts``: a loop over the experts
    held, each on the tokens that picked it (their count padded to a
    power of two, so that a few shapes serve every expert)."""
    u = _norm2(y, p["ln2"], sz["eps"])
    idx, gates = np.asarray(idx), np.asarray(gates)
    first, count = sz["held"]
    out = y
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


LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router",
                "w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _layer(params, i):
    """Layer ``i``'s leaves by short name, in float32 but the experts',
    which are cast one expert at a time."""
    return {k: (params[f"model.layers.{i}.{k}"] if k in EXPERT_LEAVES
                else params[f"model.layers.{i}.{k}"].astype(F32))
            for k in LAYER_LEAVES}


def hidden_states(cfg: dict, params: dict, ids, lowp: bool = False,
                  fault: str | None = None) -> tuple:
    """The final hidden states ``[S, H]`` (before the last norm) of one
    sequence of token ids, and for every position the smallest margin,
    over the layers, between its last pick's router logit and the first
    expert's left out."""
    sz = sizes_of(cfg)
    x = params["model.embed"][np.asarray(ids)].astype(F32)
    margin = None
    page = int(cfg.get("engine", {}).get("page_size", 1))
    top_k = sz["top_k"] - (1 if fault == "top5" else 0)
    for i in range(sz["layers"]):
        p = _layer(params, i)
        window = sz["window"] if cfg["sliding_window_layout"][i] else None
        if window is not None and fault == "window_page":
            window += page
        rope = bool(cfg["rope_layout"][i]) or fault == "rope_global"
        y, h = attention(p, x, heads=sz["heads"], kv=sz["kv"], d=sz["d"],
                         eps=sz["eps"], theta=sz["theta"], window=window,
                         rope=rope, lowp=lowp)
        idx, gates, m = route(h, p["router"], top_k)
        margin = m if margin is None else jnp.minimum(margin, m)
        x = experts(p, y, idx, gates, sz, lowp)
    return x, margin


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
    # the head's weights go in as arguments: closed over, 1.5 GB of
    # float32 would be compiled into each program as a constant
    norm_w = params["model.norm"].astype(F32)
    head_w = params["lm_head"].astype(F32)

    def gap_rows(x, tok):
        return _gap_rows(x, tok, norm_w, head_w, eps)

    def low_tokens(x_low):
        return _low_tokens(x_low, norm_w, head_w, eps)

    def by_rows(fn, *arrays):
        """``fn`` over blocks of ROWS positions (a block of logits at a
        time: the whole sequence's would be 10 GB)."""
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
        # a power of two from 256 up: a few shapes in all
        size = max(256, 1 << (n - 1).bit_length())
        ids = np.zeros((size,), np.int32)
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
