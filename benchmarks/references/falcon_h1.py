"""The plain reference of the Falcon-H1 configurations: the layer
equations of tiiuae/Falcon-H1-34B-Instruct's ``config.json`` (the SSM
branch is Mamba-2's, arXiv:2405.21060) in straightforward ``jax.numpy``,
float32, matmuls at ``highest`` precision; no kernels, no cache, no
batching, nothing imported from the program.

Block ``l``, input ``x`` [T, hidden], ``m_*`` the config's multipliers:
``h = RMSNorm_in(x)``; ``x_1 = x + A_out + S_out``; ``x' = x_1 +
(silu((g W_gate) m_gate) * (g W_up)) W_down m_down`` with ``g =
RMSNorm_ff(x_1)``; the embedding ``E[id] m_embed``, the logits
``RMSNorm_final(x_L) W_head m_head``.

- ``A_out``: ``a = h m_attn_in``; ``q = a W_q``, ``k = (a W_k) m_key``,
  ``v = a W_v``; rotary (rotate-half, the whole head, ``rope_theta``) on
  q and k at their positions; causal softmax of ``q k^T / sqrt(d)``
  (query heads ``G j .. G j + G - 1`` read KV head ``j``); ``concat
  W_o m_attn_out``.
- ``S_out``: ``u = h m_ssm_in``; ``[z | x | B | C | dt] = u W_in``, the
  slices times ``ssm_multipliers`` in that order; ``[x | B | C] =
  silu(conv + b_conv)`` with ``conv[t, c] = sum_i w[i, c] in[t - taps + 1
  + i, c]`` (zeros before the sequence); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; a head's state ``S [d_state, d_head]``, zero at
  the start, POSITION BY POSITION under ``lax.scan``: ``S_t = exp(dt_t
  A) S_{t-1} + dt_t B_t x_t^T`` with its group's B (heads ``h`` of group
  ``h // (heads / groups)``), ``y_t = S_t^T C_t + D x_t``; ``y =
  RMSNorm_grouped(y * silu(z))`` over ``mamba_n_groups`` groups; ``y W_out
  m_ssm_out``.

What the config does not give is the configuration's ``assumed`` list.
It runs a block at a time, attention a block of queries at a time and
the MLP a block of rows at a time, the head over the sampled positions
alone, so that a 4096-position sequence fits beside the weights.

``lowp`` puts the nearest lower precision in the matmuls (operands
rounded to float8_e4m3 with a per-tensor scale; norms, decay, dt and
state stay in float32 as the configuration states): the control, never
the reference. ``fault`` plants a fault a serving path can have, for the
readings of the limits: ``no_decay`` (exp(dt A) = 1), ``conv_tap`` (the
convolution one position late: it never sees the current position),
``gate_off`` (no ``silu(z)``), ``d_skip_off`` (no ``D x``),
``key_mult_off`` (no ``m_key``), ``attn_off``, ``ssm_off``, ``mlp_off``
(a branch left out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("no_decay", "conv_tap", "gate_off", "d_skip_off", "key_mult_off",
          "attn_off", "ssm_off", "mlp_off")
Q_BLOCK = 128   # queries a block of attention
MLP_ROWS = 1024  # rows a block of the MLP
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


class _Sizes(dict):
    """The sizes as a hashable static argument of the jitted pieces."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def sizes_of(cfg: dict) -> dict:
    """The published keys the equations read, as hashable statics."""
    return _Sizes({"heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
            "m_ain": float(cfg["attention_in_multiplier"]),
            "m_aout": float(cfg["attention_out_multiplier"]),
            "m_key": float(cfg["key_multiplier"]),
            "m_sin": float(cfg["ssm_in_multiplier"]),
            "m_sout": float(cfg["ssm_out_multiplier"]),
            "m_slices": tuple(float(m) for m in cfg["ssm_multipliers"]),
            "m_mlp": tuple(float(m) for m in cfg["mlp_multipliers"]),
            "ssm_heads": cfg["mamba_n_heads"], "p": cfg["mamba_d_head"],
            "n": cfg["mamba_d_state"], "groups": cfg["mamba_n_groups"],
            "d_ssm": cfg["mamba_d_ssm"], "taps": cfg["mamba_d_conv"]})


def _rotate(x, theta):
    """x [S, H, D] at positions 0..S-1, rotate-half."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


# -- the attention branch ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sz", "lowp"))
def attention(p, h, m_key, *, sz, lowp):
    """``A_out`` of the whole sequence's normed input ``h`` [S, hidden],
    a block of queries at a time; ``m_key`` the key's multiplier (a
    value: a planted fault sets it to 1 without another compile)."""
    s = h.shape[0]
    heads, kv, d = sz["heads"], sz["kv"], sz["d"]
    a = h * sz["m_ain"]
    k = _rotate((_mm(a, p["wk"], lowp) * m_key).reshape(s, kv, d),
                sz["theta"])
    v = _mm(a, p["wv"], lowp).reshape(s, kv, d)
    q = _rotate(_mm(a, p["wq"], lowp).reshape(s, heads, d), sz["theta"])
    g = heads // kv
    blk = min(Q_BLOCK, s)
    kpos = jnp.arange(s)[None]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, 0).reshape(
            blk, kv, g, d)
        sc = jnp.einsum("qjgd,kjd->jgqk", qb, k,
                        precision="highest") / (d ** 0.5)
        seen = kpos <= (start + jnp.arange(blk))[:, None]
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("jgqk,kjd->qjgd", pr, v,
                       precision="highest").reshape(blk, heads * d)
        return _mm(o, p["wo"], lowp) * sz["m_aout"]

    return jax.lax.map(rows, jnp.arange(0, s, blk)).reshape(s, -1)


# -- the SSM branch ------------------------------------------------------------

def _slice_scale(sz):
    widths = (sz["d_ssm"], sz["d_ssm"], sz["groups"] * sz["n"],
              sz["groups"] * sz["n"], sz["ssm_heads"])
    return jnp.concatenate([jnp.full((w,), m, F32)
                            for w, m in zip(widths, sz["m_slices"])])


def switches(fault) -> dict:
    """The SSM's parts as values, 1 on and 0 off: a planted fault turns
    one off without another compile."""
    off = {"no_decay": "decay", "conv_tap": "tap", "gate_off": "gate",
           "d_skip_off": "d_skip"}.get(fault)
    return {k: jnp.float32(k != off) for k in ("decay", "tap", "gate",
                                               "d_skip")}


@functools.partial(jax.jit, static_argnames=("sz", "lowp"))
def ssm(p, h, on, *, sz, lowp):
    """``S_out`` of the whole sequence's normed input ``h`` [S, hidden]:
    the recurrence a position at a time. ``on``: :func:`switches`."""
    s = h.shape[0]
    heads, pd, n, groups = sz["ssm_heads"], sz["p"], sz["n"], sz["groups"]
    d_ssm, taps = sz["d_ssm"], sz["taps"]
    gn = groups * n
    proj = _mm(h * sz["m_sin"], p["w_in"], lowp) * _slice_scale(sz)
    z, xbc, dt = (proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * gn],
                  proj[:, 2 * d_ssm + 2 * gn:])
    # one zero row more than the taps need: the planted late tap reads it
    seen = jnp.concatenate([jnp.zeros((taps, xbc.shape[1]), F32), xbc], 0)
    conv = sum(on["tap"] * seen[1 + i:1 + i + s] * p["conv"][i]
               + (1.0 - on["tap"]) * seen[i:i + s] * p["conv"][i]
               for i in range(taps)) + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_ssm].reshape(s, heads, pd)
    bm = xbc[:, d_ssm:d_ssm + gn].reshape(s, groups, n)
    cm = xbc[:, d_ssm + gn:].reshape(s, groups, n)
    rep = heads // groups
    bm, cm = jnp.repeat(bm, rep, axis=1), jnp.repeat(cm, rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])          # [S, heads]
    a = -jnp.exp(p["a_log"])
    decay = jnp.where(on["decay"] > 0, jnp.exp(dt * a), 1.0)

    def step(state, xs):
        xt, bt, ct, dtt, dec = xs
        state = state * dec[:, None, None] + \
            (dtt[:, None] * bt)[:, :, None] * xt[:, None, :]
        return state, jnp.einsum("hn,hnp->hp", ct, state,
                                 precision="highest")

    _, y = jax.lax.scan(step, jnp.zeros((heads, n, pd), F32),
                        (x, bm, cm, dt, decay))
    y = (y + on["d_skip"] * p["d_skip"][:, None] * x).reshape(s, d_ssm)
    y = y * jnp.where(on["gate"] > 0, jax.nn.silu(z), 1.0)
    y = _rms(y.reshape(s, groups, -1), 1.0, sz["eps"]).reshape(s, d_ssm) \
        * p["ssm_norm"]
    return _mm(y, p["w_out"], lowp) * sz["m_sout"]


# -- the MLP -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sz", "lowp"))
def mlp_rows(p, x1, *, sz, lowp):
    g = _rms(x1, p["ln_ff"], sz["eps"])
    m_gate, m_down = sz["m_mlp"]
    mid = jax.nn.silu(_mm(g, p["w_gate"], lowp) * m_gate) * \
        _mm(g, p["w_up"], lowp)
    return _mm(mid, p["w_down"], lowp) * m_down


def _layer(params, i):
    """Block ``i``'s leaves by short name, in float32."""
    lead = f"model.layers.{i}."
    return {k[len(lead):]: v.astype(F32) for k, v in params.items()
            if k.startswith(lead)}


def hidden_states(cfg: dict, params: dict, ids, lowp: bool = False,
                  fault: str | None = None):
    """The final hidden states ``[S, hidden]`` (before the last norm) of
    one sequence of token ids."""
    sz = sizes_of(cfg)
    m_key = jnp.float32(1.0 if fault == "key_mult_off" else sz["m_key"])
    on = switches(fault)
    x = params["model.embed"][np.asarray(ids)].astype(F32) * \
        float(cfg["embedding_multiplier"])
    for i in range(cfg["num_hidden_layers"]):
        p = _layer(params, i)
        h = _rms(x, p["ln_in"], sz["eps"])
        if fault != "attn_off":
            x = x + attention(p, h, m_key, sz=sz, lowp=lowp)
        if fault != "ssm_off":
            x = x + ssm(p, h, on, sz=sz, lowp=lowp)
        if fault != "mlp_off":
            x = x + jnp.concatenate([
                mlp_rows(p, x[lo:lo + MLP_ROWS], sz=sz, lowp=lowp)
                for lo in range(0, x.shape[0], MLP_ROWS)], axis=0)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "m_head", "lowp"))
def head_logits(x, norm_w, head_w, eps, m_head, lowp=False):
    """Logits of the rows ``x`` [N, hidden]: the last norm, the untied
    head ``[V, hidden]``, its multiplier."""
    return _mm(_rms(x, norm_w, eps), head_w.T, lowp) * m_head


def forward_logits(cfg: dict, params: dict, ids, fault=None):
    """Logits ``[S, V]`` of one whole sequence (the tests' size)."""
    x = hidden_states(cfg, params, ids, fault=fault)
    return head_logits(x, params["model.norm"].astype(F32),
                       params["lm_head"].astype(F32), cfg["rms_norm_eps"],
                       float(cfg["lm_head_multiplier"]))


def padded_size(n: int) -> int:
    """A power of two from 256 up: a handful of shapes, whatever the
    lengths."""
    return max(256, 1 << (n - 1).bit_length())


def served_token_gaps(cfg: dict, params: dict, sequences: list,
                      prompt_lens: list, control: bool = False,
                      fault: str | None = None) -> dict:
    """For each sequence (prompt + the tokens that were served), at
    every position that produced a served token: the gap of the served
    token below the reference's best logit (``gaps``) and, with
    ``control``, the gap of the token the lower precision puts first
    (``control_gaps``)."""
    eps, m_head = cfg["rms_norm_eps"], float(cfg["lm_head_multiplier"])
    # the head's weights go in as arguments: closed over, they would be
    # compiled into each program as a constant
    norm_w = params["model.norm"].astype(F32)
    head_w = params["lm_head"].astype(F32)

    def gap_rows(x, tok):
        ref = head_logits(x, norm_w, head_w, eps, m_head)
        return jnp.max(ref, -1) - jnp.take_along_axis(
            ref, tok[:, None], -1)[:, 0]

    def low_tokens(x_low):
        return jnp.argmax(head_logits(x_low, norm_w, head_w, eps, m_head,
                                      True), -1)

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

    gaps, lows = [], []
    for seq, plen in zip(sequences, prompt_lens):
        n = len(seq)
        ids = np.zeros((padded_size(n),), np.int32)
        ids[:n] = seq
        x = hidden_states(cfg, params, ids, fault=fault)
        at = slice(plen - 1, n - 1)
        served = np.asarray(seq[plen:], np.int32)
        gaps.append(by_rows(gap_rows, x[at], served))
        if control:
            x_low = hidden_states(cfg, params, ids, lowp=True)
            low_tok = by_rows(low_tokens, x_low[at])
            lows.append(by_rows(gap_rows, x[at], low_tok))
    return {"gaps": gaps, "control_gaps": lows}
