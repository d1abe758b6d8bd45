"""The plain reference of the GPT configurations: the published
equations in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision; no kernels, no cache, no batching tricks, nothing
imported from the program.

Pre-norm decoder (Radford et al. 2019; Brown et al. 2020): learned
positions, LayerNorm(eps), fused QKV laid out [3, heads, head_dim] on
the output axis, causal softmax attention scaled by 1/sqrt(head_dim),
GELU (tanh form), tied output head, mean next-token cross-entropy.
Departures from the paper are the configuration's ``assumed`` list.

Training follows AdamW with decoupled decay on every leaf. Arithmetic
is float32; what the configuration STORES in a narrower type (bf16
weights and moments) is rounded to that type when it is stored, as the
configuration states, and nowhere else. It runs layer by layer
(``jax.vjp`` of one block at a time, inputs of the blocks kept, each
leaf updated as soon as its gradient exists) so that 1.3B parameters
fit beside their moments on one 16 GB chip.

``lowp`` puts the nearest lower precision in the matmuls (operands
rounded to float8_e4m3 with a per-tensor scale): the control, never the
reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _q8(x):
    """Round to float8_e4m3 (per-tensor scale), straight-through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, lowp):
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision="highest")


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def block(p, x, heads, eps, lowp):
    """One decoder block. ``p``: this layer's leaves by short name."""
    b, s, h = x.shape
    d = h // heads
    y = _ln(x, p["ln_1.weight"], p["ln_1.bias"], eps)
    qkv = _mm(y, p["attn.qkv_proj.weight"], lowp) + p["attn.qkv_proj.bias"]
    qkv = qkv.reshape(b, s, 3, heads, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / (d ** 0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision="highest")
    a = a.reshape(b, s, h)
    x = x + _mm(a, p["attn.out_proj.weight"], lowp) + p["attn.out_proj.bias"]
    y = _ln(x, p["ln_2.weight"], p["ln_2.bias"], eps)
    y = _gelu(_mm(y, p["mlp.fc_in.weight"], lowp) + p["mlp.fc_in.bias"])
    return x + _mm(y, p["mlp.fc_out.weight"], lowp) + p["mlp.fc_out.bias"]


def embed(p, ids):
    s = ids.shape[1]
    return p["gpt.wte.weight"][ids] + p["gpt.wpe.weight"][jnp.arange(s)][None]


def head_logits(p, x, eps, lowp):
    y = _ln(x, p["gpt.ln_f.weight"], p["gpt.ln_f.bias"], eps)
    return _mm(y, p["gpt.wte.weight"].T, lowp)


def head_loss(p, x, labels, eps, lowp):
    logits = head_logits(p, x, eps, lowp)[:, :-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, labels[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - pick)


LAYER_LEAVES = ("ln_1.weight", "ln_1.bias", "attn.qkv_proj.weight",
                "attn.qkv_proj.bias", "attn.out_proj.weight",
                "attn.out_proj.bias", "ln_2.weight", "ln_2.bias",
                "mlp.fc_in.weight", "mlp.fc_in.bias", "mlp.fc_out.weight",
                "mlp.fc_out.bias")
TOP_LEAVES = ("gpt.wte.weight", "gpt.wpe.weight", "gpt.ln_f.weight",
              "gpt.ln_f.bias")


def _layer(params, i, cast=True):
    out = {k: params[f"gpt.h.{i}.{k}"] for k in LAYER_LEAVES}
    return {k: v.astype(F32) for k, v in out.items()} if cast else out


def _top(params):
    return {k: params[k].astype(F32) for k in TOP_LEAVES}


# -- serving: one forward over prompt + served tokens ----------------------

def served_token_gaps(cfg: dict, params: dict, sequences: list,
                      prompt_lens: list, control: bool = False) -> dict:
    """For each sequence (prompt + the tokens that were served), the
    reference's logits at every position that produced a served token.
    Returns the gap of each served token below the reference's best
    (``gaps``) and, with ``control``, at the same positions the gap of
    the token that the lower precision puts first (``control_gaps``)."""
    heads, eps = cfg["num_heads"], cfg["layer_norm_epsilon"]

    @functools.partial(jax.jit, static_argnums=(2,))
    def blk(p, x, lowp):
        return block(p, x, heads, eps, lowp)

    # every shape below depends on the padded length alone, so that a
    # fresh seed's prompts and answers compile nothing new
    @jax.jit
    def gap_rows(top, x, nxt):
        """At every position: the reference's best logit minus its logit
        of the token that follows in the sequence."""
        ref = head_logits(top, x, eps, False)[0]
        return jnp.max(ref, -1) - jnp.take_along_axis(
            ref, nxt[:, None], -1)[:, 0]

    @jax.jit
    def control_rows(top, x, x_low):
        """The same for the token the lower precision puts first."""
        ref = head_logits(top, x, eps, False)[0]
        low_tok = jnp.argmax(head_logits(top, x_low, eps, True)[0], -1)
        return jnp.max(ref, -1) - jnp.take_along_axis(
            ref, low_tok[:, None], -1)[:, 0]

    top = _top(params)
    gaps, lows = [], []
    for seq, plen in zip(sequences, prompt_lens):
        n = len(seq)
        # a power of two from 256 up: three or four shapes in all, so
        # every run after a checkout's first finds them compiled
        size = max(256, 1 << (n - 1).bit_length())
        ids = np.zeros((1, size), np.int32)
        ids[0, :n] = seq
        nxt = np.roll(ids[0], -1)
        x = embed(top, ids)
        for i in range(cfg["num_layers"]):
            x = blk(_layer(params, i), x, False)
        gaps.append(np.asarray(gap_rows(top, x, nxt))[plen - 1:n - 1])
        if control:
            x_low = embed(top, ids)
            for i in range(cfg["num_layers"]):
                x_low = blk(_layer(params, i), x_low, True)
            lows.append(np.asarray(
                control_rows(top, x, x_low))[plen - 1:n - 1])
    return {"gaps": gaps, "control_gaps": lows}


# -- training: AdamW steps, layer by layer ----------------------------------

def _store(x, like):
    return x.astype(like.dtype)


def train_steps(cfg: dict, params: dict, batches: list, hp: dict,
                lowp: bool = False, fault: str | None = None,
                sample_index=None) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``params`` (consumed).
    ``batches``: token ids [batch, seq] per step; labels are the ids.
    Returns per-step losses, the per-leaf norm of the first gradient and
    of the parameters' change over all the steps, and for the vector
    leaves (biases, norms) the first gradient's magnitude and the change
    element by element.

    ``fault`` plants one of the faults a training step can have, for the
    tests and the readings of the limits: ``half_batch`` (the second
    half of the rows left out, the mean taken over the rest),
    ``state_unchanged`` (every step returns the state it was given).
    ``sample_index(size)`` gives the places of a flattened leaf at which
    the first gradient is kept element by element (``grad_samples``)."""
    heads, eps, n_layers = (cfg["num_heads"], cfg["layer_norm_epsilon"],
                            cfg["num_layers"])
    lr, b1, b2 = float(hp["learning_rate"]), float(hp["beta1"]), float(hp["beta2"])
    aeps, wd = float(hp["epsilon"]), float(hp["weight_decay"])
    mdt = jnp.dtype(cfg["dtype"]["optimizer_moments"])

    blk = jax.jit(lambda p, x: block(p, x, heads, eps, lowp))

    @jax.jit
    def blk_bwd(p, x, dy):
        _, vjp = jax.vjp(lambda pp, xx: block(pp, xx, heads, eps, lowp), p, x)
        return vjp(dy)

    @jax.jit
    def head_bwd(p, x, labels):
        loss, vjp = jax.vjp(
            lambda pp, xx: head_loss(pp, xx, labels, eps, lowp), p, x)
        dp, dx = vjp(jnp.ones((), F32))
        return loss, dp, dx

    @jax.jit
    def embed_bwd(p, ids, dx):
        _, vjp = jax.vjp(lambda pp: embed(pp, ids), p)
        return vjp(dx)[0]

    @jax.jit
    def adam(p, g, m, v, t):
        p32 = p.astype(F32)
        m32 = b1 * m.astype(F32) + (1 - b1) * g
        v32 = b2 * v.astype(F32) + (1 - b2) * g * g
        upd = lr * (m32 / (1 - b1 ** t)) / (jnp.sqrt(v32 / (1 - b2 ** t)) + aeps)
        new = p32 - upd - lr * wd * p32
        return (_store(new, p), m32.astype(m.dtype), v32.astype(v.dtype),
                jnp.sqrt(jnp.sum(jnp.square(g))))

    start = {k: v for k, v in params.items()}
    mom = {k: (jnp.zeros(v.shape, v.dtype if v.dtype == F32 else mdt),
               jnp.zeros(v.shape, v.dtype if v.dtype == F32 else mdt))
           for k, v in params.items()}
    params = dict(params)
    losses, grad_norms, grad_vectors, grad_samples = [], {}, {}, {}

    def update(name, g, t):
        m, v = mom[name]
        new, m, v, gn = adam(params[name], g, m, v, float(t))
        if fault != "state_unchanged":
            params[name], mom[name] = new, (m, v)
        if t == 1:
            grad_norms[name] = gn
            if sample_index is not None:
                grad_samples[name] = g.reshape(-1)[sample_index(g.size)]
            if g.ndim == 1:
                grad_vectors[name] = jnp.abs(g)

    for t, ids in enumerate(batches, start=1):
        ids = jnp.asarray(ids, jnp.int32)
        if fault == "half_batch":
            ids = ids[: max(1, ids.shape[0] // 2)]
        top = _top(params)
        xs = [embed(top, ids)]
        for i in range(n_layers):
            xs.append(blk(_layer(params, i), xs[-1]))
        loss, dtop, dx = head_bwd(top, xs[-1], ids)
        losses.append(loss)
        for i in reversed(range(n_layers)):
            dp, dx = blk_bwd(_layer(params, i), xs[i], dx)
            xs[i + 1] = None
            for k in LAYER_LEAVES:
                update(f"gpt.h.{i}.{k}", dp[k], t)
        demb = embed_bwd(top, ids, dx)
        for k in TOP_LEAVES:
            update(k, dtop[k] + demb[k], t)

    @jax.jit
    def dnorm(a, b):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))

    delta = {k: dnorm(params[k], start[k]) for k in params}
    vectors = {k: params[k].astype(F32) - start[k].astype(F32)
               for k in params if params[k].ndim == 1}
    return {"losses": [float(x) for x in jax.device_get(losses)],
            "grad_vectors": jax.device_get(grad_vectors),
            "grad_samples": jax.device_get(grad_samples),
            "delta_vectors": jax.device_get(vectors),
            "grad_norms": {k: float(v) for k, v in
                           jax.device_get(grad_norms).items()},
            "delta_norms": {k: float(v) for k, v in
                            jax.device_get(delta).items()}}
