"""The program's Falcon-H1 for a configuration, loaded with the
benchmark's weights: the one place that maps the configuration's
(published) keys onto ``paddle_tpu.models.FalconH1Config``.

``build`` makes the model as shapes only and then loads the weights:
the program's own initial values are never made, so the weights stand
on the chip once.

Weights (the configuration's ``assumed``): a matrix N(0, 1/sqrt(fan_in))
divided by the constant multipliers that scale its output on the way to
the residual (so that each branch adds at unit scale and dropping any
multiplier shows): ``wq``, ``wv`` and ``w_up`` 1/sqrt(hidden), ``wk``
1/(m_key sqrt(hidden)), ``wo`` 1/(m_attn_out sqrt(heads x d)), ``w_in``
1/(m_ssm_in sqrt(hidden)) (its five slice multipliers left to act),
``w_out`` 1/(m_ssm_out sqrt(d_ssm)), ``w_gate`` 1/(m_gate sqrt(hidden)),
``w_down`` 1/(m_down sqrt(intermediate)); the embedding N(0,
1/m_embed); the head N(0, 1/sqrt(hidden)) under its 1/128; norms 1;
Mamba-2's own ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus
of U(1e-3, 0.1), ``D = 1``, the convolution's taps and bias U(-1/2,
1/2) (1/sqrt(taps)). One jitted call a distinct (shape, kind), a leaf
at a time, so that no float32 temporary larger than one leaf exists
beside the weights.
"""

from __future__ import annotations

import functools
import math

from benchmarks.weights import seed_key

KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "hidden_act", "attention_bias", "attention_in_multiplier",
        "attention_out_multiplier", "attn_layer_indices",
        "embedding_multiplier", "key_multiplier", "lm_head_multiplier",
        "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv",
        "mamba_d_head", "mamba_d_ssm", "mamba_d_state", "mamba_expand",
        "mamba_n_groups", "mamba_n_heads", "mamba_norm_before_gate",
        "mamba_proj_bias", "mamba_rms_norm", "mamba_use_mlp",
        "max_position_embeddings", "mlp_bias", "mlp_expansion_factor",
        "mlp_multipliers", "projectors_bias", "rms_norm_eps",
        "rope_scaling", "rope_theta", "ssm_in_multiplier",
        "ssm_multipliers", "ssm_out_multiplier", "tie_word_embeddings")


def program_config(cfg: dict, **extra):
    from paddle_tpu.models import FalconH1Config
    seg = cfg.get("engine", {}).get("prefill_segment")
    if seg is not None:
        extra.setdefault("prefill_segment", int(seg))
    return FalconH1Config(num_hidden_layers=int(cfg["num_hidden_layers"]),
                          dtype=cfg["dtype"]["weights"],
                          **{k: cfg[k] for k in KEYS}, **extra)


def leaf_table(cfg: dict) -> list:
    """``(name, shape, kind, std)`` of every parameter, by the names the
    program's model gives them; a block's leaves carry ``{i}``."""
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, ns, g = cfg["mamba_n_heads"], cfg["mamba_d_state"], \
        cfg["mamba_n_groups"]
    d_ssm, taps, v = cfg["mamba_d_ssm"], cfg["mamba_d_conv"], \
        cfg["vocab_size"]
    conv = d_ssm + 2 * g * ns
    m_gate, m_down = cfg["mlp_multipliers"]
    m_in = cfg["attention_in_multiplier"]
    lay = "model.layers.{i}."
    return [
        ("model.embed", (v, h), "w", 1.0 / cfg["embedding_multiplier"]),
        (lay + "ln_in", (h,), "one", 0.0),
        (lay + "wq", (h, hq * d), "w", 1.0 / (m_in * math.sqrt(h))),
        (lay + "wk", (h, kv * d), "w",
         1.0 / (m_in * cfg["key_multiplier"] * math.sqrt(h))),
        (lay + "wv", (h, kv * d), "w", 1.0 / (m_in * math.sqrt(h))),
        (lay + "wo", (hq * d, h), "w",
         1.0 / (cfg["attention_out_multiplier"] * math.sqrt(hq * d))),
        (lay + "w_in", (h, 2 * d_ssm + 2 * g * ns + n), "w",
         1.0 / (cfg["ssm_in_multiplier"] * math.sqrt(h))),
        (lay + "conv", (taps, conv), "conv", 0.0),
        (lay + "conv_bias", (conv,), "conv_bias", 0.0),
        (lay + "dt_bias", (n,), "dt_bias", 0.0),
        (lay + "a_log", (n,), "a_log", 0.0),
        (lay + "d_skip", (n,), "d_skip", 0.0),
        (lay + "ssm_norm", (d_ssm,), "one", 0.0),
        (lay + "w_out", (d_ssm, h), "w",
         1.0 / (cfg["ssm_out_multiplier"] * math.sqrt(d_ssm))),
        (lay + "ln_ff", (h,), "one", 0.0),
        (lay + "w_gate", (h, f), "w", 1.0 / (m_gate * math.sqrt(h))),
        (lay + "w_up", (h, f), "w", 1.0 / math.sqrt(h)),
        (lay + "w_down", (f, h), "w", 1.0 / (m_down * math.sqrt(f))),
        ("model.norm", (h,), "one", 0.0),
        ("lm_head", (v, h), "w", 1.0 / math.sqrt(h)),
    ]


def make_weights(cfg: dict, seed: int) -> dict:
    """``{name: array}`` for the whole model, on the device."""
    import jax
    import jax.numpy as jnp

    wdt = jnp.dtype(cfg["dtype"]["weights"])
    ndt = jnp.dtype(cfg["dtype"]["norms"])
    f32 = jnp.float32
    taps = cfg["mamba_d_conv"]

    @functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def draw(key, shape, kind, std, dt):
        if kind == "a_log":
            x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
        elif kind == "dt_bias":
            t = jax.random.uniform(key, shape, f32, 1e-3, 0.1)
            x = t + jnp.log(-jnp.expm1(-t))
        elif kind == "d_skip":
            x = jnp.ones(shape, f32)
        elif kind in ("conv", "conv_bias"):
            bound = 1.0 / math.sqrt(taps)
            x = jax.random.uniform(key, shape, f32, -bound, bound)
        else:
            x = jax.random.normal(key, shape, f32) * std
        return x.astype(dt)

    n = int(cfg["num_hidden_layers"])
    key = seed_key(seed)
    out = {}
    for j, (name, shape, kind, std) in enumerate(leaf_table(cfg)):
        for i in ((None,) if "{i}" not in name else range(n)):
            full = name if i is None else name.format(i=i)
            if kind == "one":
                out[full] = jnp.ones(shape, ndt)
                continue
            k = jax.random.fold_in(key, j) if i is None else \
                jax.random.fold_in(jax.random.fold_in(key, j), i)
            dt = f32 if kind in ("a_log", "dt_bias", "d_skip") else wdt
            out[full] = draw(k, shape, kind, std, dt)
    return out


def build(cfg: dict, seed: int, **extra):
    """The program's model with the benchmark's weights in it."""
    from paddle_tpu.models import FalconH1ForCausalLM
    model = FalconH1ForCausalLM(program_config(cfg, **extra), abstract=True)
    model.load_weights(make_weights(cfg, seed))
    return model
