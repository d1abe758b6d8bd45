"""The program's SmallThinker for a configuration, loaded with the
benchmark's weights: the one place that maps the configuration's
(published) keys onto ``paddle_tpu.models.SmallThinkerConfig``.

``build`` makes the model as shapes only and then loads the weights:
the program's own initial values are never made, so the 11 GB of a
12-layer cut stand on the chip once.

Weights (the configuration's ``assumed``): matrices N(0, 0.02), the two
projections into the residual stream (``wo``, ``w_down``) scaled by
1/sqrt(2 x layers held), norms 1, no bias; the router in float32. One
jitted call a distinct (shape, type), a leaf at a time, so that no
float32 temporary larger than one leaf exists beside the weights.
"""

from __future__ import annotations

import functools
import math

from benchmarks.weights import seed_key


def program_config(cfg: dict, **extra):
    from paddle_tpu.models import SmallThinkerConfig
    n = int(cfg["num_hidden_layers"])
    held = cfg.get("experts_held")
    return SmallThinkerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=n,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        moe_ffn_hidden_size=cfg["moe_ffn_hidden_size"],
        moe_num_primary_experts=cfg["moe_num_primary_experts"],
        moe_num_active_primary_experts=cfg[
            "moe_num_active_primary_experts"],
        moe_primary_router_apply_softmax=cfg[
            "moe_primary_router_apply_softmax"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        # the layouts stand in the file as published (52 entries); the
        # layers held are the first ones
        rope_layout=tuple(cfg["rope_layout"][:n]),
        sliding_window_layout=tuple(cfg["sliding_window_layout"][:n]),
        sliding_window_size=cfg["sliding_window_size"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        experts_held=None if held is None else tuple(held),
        dtype=cfg["dtype"]["weights"], **extra)


def leaf_table(cfg: dict) -> list:
    """``(name, shape, kind, std)`` of every parameter, by the names the
    program's model gives them; per-layer leaves carry ``{i}``."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, v = cfg["moe_ffn_hidden_size"], cfg["vocab_size"]
    e_all = cfg["moe_num_primary_experts"]
    e = (cfg.get("experts_held") or (0, e_all))[1]
    # 0.02 as the configuration assumes; a rehearsal at toy widths sets
    # a wider one, or attention and routing at those widths are too
    # flat for a planted fault to show
    std = float(cfg.get("initializer_range", 0.02))
    res = std / math.sqrt(2.0 * cfg["num_hidden_layers"])
    lay = "model.layers.{i}."
    return [
        ("model.embed", (v, h), "w", std),
        (lay + "ln1", (h,), "one", 0.0),
        (lay + "wq", (h, hq * d), "w", std),
        (lay + "wk", (h, kv * d), "w", std),
        (lay + "wv", (h, kv * d), "w", std),
        (lay + "wo", (hq * d, h), "w", res),
        (lay + "ln2", (h,), "one", 0.0),
        (lay + "router", (h, e_all), "router", std),
        (lay + "w_gate", (e, h, f), "w", std),
        (lay + "w_up", (e, h, f), "w", std),
        (lay + "w_down", (e, f, h), "w", res),
        ("model.norm", (h,), "one", 0.0),
        ("lm_head", (v, h), "w", std),
    ]


def make_weights(cfg: dict, seed: int) -> dict:
    """``{name: array}`` for the whole model, on the device."""
    import jax
    import jax.numpy as jnp

    wdt = jnp.dtype(cfg["dtype"]["weights"])
    ndt = jnp.dtype(cfg["dtype"]["norms"])
    rdt = jnp.dtype(cfg["dtype"]["router"])

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, std, dt):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    key = seed_key(seed)
    out = {}
    for j, (name, shape, kind, std) in enumerate(leaf_table(cfg)):
        layers = range(cfg["num_hidden_layers"]) if "{i}" in name else (None,)
        for i in layers:
            full = name if i is None else name.format(i=i)
            if kind == "one":
                out[full] = jnp.ones(shape, ndt)
                continue
            k = jax.random.fold_in(key, j) if i is None else \
                jax.random.fold_in(jax.random.fold_in(key, j), i)
            out[full] = normal(k, shape, std,
                               rdt if kind == "router" else wdt)
    return out


def build(cfg: dict, seed: int, **extra):
    """The program's model with the benchmark's weights in it."""
    from paddle_tpu.models import SmallThinkerForCausalLM
    model = SmallThinkerForCausalLM(program_config(cfg, **extra),
                                    abstract=True)
    model.load_weights(make_weights(cfg, seed))
    return model
