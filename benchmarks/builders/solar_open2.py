"""The program's Solar Open 2 for a configuration, loaded with the
benchmark's weights: the one place that maps the configuration's
(published) keys onto ``paddle_tpu.models.SolarOpen2Config``.

``build`` makes the model as shapes only and then loads the weights:
the program's own initial values are never made, so the weights stand
on the chip once.

Weights (the configuration's ``assumed``): matrices N(0, 0.02), the
projections into the residual stream (``wo``, ``w_down``, ``ws_down``)
scaled by 1/sqrt(2 x layers held), norms 1, no bias; the router in
float32 with a selection bias N(0, 0.01); a KDA layer's ``a_log`` =
log U(1, 16) and ``dt_bias`` = the inverse softplus of U(1e-3, 0.1)
(the family's: with N(0, 0.02) every channel would decay alike), its
convolution's taps U(-1/2, 1/2) (1/sqrt(taps), a depthwise
convolution's usual start: with 0.02 q, k and v would be silu's linear
part of nearly nothing). One jitted call a distinct (shape, kind), a
leaf at a time, so that no float32 temporary larger than one leaf
exists beside the weights.
"""

from __future__ import annotations

import functools
import math

from benchmarks.weights import seed_key


def held_of(cfg: dict) -> tuple:
    """``(experts the router scores, (first, count) held here)``: in a
    cut, ``n_routed_experts`` counts the experts held and ``published``
    has the router's width."""
    held = cfg.get("experts_held")
    if held is None:
        return cfg["n_routed_experts"], None
    return cfg["published"]["n_routed_experts"], (int(held[0]), int(held[1]))


def program_config(cfg: dict, **extra):
    from paddle_tpu.models import SolarOpen2Config
    n = int(cfg["num_hidden_layers"])
    n_all, held = held_of(cfg)
    keys = ("vocab_size", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "moe_intermediate_size", "n_shared_experts",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "first_k_dense_replace", "use_rope",
            "gqa_interval", "use_gqa_gate", "kda_use_full_proj",
            "kda_allow_neg_eigval", "rms_norm_eps",
            "max_position_embeddings", "tie_word_embeddings")
    seg = cfg.get("engine", {}).get("prefill_segment")
    if seg is not None:
        extra.setdefault("prefill_segment", int(seg))
    return SolarOpen2Config(
        num_hidden_layers=n, n_routed_experts=n_all, experts_held=held,
        # gqa_layers stands in the file as published (12 entries); the
        # layers held are the first ones
        gqa_layers=tuple(i for i in cfg["gqa_layers"] if i < n),
        linear_attn_config=dict(cfg["linear_attn_config"]),
        dtype=cfg["dtype"]["weights"], **{k: cfg[k] for k in keys}, **extra)


def leaf_table(cfg: dict) -> list:
    """``(name, shape, kind, std)`` of every parameter, by the names the
    program's model gives them; a layer's leaves carry ``{i}`` and are
    the softmax layers' (``S``), the KDA layers' (``K``) or every
    layer's (``*``)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lin = cfg["linear_attn_config"]
    n, ld, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    f, v = cfg["moe_intermediate_size"], cfg["vocab_size"]
    e_all, held = held_of(cfg)
    e = e_all if held is None else held[1]
    # 0.02 as the configuration assumes; a rehearsal at toy widths sets
    # a wider one, or attention and routing at those widths are too
    # flat for a planted fault to show
    std = float(cfg.get("initializer_range", 0.02))
    res = std / math.sqrt(2.0 * cfg["num_hidden_layers"])
    lay = "model.layers.{i}."
    return [
        ("model.embed", (v, h), "w", std, "-"),
        (lay + "ln1", (h,), "one", 0.0, "*"),
        (lay + "wq", (h, hq * d), "w", std, "S"),
        (lay + "wk", (h, kv * d), "w", std, "S"),
        (lay + "wv", (h, kv * d), "w", std, "S"),
        (lay + "w_ogate", (h, hq * d), "w", std, "S"),
        (lay + "wo", (hq * d, h), "w", res, "S"),
        (lay + "wqkv", (h, 3 * n * ld), "w", std, "K"),
        (lay + "conv", (taps, 3 * n * ld), "conv", 0.0, "K"),
        (lay + "wf_down", (h, ld), "w", std, "K"),
        (lay + "wf_up", (ld, n * ld), "w", std, "K"),
        (lay + "a_log", (n,), "a_log", 0.0, "K"),
        (lay + "dt_bias", (n * ld,), "dt_bias", 0.0, "K"),
        (lay + "w_beta", (h, n), "w", std, "K"),
        (lay + "wg_down", (h, ld), "w", std, "K"),
        (lay + "wg_up", (ld, n * ld), "w", std, "K"),
        (lay + "o_norm", (ld,), "one", 0.0, "K"),
        (lay + "wo", (n * ld, h), "w", res, "K"),
        (lay + "ln2", (h,), "one", 0.0, "*"),
        (lay + "router", (h, e_all), "router", std, "*"),
        (lay + "router_bias", (e_all,), "router", 0.01, "*"),
        (lay + "w_gate", (e, h, f), "w", std, "*"),
        (lay + "w_up", (e, h, f), "w", std, "*"),
        (lay + "w_down", (e, f, h), "w", res, "*"),
        (lay + "ws_gate", (h, f), "w", std, "*"),
        (lay + "ws_up", (h, f), "w", std, "*"),
        (lay + "ws_down", (f, h), "w", res, "*"),
        ("model.norm", (h,), "one", 0.0, "-"),
        ("lm_head", (v, h), "w", std, "-"),
    ]


def make_weights(cfg: dict, seed: int) -> dict:
    """``{name: array}`` for the whole model, on the device."""
    import jax
    import jax.numpy as jnp

    wdt = jnp.dtype(cfg["dtype"]["weights"])
    ndt = jnp.dtype(cfg["dtype"]["norms"])
    rdt = jnp.dtype(cfg["dtype"]["router"])
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def draw(key, shape, kind, std, dt):
        if kind == "a_log":
            x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
        elif kind == "dt_bias":
            t = jax.random.uniform(key, shape, f32, 1e-3, 0.1)
            x = t + jnp.log(-jnp.expm1(-t))
        elif kind == "conv":
            bound = 1.0 / math.sqrt(shape[0])
            x = jax.random.uniform(key, shape, f32, -bound, bound)
        else:
            x = jax.random.normal(key, shape, f32) * std
        return x.astype(dt)

    n = int(cfg["num_hidden_layers"])
    softmax = {i for i in cfg["gqa_layers"] if i < n}
    key = seed_key(seed)
    out = {}
    for j, (name, shape, kind, std, where) in enumerate(leaf_table(cfg)):
        layers = (None,) if where == "-" else [
            i for i in range(n) if where == "*"
            or (where == "S") == (i in softmax)]
        for i in layers:
            full = name if i is None else name.format(i=i)
            if kind == "one":
                out[full] = jnp.ones(shape, ndt)
                continue
            k = jax.random.fold_in(key, j) if i is None else \
                jax.random.fold_in(jax.random.fold_in(key, j), i)
            dt = rdt if kind in ("router", "a_log", "dt_bias") else wdt
            out[full] = draw(k, shape, kind, std, dt)
    return out


def build(cfg: dict, seed: int, **extra):
    """The program's model with the benchmark's weights in it."""
    from paddle_tpu.models import SolarOpen2ForCausalLM
    model = SolarOpen2ForCausalLM(program_config(cfg, **extra),
                                  abstract=True)
    model.load_weights(make_weights(cfg, seed))
    return model
