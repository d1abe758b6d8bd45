"""The program's GLM-4 MoE Lite for a configuration, loaded with the
benchmark's weights: the one place that maps the configuration's
(published) keys onto ``paddle_tpu.models.Glm4MoeLiteConfig``.

``build`` makes the model as shapes only and then loads the weights:
the program's own initial values are never made, so the weights stand
on the chip once. ``W_kvb`` is drawn as the two leaves the program
holds (``w_uk`` [H, rank, nope] and ``w_uv`` [H, rank, v], its column
blocks by head: with seeded weights the published layout is a fixed
permutation of them), so the weights' bytes are the file's arithmetic
and no second copy of ``W_kvb`` exists for the absorbed path; the
reference puts ``W_kvb`` together from them.

Weights (the configuration's ``assumed``): matrices N(0, 0.02), the
projections into the residual stream (``wo``, ``wd_down``, ``w_down``,
``ws_down``) scaled by 1/sqrt(2 x layers held), norms 1, no bias; the
router in float32 with a selection bias N(0, 0.01). One jitted call a
distinct (shape, kind), a leaf at a time, so that no float32 temporary
larger than one leaf exists beside the weights.
"""

from __future__ import annotations

import functools
import math

from benchmarks.weights import seed_key

KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
        "first_k_dense_replace", "norm_topk_prob", "routed_scaling_factor",
        "topk_method", "n_group", "topk_group", "hidden_act",
        "attention_bias", "rope_theta", "rope_scaling",
        "partial_rotary_factor", "rms_norm_eps", "max_position_embeddings",
        "tie_word_embeddings", "num_nextn_predict_layers")


def program_config(cfg: dict, **extra):
    from paddle_tpu.models import Glm4MoeLiteConfig
    seg = cfg.get("engine", {}).get("prefill_segment")
    if seg is not None:
        extra.setdefault("prefill_segment", int(seg))
    return Glm4MoeLiteConfig(dtype=cfg["dtype"]["weights"],
                             **{k: cfg[k] for k in KEYS}, **extra)


def leaf_table(cfg: dict) -> list:
    """``(name, shape, kind, std, where)`` of every parameter, by the
    names the program's model gives them; a layer's leaves carry ``{i}``
    and are the dense layers' (``D``), the expert layers' (``E``) or
    every layer's (``*``)."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, rank, rope = (cfg["q_lora_rank"], cfg["kv_lora_rank"],
                      cfg["qk_rope_head_dim"])
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    fd, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, v = cfg["n_routed_experts"], cfg["vocab_size"]
    # 0.02 as the configuration assumes; a rehearsal at toy widths sets
    # a wider one, or attention and routing at those widths are too
    # flat for a planted fault to show
    std = float(cfg.get("initializer_range", 0.02))
    res = std / math.sqrt(2.0 * cfg["num_hidden_layers"])
    lay = "model.layers.{i}."
    return [
        ("model.embed", (v, h), "w", std, "-"),
        (lay + "ln1", (h,), "one", 0.0, "*"),
        (lay + "wq_a", (h, qr), "w", std, "*"),
        (lay + "q_norm", (qr,), "one", 0.0, "*"),
        (lay + "wq_b", (qr, n * (nope + rope)), "w", std, "*"),
        (lay + "wkv_a", (h, rank + rope), "w", std, "*"),
        (lay + "kv_norm", (rank,), "one", 0.0, "*"),
        (lay + "w_uk", (n, rank, nope), "w", std, "*"),
        (lay + "w_uv", (n, rank, vd), "w", std, "*"),
        (lay + "wo", (n * vd, h), "w", res, "*"),
        (lay + "ln2", (h,), "one", 0.0, "*"),
        (lay + "wd_gate", (h, fd), "w", std, "D"),
        (lay + "wd_up", (h, fd), "w", std, "D"),
        (lay + "wd_down", (fd, h), "w", res, "D"),
        (lay + "router", (h, e), "router", std, "E"),
        (lay + "router_bias", (e,), "router", 0.01, "E"),
        (lay + "w_gate", (e, h, f), "w", std, "E"),
        (lay + "w_up", (e, h, f), "w", std, "E"),
        (lay + "w_down", (e, f, h), "w", res, "E"),
        (lay + "ws_gate", (h, f), "w", std, "E"),
        (lay + "ws_up", (h, f), "w", std, "E"),
        (lay + "ws_down", (f, h), "w", res, "E"),
        ("model.norm", (h,), "one", 0.0, "-"),
        ("lm_head", (v, h), "w", std, "-"),
    ]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights ``make_weights`` puts on the chip."""
    import numpy as np
    sizes = {"one": np.dtype(cfg["dtype"]["norms"]).itemsize,
             "router": np.dtype(cfg["dtype"]["router"]).itemsize,
             "w": np.dtype(cfg["dtype"]["weights"]).itemsize}
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    times = {"-": 1, "*": n, "D": min(dense, n), "E": max(n - dense, 0)}
    return sum(math.prod(shape) * sizes[kind] * times[where]
               for _, shape, kind, _, where in leaf_table(cfg))


def make_weights(cfg: dict, seed: int) -> dict:
    """``{name: array}`` for the whole model, on the device."""
    import jax
    import jax.numpy as jnp

    wdt = jnp.dtype(cfg["dtype"]["weights"])
    ndt = jnp.dtype(cfg["dtype"]["norms"])
    rdt = jnp.dtype(cfg["dtype"]["router"])

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def draw(key, shape, std, dt):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    n, dense = int(cfg["num_hidden_layers"]), cfg["first_k_dense_replace"]
    key = seed_key(seed)
    out = {}
    for j, (name, shape, kind, std, where) in enumerate(leaf_table(cfg)):
        layers = (None,) if where == "-" else [
            i for i in range(n) if where == "*"
            or (where == "D") == (i < dense)]
        for i in layers:
            full = name if i is None else name.format(i=i)
            if kind == "one":
                out[full] = jnp.ones(shape, ndt)
                continue
            k = jax.random.fold_in(key, j) if i is None else \
                jax.random.fold_in(jax.random.fold_in(key, j), i)
            out[full] = draw(k, shape, std, rdt if kind == "router" else wdt)
    return out


def build(cfg: dict, seed: int, **extra):
    """The program's model with the benchmark's weights in it."""
    from paddle_tpu.models import Glm4MoeLiteForCausalLM
    model = Glm4MoeLiteForCausalLM(program_config(cfg, **extra),
                                   abstract=True)
    model.load_weights(make_weights(cfg, seed))
    return model
