"""The program's GLM-5.3-Flash for a configuration, loaded with the
benchmark's weights: the one place that maps the configuration's
(published) keys onto ``paddle_tpu.models.Glm5NextConfig``.

``build`` makes the model as shapes only and then loads the weights:
the program's own initial values are never made, so the weights stand
on the chip once. ``weight_bytes`` is what they take there.

Weights (the configuration's ``assumed``): matrices N(0, 0.02), the
projections into the residual stream (``wo``, ``wd_down``, ``w_down``,
``ws_down``) scaled by 1/sqrt(2 x layers held), norms 1, the indexer
key norm's bias 0; ``W_qb`` and ``W_UK`` N(0, ``attn_init``) (0.05:
attention logits of about 2.2 standard deviations, so that which
positions a query attends to moves its output); the router in float32
with a selection bias N(0, 0.01); a KDA layer's ``a_log`` = log U(1,
16), ``dt_bias`` the inverse softplus of U(1e-3, 0.1) and its
convolution's taps U(-1/2, 1/2) (Solar Open 2's, benchmarks/builders/
solar_open2.py); the mHC mappings' ``Phi`` N(0, 0.02) in float32, their
scales ``alpha`` 0.1 and biases N(0, ``hc_bias_init``) (1: every ``H``
far from its trivial value, the input-dependent part about a quarter of
it). One jitted call a distinct (shape, kind), a leaf at a time, so
that no float32 temporary larger than one leaf exists beside the
weights.
"""

from __future__ import annotations

import functools
import math

from benchmarks.weights import seed_key

KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "qk_head_dim", "v_head_dim", "mla_use_nope",
        "index_n_heads", "index_head_dim", "index_topk", "index_kpool",
        "index_kpool_compress", "index_kpool_always_select_tail",
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc",
        "intermediate_size", "moe_intermediate_size", "n_shared_experts",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "scoring_func", "topk_method", "n_group", "topk_group",
        "first_k_dense_replace", "swiglu_limit", "hidden_act",
        "attention_bias", "rms_norm_eps", "max_position_embeddings",
        "tie_word_embeddings", "num_nextn_predict_layers", "vocab_size")


def held_of(cfg: dict) -> tuple:
    """``(experts the router scores, (first, count) held here)``: in a
    cut, ``n_routed_experts`` counts the experts held and ``published``
    has the router's width."""
    held = cfg.get("experts_held")
    if held is None:
        return cfg["n_routed_experts"], None
    return cfg["published"]["n_routed_experts"], (int(held[0]), int(held[1]))


def layer_ids(cfg: dict) -> list:
    return list(cfg.get("layer_ids") or range(cfg["num_hidden_layers"]))


def program_config(cfg: dict, **extra):
    from paddle_tpu.models import Glm5NextConfig
    n_all, held = held_of(cfg)
    seg = cfg.get("engine", {}).get("prefill_segment")
    if seg is not None:
        extra.setdefault("prefill_segment", int(seg))
    return Glm5NextConfig(
        num_hidden_layers=len(layer_ids(cfg)),
        layer_ids=tuple(layer_ids(cfg)), n_routed_experts=n_all,
        experts_held=held, layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        linear_attn_config=dict(cfg["linear_attn_config"]),
        dtype=cfg["dtype"]["weights"], **{k: cfg[k] for k in KEYS}, **extra)


def kinds(cfg: dict) -> list:
    """``(mixer, feed-forward)`` of each layer held: ``K`` or ``S``
    (KDA, sparse attention), ``D`` or ``E`` (dense, experts)."""
    return [("S" if cfg["layer_types"][i] == "deepseek_sparse_attention"
             else "K", "D" if cfg["mlp_layer_types"][i] == "dense" else "E")
            for i in layer_ids(cfg)]


def leaf_table(cfg: dict) -> list:
    """``(name, shape, kind, std, where)`` of every parameter, by the
    names the program's model gives them; a layer's leaves carry ``{i}``
    and are the KDA layers' (``K``), the sparse attention layers'
    (``S``), the dense feed-forward's (``D``), the expert layers' (``E``)
    or every layer's (``*``)."""
    h, n = cfg["hidden_size"], cfg["hc_mult"]
    lin = cfg["linear_attn_config"]
    k, d, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    qr, nope, vd = cfg["q_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["v_head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    fd, f, v = cfg["intermediate_size"], cfg["moe_intermediate_size"], \
        cfg["vocab_size"]
    e_all, held = held_of(cfg)
    e = e_all if held is None else held[1]
    std = float(cfg.get("initializer_range", 0.02))
    attn = float(cfg.get("attn_init") or std)
    hb = float(cfg.get("hc_bias_init", 1.0))
    res = std / math.sqrt(2.0 * len(layer_ids(cfg)))
    lay = "model.layers.{i}."
    hc = []
    for s in ("a", "f"):
        hc += [(lay + f"hc_norm_{s}", (n * h,), "one", 0.0, "*"),
               (lay + f"hc_phi_{s}", (n * h, n * (n + 2)), "f32", std, "*"),
               (lay + f"hc_alpha_{s}", (3,), "alpha", 0.1, "*"),
               (lay + f"hc_bias_{s}", (n * (n + 2),), "f32", hb, "*")]
    return [("model.embed", (v, h), "w", std, "-")] + hc + [
        (lay + "ln1", (h,), "one", 0.0, "*"),
        (lay + "wqkv", (h, 3 * k * d), "w", std, "K"),
        (lay + "conv", (taps, 3 * k * d), "conv", 0.0, "K"),
        (lay + "wf_down", (h, d), "w", std, "K"),
        (lay + "wf_up", (d, k * d), "w", std, "K"),
        (lay + "a_log", (k,), "a_log", 0.0, "K"),
        (lay + "dt_bias", (k * d,), "dt_bias", 0.0, "K"),
        (lay + "w_beta", (h, k), "w", std, "K"),
        (lay + "wg_down", (h, d), "w", std, "K"),
        (lay + "wg_up", (d, k * d), "w", std, "K"),
        (lay + "o_norm", (d,), "one", 0.0, "K"),
        (lay + "wo", (k * d, h), "w", res, "K"),
        (lay + "wq_a", (h, qr), "w", std, "S"),
        (lay + "q_norm", (qr,), "one", 0.0, "S"),
        (lay + "wq_b", (qr, heads * nope), "w", attn, "S"),
        (lay + "wkv_a", (h, rank), "w", std, "S"),
        (lay + "kv_norm", (rank,), "one", 0.0, "S"),
        (lay + "w_uk", (heads, rank, nope), "w", attn, "S"),
        (lay + "w_uv", (heads, rank, vd), "w", std, "S"),
        (lay + "wo", (heads * vd, h), "w", res, "S"),
        (lay + "wi_q", (qr, hi * di), "w", std, "S"),
        (lay + "wi_k", (h, di), "w", std, "S"),
        (lay + "ki_norm", (di,), "one", 0.0, "S"),
        (lay + "ki_bias", (di,), "zero", 0.0, "S"),
        (lay + "wi_w", (h, hi), "w", std, "S"),
        (lay + "ln2", (h,), "one", 0.0, "*"),
        (lay + "wd_gate", (h, fd), "w", std, "D"),
        (lay + "wd_up", (h, fd), "w", std, "D"),
        (lay + "wd_down", (fd, h), "w", res, "D"),
        (lay + "router", (h, e_all), "f32", std, "E"),
        (lay + "router_bias", (e_all,), "f32", 0.01, "E"),
        (lay + "w_gate", (e, h, f), "w", std, "E"),
        (lay + "w_up", (e, h, f), "w", std, "E"),
        (lay + "w_down", (e, f, h), "w", res, "E"),
        (lay + "ws_gate", (h, f), "w", std, "E"),
        (lay + "ws_up", (h, f), "w", std, "E"),
        (lay + "ws_down", (f, h), "w", res, "E"),
        ("model.norm", (h,), "one", 0.0, "-"),
        ("lm_head", (v, h), "w", std, "-"),
    ]


F32_KINDS = ("one", "zero", "f32", "alpha", "a_log", "dt_bias")


def _leaves(cfg: dict):
    """``(full name, shape, kind, std, table index, layer)`` of every
    leaf of the model."""
    ks = kinds(cfg)
    for j, (name, shape, kind, std, where) in enumerate(leaf_table(cfg)):
        layers = (None,) if where == "-" else [
            i for i, (mix, ffn) in enumerate(ks)
            if where in ("*", mix, ffn)]
        for i in layers:
            yield (name if i is None else name.format(i=i)), shape, kind, \
                std, j, i


def weight_bytes(cfg: dict) -> int:
    """Bytes the weights take on the chip, in the types the
    configuration states."""
    import jax.numpy as jnp
    import numpy as np
    wsize = jnp.dtype(cfg["dtype"]["weights"]).itemsize
    return sum(int(np.prod(shape)) * (4 if kind in F32_KINDS else wsize)
               for _, shape, kind, _, _, _ in _leaves(cfg))


def make_weights(cfg: dict, seed: int) -> dict:
    """``{name: array}`` for the whole model, on the device."""
    import jax
    import jax.numpy as jnp

    wdt = jnp.dtype(cfg["dtype"]["weights"])
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

    key = seed_key(seed)
    out = {}
    for full, shape, kind, std, j, i in _leaves(cfg):
        if kind in ("one", "zero", "alpha"):
            val = {"one": 1.0, "zero": 0.0, "alpha": std}[kind]
            out[full] = jnp.full(shape, val, f32)
            continue
        k = jax.random.fold_in(key, j) if i is None else \
            jax.random.fold_in(jax.random.fold_in(key, j), i)
        out[full] = draw(k, shape, kind, std,
                         f32 if kind in F32_KINDS else wdt)
    return out


def build(cfg: dict, seed: int, **extra):
    """The program's model with the benchmark's weights in it."""
    from paddle_tpu.models import Glm5NextForCausalLM
    model = Glm5NextForCausalLM(program_config(cfg, **extra), abstract=True)
    model.load_weights(make_weights(cfg, seed))
    return model
