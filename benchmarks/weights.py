"""Weights from the seed: made by the benchmark, on the device, in one
jitted call, in the types the configuration states. The program's model
is loaded with them and the plain reference starts from the same call;
neither takes anything the other has made.

GPT-2's initialisation: matrices N(0, 0.02), the two projections that
write into the residual stream scaled by 1/sqrt(2L), positions N(0,
0.01), norms 1 and 0, biases N(0, 0.01) so that no bias gradient is
trivially zero.
"""

from __future__ import annotations

import math


def leaf_table(cfg: dict) -> list:
    """``(name, shape, kind, std)`` of every parameter, by the names
    the program's GPT gives them; per-layer leaves carry ``{i}``."""
    h, f, v = cfg["hidden_size"], cfg["ffn_hidden_size"], cfg["vocab_size"]
    s, n = cfg["max_seq_len"], cfg["num_layers"]
    proj = 0.02 / math.sqrt(2.0 * n)
    return [
        ("gpt.wte.weight", (v, h), "w", 0.02),
        ("gpt.wpe.weight", (s, h), "w", 0.01),
        ("gpt.h.{i}.ln_1.weight", (h,), "one", 0.0),
        ("gpt.h.{i}.ln_1.bias", (h,), "norm_b", 0.0),
        ("gpt.h.{i}.attn.qkv_proj.weight", (h, 3 * h), "w", 0.02),
        ("gpt.h.{i}.attn.qkv_proj.bias", (3 * h,), "w", 0.01),
        ("gpt.h.{i}.attn.out_proj.weight", (h, h), "w", proj),
        ("gpt.h.{i}.attn.out_proj.bias", (h,), "w", 0.01),
        ("gpt.h.{i}.ln_2.weight", (h,), "one", 0.0),
        ("gpt.h.{i}.ln_2.bias", (h,), "norm_b", 0.0),
        ("gpt.h.{i}.mlp.fc_in.weight", (h, f), "w", 0.02),
        ("gpt.h.{i}.mlp.fc_in.bias", (f,), "w", 0.01),
        ("gpt.h.{i}.mlp.fc_out.weight", (f, h), "w", proj),
        ("gpt.h.{i}.mlp.fc_out.bias", (h,), "w", 0.01),
        ("gpt.ln_f.weight", (h,), "one", 0.0),
        ("gpt.ln_f.bias", (h,), "norm_b", 0.0),
    ]


def seed_key(seed: int):
    """A key for any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(cfg: dict, seed: int) -> dict:
    """``{name: array}`` for the whole model, one jitted call."""
    import jax
    import jax.numpy as jnp

    wdt = jnp.dtype(cfg["dtype"]["weights"])
    ndt = jnp.dtype(cfg["dtype"]["norms"])
    n = cfg["num_layers"]
    table = leaf_table(cfg)

    def build(key):
        out = {}
        for j, (name, shape, kind, std) in enumerate(table):
            per_layer = "{i}" in name
            full = ((n,) + shape) if per_layer else shape
            is_norm = kind in ("one", "norm_b")
            dt = ndt if is_norm else wdt
            if kind == "one":
                val = jnp.ones(full, dt)
            elif kind == "norm_b":
                val = jnp.zeros(full, dt)
            else:
                val = (jax.random.normal(jax.random.fold_in(key, j), full,
                                         jnp.float32) * std).astype(dt)
            if per_layer:
                for i in range(n):
                    out[name.format(i=i)] = val[i]
            else:
                out[name] = val
        return out

    return jax.jit(build)(seed_key(seed))


def load_into(model, weights: dict) -> None:
    """Put the benchmark's weights into the program's model; the names
    and shapes must be the program's own, leaf for leaf."""
    named = dict(model.named_parameters())
    if set(named) != set(weights):
        raise RuntimeError(
            f"the model's parameters and the configuration's differ: "
            f"{sorted(set(named) ^ set(weights))[:6]}")
    for name, p in named.items():
        if tuple(p.shape) != tuple(weights[name].shape):
            raise RuntimeError(f"{name}: model {tuple(p.shape)} "
                               f"configuration {weights[name].shape}")
        p.value = weights[name]
