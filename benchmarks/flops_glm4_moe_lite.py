"""Operations and bytes a GLM-4 MoE Lite configuration needs, from
shapes alone (the configuration's published keys).

The same work whatever implements it. A matmul of [m,k]x[k,n] is 2mkn.
A token goes through its layer's latent-attention mixer and its
feed-forward layer: the dense one in the first ``first_k_dense_replace``
layers, else the router over all experts, the shared expert and the
``num_experts_per_tok`` experts it picked. The mixer is counted in the
form with fewer operations for the token: EXPANDED for a prompt's token
(q_a, q_b, kv_a, the latent's expansion kv_b, o; ``2 (qk + v)`` a head a
causal key), ABSORBED for a decoded token (q_a, q_b, kv_a, the query
through ``W_UK``, the output through ``W_UV``, o; ``2 ((rank + rope) +
rank)`` a head a key of its context; never the expansion of the
context). The two forms multiply a token by the same parameters: the
absorbed one applies kv_b's two blocks to the token's query and output
where the expanded one applies them to its latent. The embedding gather
is no FLOPs; the untied head counts at the positions that are sampled.
Norms, softmax, rotary, silu and the gates' sigmoids are left out.
"""

from __future__ import annotations


def heads(cfg: dict) -> int:
    return cfg["num_attention_heads"]


def qk_dim(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def latent_row(cfg: dict) -> int:
    """Values a position leaves in a layer's cache: the latent and the
    shared rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mixer_params(cfg: dict) -> int:
    """q_a, q_b, kv_a, kv_b (as ``W_UK`` and ``W_UV``, on the latent or
    on the query and the output) and o."""
    h, n = cfg["hidden_size"], heads(cfg)
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * n * qk_dim(cfg)
            + h * latent_row(cfg)
            + cfg["kv_lora_rank"] * n
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + n * cfg["v_head_dim"] * h)


def dense_layers(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_params_per_token(cfg: dict) -> int:
    """Router, shared expert and the token's picks."""
    return cfg["hidden_size"] * cfg["n_routed_experts"] + \
        (cfg["n_shared_experts"] + cfg["num_experts_per_tok"]) \
        * expert_params(cfg)


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters a token multiplies, all layers held."""
    return cfg["num_hidden_layers"] * mixer_params(cfg) \
        + dense_layers(cfg) * dense_ffn_params(cfg) \
        + expert_layers(cfg) * moe_params_per_token(cfg)


def keys_seen_sum(start: int, n: int) -> int:
    """Keys seen by ``n`` consecutive positions from ``start``."""
    return n * start + n * (n + 1) // 2


def expanded_flops_per_key(cfg: dict) -> float:
    """QK^T and PV of one query against one key, all heads, a layer."""
    return 2.0 * heads(cfg) * (qk_dim(cfg) + cfg["v_head_dim"])


def absorbed_flops_per_key(cfg: dict) -> float:
    """The absorbed scores and the sum over latents of one query
    against one cached row, all heads, a layer."""
    return 2.0 * heads(cfg) * (latent_row(cfg) + cfg["kv_lora_rank"])


def flash_flops(cfg: dict, prompt_len: int) -> float:
    """A prompt's causal attention in the expanded form, every layer."""
    return expanded_flops_per_key(cfg) * keys_seen_sum(0, prompt_len) \
        * cfg["num_hidden_layers"]


def head_flops(cfg: dict, tokens: int) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * tokens


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return 2.0 * matmul_params_per_token(cfg) * prompt_len \
        + flash_flops(cfg, prompt_len) + head_flops(cfg, 1)


def decode_flops(cfg: dict, context_lens) -> float:
    """One token for each sequence; ``context`` counts the token."""
    lens = [int(c) for c in context_lens]
    return len(lens) * (2.0 * matmul_params_per_token(cfg)
                        + head_flops(cfg, 1)) \
        + absorbed_flops_per_key(cfg) * sum(lens) * cfg["num_hidden_layers"]


def expert_bytes(cfg: dict, touched: int, itemsize: int = 2) -> float:
    """Weight bytes of ``touched`` routed experts (distinct experts hit,
    summed over layers and steps): what a step that is bound by the
    experts' weights must read."""
    return float(touched) * expert_params(cfg) * itemsize


def latent_decode_bytes(cfg: dict, context_lens, itemsize: int = 2) -> float:
    """The cached rows a decoded token must read: its context, every
    layer, ``rank + rope`` values a row whatever the pool pads a row
    to."""
    return float(sum(int(c) for c in context_lens)) * latent_row(cfg) \
        * itemsize * cfg["num_hidden_layers"]
