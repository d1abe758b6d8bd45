"""Operations and bytes a Solar Open 2 configuration needs, from shapes
alone (the configuration's published keys).

The same work whatever implements it. A matmul of [m,k]x[k,n] is 2mkn.
A token goes through its layer's mixer (the softmax layer's q, k, v,
gate and o; a KDA layer's q, k, v, the two low-rank projections, beta
and o), the router over ALL experts, the shared expert and the experts
it picked THAT ARE HELD HERE: with ``experts_held`` a chip computes
``count / all`` of the picks (what uniform routing sends it; the
program's own count of a step is its ``moe`` counters). Softmax
attention is counted over the keys a query sees (causal, no window).
The recurrence of a KDA layer is ``6 d_k d_v`` a head a token: the
decayed state against the key, the outer product into it, the state
against the query, two operations an element each. The embedding gather
is no FLOPs; the untied head counts at the positions that are sampled.
Norms, softmax, the convolution's four taps, silu and the gates'
sigmoids are left out.
"""

from __future__ import annotations


def layer_kinds(cfg: dict) -> list:
    """``"softmax"`` or ``"kda"`` for each layer held."""
    n = cfg["num_hidden_layers"]
    soft = {i for i in cfg["gqa_layers"] if i < n}
    return ["softmax" if i in soft else "kda" for i in range(n)]


def kda_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("kda")


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_width(cfg: dict) -> int:
    return (cfg["published"]["n_routed_experts"]
            if cfg.get("experts_held") else cfg["n_routed_experts"])


def picks_held_per_token(cfg: dict) -> float:
    """Of a token's picks, those that land on experts held here."""
    held = cfg.get("experts_held")
    share = 1.0 if not held else held[1] / router_width(cfg)
    return cfg["num_experts_per_tok"] * share


def mixer_params(cfg: dict, kind: str) -> int:
    h = cfg["hidden_size"]
    if kind == "softmax":
        hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        return 3 * h * hq * d + 2 * h * kv * d  # q, gate, o; k, v
    lin = cfg["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    low = 2 * (h * d + d * n * d)  # decay and gate, down and up
    return 4 * h * n * d + low + h * n  # q, k, v, o; beta


def moe_params_per_token(cfg: dict) -> float:
    """Router, shared expert and the picks held here."""
    return cfg["hidden_size"] * router_width(cfg) + \
        (cfg["n_shared_experts"] + picks_held_per_token(cfg)) \
        * expert_params(cfg)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token multiplies, all layers held."""
    return sum(mixer_params(cfg, k) + moe_params_per_token(cfg)
               for k in layer_kinds(cfg))


def keys_seen_sum(start: int, n: int) -> int:
    """Keys seen by ``n`` consecutive positions from ``start``."""
    return n * start + n * (n + 1) // 2


def attention_flops(cfg: dict, start: int, n: int) -> float:
    """QK^T and PV of ``n`` positions from ``start``, the softmax
    layers held."""
    per_key = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_key * keys_seen_sum(start, n) \
        * layer_kinds(cfg).count("softmax")


def recurrence_flops(cfg: dict, tokens: int) -> float:
    """The delta rule's state update and read-out, the KDA layers
    held: ``6 d_k d_v`` a head a token."""
    lin = cfg["linear_attn_config"]
    return 6.0 * lin["head_dim"] ** 2 * lin["num_heads"] * tokens \
        * kda_layers(cfg)


def forward_flops(cfg: dict, start: int, n: int, head_tokens: int) -> float:
    dense = 2.0 * matmul_params_per_token(cfg) * n
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    return dense + attention_flops(cfg, start, n) \
        + recurrence_flops(cfg, n) + head


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return forward_flops(cfg, 0, prompt_len, head_tokens=1)


def decode_flops(cfg: dict, context_lens) -> float:
    """One token for each sequence; ``context`` counts the token."""
    return sum(forward_flops(cfg, int(c) - 1, 1, head_tokens=1)
               for c in context_lens)


def expert_bytes(cfg: dict, touched: int, itemsize: int = 2) -> float:
    """Weight bytes of ``touched`` routed experts (distinct experts hit,
    summed over layers and steps): what a step that is bound by the
    experts' weights must read."""
    return float(touched) * expert_params(cfg) * itemsize


def state_bytes(cfg: dict) -> int:
    """One sequence's state in one KDA layer, float32."""
    lin = cfg["linear_attn_config"]
    return 4 * lin["num_heads"] * lin["head_dim"] ** 2


def kda_decode_bytes(cfg: dict, slot_steps: int) -> float:
    """The states a decode step must read and write: ``slot_steps``
    live sequences (summed over steps), every KDA layer held."""
    return 2.0 * state_bytes(cfg) * kda_layers(cfg) * slot_steps


def kda_prefill_flops(cfg: dict, prompt_len: int) -> float:
    return recurrence_flops(cfg, prompt_len)


def kda_prefill_bytes(cfg: dict, prompt_len: int, itemsize: int = 2) -> float:
    """q, k, v read and o written in the activations' type, the
    log-decay (float32, a key channel) and beta (float32, a head) read,
    at the prompt's true length, and the final state written once: the
    KDA layers held."""
    lin = cfg["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    per_token = 4 * n * d * itemsize + 4 * n * d + 4 * n
    return float(per_token * prompt_len + state_bytes(cfg)) * kda_layers(cfg)
