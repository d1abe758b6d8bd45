"""Operations and bytes a Falcon-H1 configuration needs, from shapes
alone (the configuration's published keys).

The same work whatever implements it. A matmul of [m,k]x[k,n] is 2mkn.
A token goes through every block held: attention's q, k, v and o, the
SSM's in- and out-projections, the MLP's gate, up and down. Softmax
attention is counted over the keys a query sees (causal, no window). The
recurrence of the SSM is ``4 N P`` a head a token: ``dt B x^T`` into the
state and the state against C, two operations an element each (the
decay's scaling and the chunked form's extra products are not required
work). The embedding gather is no FLOPs; the untied head counts at the
positions that are sampled. Norms, softmax, the convolution's four taps,
the gates and the multipliers are left out.
"""

from __future__ import annotations


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def matmul_params_per_layer(cfg: dict) -> int:
    """Parameters a token multiplies in one block."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    d_ssm = cfg["mamba_d_ssm"]
    in_width = 2 * d_ssm + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] \
        + cfg["mamba_n_heads"]
    return (2 * h * hq * d + 2 * h * kv * d      # q, o; k, v
            + h * in_width + d_ssm * h           # SSM in, out
            + 3 * h * f)                         # MLP


def keys_seen_sum(start: int, n: int) -> int:
    """Keys seen by ``n`` consecutive positions from ``start``."""
    return n * start + n * (n + 1) // 2


def attention_flops(cfg: dict, start: int, n: int) -> float:
    """QK^T and PV of ``n`` positions from ``start``, every block held."""
    per_key = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_key * keys_seen_sum(start, n) * layers(cfg)


def recurrence_flops(cfg: dict, tokens: int) -> float:
    """The SSM's state update and read-out, every block held: ``4 N P``
    a head a token."""
    return 4.0 * cfg["mamba_d_state"] * cfg["mamba_d_head"] \
        * cfg["mamba_n_heads"] * tokens * layers(cfg)


def forward_flops(cfg: dict, start: int, n: int, head_tokens: int) -> float:
    dense = 2.0 * matmul_params_per_layer(cfg) * layers(cfg) * n
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    return dense + attention_flops(cfg, start, n) \
        + recurrence_flops(cfg, n) + head


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return forward_flops(cfg, 0, prompt_len, head_tokens=1)


def decode_flops(cfg: dict, context_lens) -> float:
    """One token for each sequence; ``context`` counts the token."""
    return sum(forward_flops(cfg, int(c) - 1, 1, head_tokens=1)
               for c in context_lens)


def state_bytes(cfg: dict) -> int:
    """One sequence's state in one block, float32."""
    return 4 * cfg["mamba_n_heads"] * cfg["mamba_d_state"] \
        * cfg["mamba_d_head"]


def ssd_token_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What a position moves through the scan besides the state, one
    block: x and B, C read and y written in the activations' type, dt
    (float32, a head) read."""
    d_ssm = cfg["mamba_d_ssm"]
    bc = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return (2 * d_ssm + bc) * itemsize + 4 * cfg["mamba_n_heads"]


def ssd_decode_bytes(cfg: dict, slot_steps: int) -> float:
    """What single-token steps must move through the scan: the states
    of ``slot_steps`` live sequences (summed over steps) read and
    written, and their positions' x, B, C, dt and y, every block held.
    The convolution's tails are not the kernel's: they are moved beside
    it."""
    return float(2 * state_bytes(cfg) + ssd_token_bytes(cfg)) \
        * layers(cfg) * slot_steps


def ssd_prefill_flops(cfg: dict, prompt_len: int) -> float:
    return recurrence_flops(cfg, prompt_len)


def ssd_prefill_bytes(cfg: dict, prompt_len: int) -> float:
    """x, B, C, dt and y at the prompt's true length and the final state
    written once: every block held."""
    return float(ssd_token_bytes(cfg) * prompt_len + state_bytes(cfg)) \
        * layers(cfg)
