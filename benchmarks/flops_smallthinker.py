"""Operations and bytes a SmallThinker configuration needs, from shapes
alone (the configuration's published keys).

The same work whatever implements it. A matmul of [m,k]x[k,n] is 2mkn.
A token goes through the attention projections, the router and the
``k`` experts it picked (never the experts it did not). Attention is
counted over the keys a query sees: causal, and in a window layer at
most ``window`` of them. The embedding gather is no FLOPs; the untied
head counts at the positions that are sampled. Norms, softmax, rotary
and relu are left out.
"""

from __future__ import annotations


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters a token multiplies in one layer: q, k, v, o, the
    router over all experts, and the picked experts."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * h * hq * d + 2 * h * kv * d
    router = h * cfg["moe_num_primary_experts"]
    return attn + router + \
        cfg["moe_num_active_primary_experts"] * expert_params(cfg)


def layer_windows(cfg: dict) -> list:
    """The window of each layer held, ``None`` where every key is seen."""
    n = cfg["num_hidden_layers"]
    return [cfg["sliding_window_size"] if w else None
            for w in cfg["sliding_window_layout"][:n]]


def keys_seen_sum(start: int, n: int, window) -> int:
    """Keys seen by ``n`` consecutive positions from ``start`` (a
    position sees itself and what precedes it, at most ``window``)."""
    if window is None:
        return n * start + n * (n + 1) // 2
    # positions p = start .. start+n-1 see min(p + 1, window) keys
    ramp = max(0, min(start + n, window) - start)  # positions below it
    return ramp * start + ramp * (ramp + 1) // 2 + (n - ramp) * window


def attention_flops(cfg: dict, start: int, n: int) -> float:
    """QK^T and PV of ``n`` positions from ``start``, all layers."""
    per_key = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_key * sum(keys_seen_sum(start, n, w)
                         for w in layer_windows(cfg))


def forward_flops(cfg: dict, start: int, n: int, head_tokens: int) -> float:
    dense = 2.0 * matmul_params_per_token(cfg) * cfg["num_hidden_layers"] * n
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    return dense + attention_flops(cfg, start, n) + head


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    return forward_flops(cfg, 0, prompt_len, head_tokens=1)


def decode_flops(cfg: dict, context_lens) -> float:
    """One token for each sequence; ``context`` counts the token."""
    return sum(forward_flops(cfg, int(c) - 1, 1, head_tokens=1)
               for c in context_lens)


def expert_flops(cfg: dict, tokens: int) -> float:
    """The expert matmuls of ``tokens`` positions, all layers."""
    return 2.0 * cfg["moe_num_active_primary_experts"] * expert_params(cfg) \
        * cfg["num_hidden_layers"] * tokens


def expert_bytes(cfg: dict, touched: int, itemsize: int = 2) -> float:
    """Weight bytes of ``touched`` experts (distinct experts hit, summed
    over layers and steps): what a step that is bound by the experts'
    weights must read."""
    return float(touched) * expert_params(cfg) * itemsize


def pages_spanned(context: int, page_size: int, window) -> int:
    """Pages that hold a key the newest of ``context`` positions sees."""
    last = -(-int(context) // page_size)
    first = 0 if window is None else max(int(context) - window, 0) // page_size
    return last - first


def paged_decode_bytes(cfg: dict, context_lens, itemsize: int = 2) -> float:
    """K and V pages a decode step must read, whole pages, per kind of
    layer: every page of the context in a global layer, the pages the
    window spans in a window layer."""
    page = int(cfg["engine"]["page_size"])
    one = 2.0 * page * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    return one * sum(pages_spanned(c, page, w) for c in context_lens
                     for w in layer_windows(cfg))


def flash_flops(cfg: dict, prompt_len: int) -> float:
    """Causal attention of one prompt, all layers: only the keys inside
    causal AND window count."""
    return attention_flops(cfg, 0, prompt_len)


def flash_bytes(cfg: dict, prompt_len: int, itemsize: int = 2) -> float:
    """Reads q, k, v and writes o, all layers: grouped heads, so k and v
    are ``num_key_value_heads`` wide."""
    d = cfg["head_dim"]
    per_token = (2 * cfg["num_attention_heads"]
                 + 2 * cfg["num_key_value_heads"]) * d * itemsize
    return float(per_token) * prompt_len * cfg["num_hidden_layers"]
