"""Operations and bytes the algorithm needs, from shapes alone.

The same work whatever implements it. A matmul of [m,k]x[k,n] is 2mkn.
Causal attention is counted at the half that is needed: a query at
position p attends to p+1 keys. The embedding gather is no FLOPs; the
tied head counts. Layer norms, softmax, gelu and biases are left out
(a fraction of a percent at these widths).
"""

from __future__ import annotations


def matmul_params_per_layer(cfg: dict) -> int:
    h, f = cfg["hidden_size"], cfg["ffn_hidden_size"]
    return 3 * h * h + h * h + 2 * h * f


def forward_flops(cfg: dict, new_tokens: int, context_sum: int,
                  head_tokens: int | None = None) -> float:
    """Forward FLOPs of ``new_tokens`` positions whose attention spans
    sum to ``context_sum`` keys (a position attends to itself and what
    precedes it). ``head_tokens`` positions go through the tied head
    (all of them in training; one per prompt in prefill)."""
    h, layers = cfg["hidden_size"], cfg["num_layers"]
    if head_tokens is None:
        head_tokens = new_tokens
    dense = 2.0 * matmul_params_per_layer(cfg) * layers * new_tokens
    attn = 4.0 * h * layers * context_sum  # QK^T and PV, 2*h each per key
    head = 2.0 * h * cfg["vocab_size"] * head_tokens
    return dense + attn + head


def causal_context_sum(start: int, n: int) -> int:
    """Keys attended by ``n`` consecutive positions from ``start``."""
    return n * start + n * (n + 1) // 2


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward (3x forward) per token of a [*, seq] batch,
    causal attention at the required half: 6*L*H*(seq+1) ~ 6LHS."""
    return 3.0 * forward_flops(cfg, seq, causal_context_sum(0, seq)) / seq


def prefill_flops(cfg: dict, prompt_len: int, cached: int = 0) -> float:
    n = prompt_len - cached
    return forward_flops(cfg, n, causal_context_sum(cached, n),
                         head_tokens=1)


def decode_flops(cfg: dict, context_lens) -> float:
    """One token for each sequence, attending ``context`` keys (its own
    included)."""
    lens = list(context_lens)
    return forward_flops(cfg, len(lens), sum(lens))


def flash_flops(batch: int, heads: int, seq: int, head_dim: int,
                backward: bool) -> float:
    """Causal attention of [batch, seq, heads, head_dim]: QK^T and PV
    over the lower triangle forward (2 matmuls); backward needs dQ, dK,
    dV, dP and the recomputed scores (5 matmuls)."""
    pairs = seq * (seq + 1) / 2.0
    per_matmul = 2.0 * head_dim * pairs * heads * batch
    return per_matmul * (5.0 if backward else 2.0)


def flash_bytes(batch: int, heads: int, seq: int, head_dim: int,
                itemsize: int, backward: bool) -> float:
    """HBM traffic no implementation can avoid: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv
    (the log-sum-exp rows are a 1/head_dim of one operand: left out)."""
    one = float(batch * seq * heads * head_dim * itemsize)
    return one * (8.0 if backward else 4.0)


def paged_decode_bytes(context_lens, page_size: int, heads: int,
                       head_dim: int, itemsize: int, layers: int = 1) -> float:
    """Bytes of K and V pages a decode step must read: every page that
    holds a key of the sequence, whole pages (the pool is read page by
    page)."""
    pages = sum(-(-int(n) // page_size) for n in context_lens)
    return 2.0 * pages * page_size * heads * head_dim * itemsize * layers
