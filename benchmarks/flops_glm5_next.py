"""Operations and bytes a GLM-5.3-Flash configuration needs, from shapes
alone (the configuration's published keys).

The same work whatever implements it. A matmul of [m,k]x[k,n] is 2mkn.
A token goes through both mHC mappings of every layer held (``x Phi``,
``n h x n (n + 2)``; the mixing itself, Sinkhorn and the norms are left
out), its layer's mixer and its feed-forward layer: the dense one, or
the router over ALL experts, the shared expert and the picks that land
on experts held here (``count / all`` of its ``num_experts_per_tok``,
what uniform routing sends this chip). A KDA layer: q, k, v, o, the two
low-rank projections, beta, and the recurrence's ``6 d_k d_v`` a head a
token. A DSA layer: its projections (q_a, q_b, kv_a, ``W_UK`` and
``W_UV`` once a token, on the latent or on the query and the output, o)
and the indexer's (q, k, w), the indexer's ``2 Hi Di`` a scored block
(every complete block before the query's own), and attention over the
positions the query SELECTS (:func:`selected`), in the form with fewer
operations: expanded for a prompt's token (``2 (qk + v)`` a head a
key), absorbed for a decoded one (``2 (rank + rank)`` a head a key).
The embedding gather is no FLOPs; the untied head counts at the
positions that are sampled.
"""

from __future__ import annotations

# an expert and a KDA layer's state have Solar-Open2's shapes and keys
from benchmarks.flops_solar_open2 import expert_bytes, state_bytes  # noqa: F401

SPARSE = "deepseek_sparse_attention"


def layer_ids(cfg: dict) -> list:
    return list(cfg.get("layer_ids") or range(cfg["num_hidden_layers"]))


def layer_kinds(cfg: dict) -> list:
    """``(mixer, feed-forward)`` of each layer held: ``"dsa"`` or
    ``"kda"``, ``"dense"`` or ``"sparse"``."""
    return [("dsa" if cfg["layer_types"][i] == SPARSE else "kda",
             cfg["mlp_layer_types"][i]) for i in layer_ids(cfg)]


def count(cfg: dict, kind: str) -> int:
    return sum(1 for k in layer_kinds(cfg) if kind in k)


def router_width(cfg: dict) -> int:
    return (cfg["published"]["n_routed_experts"]
            if cfg.get("experts_held") else cfg["n_routed_experts"])


def picks_held_per_token(cfg: dict) -> float:
    held = cfg.get("experts_held")
    share = 1.0 if not held else held[1] / router_width(cfg)
    return cfg["num_experts_per_tok"] * share


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def hc_params(cfg: dict) -> int:
    """``Phi`` of one sublayer's mappings."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * n * (n + 2)


def kda_params(cfg: dict) -> int:
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    k, d = lin["num_heads"], lin["head_dim"]
    return 4 * h * k * d + 2 * (h * d + d * k * d) + h * k


def indexer_params(cfg: dict) -> int:
    h, hi, di = (cfg["hidden_size"], cfg["index_n_heads"],
                 cfg["index_head_dim"])
    return cfg["q_lora_rank"] * hi * di + h * di + h * hi


def dsa_params(cfg: dict) -> int:
    """The latent attention's projections, the indexer's beside them."""
    h, heads, rank = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["kv_lora_rank"])
    qr, nope, v = (cfg["q_lora_rank"], cfg["qk_nope_head_dim"],
                   cfg["v_head_dim"])
    return (h * qr + qr * heads * nope + h * rank + rank * heads * (nope + v)
            + heads * v * h + indexer_params(cfg))


def ffn_params_per_token(cfg: dict, mlp: str) -> float:
    h = cfg["hidden_size"]
    if mlp == "dense":
        return 3.0 * h * cfg["intermediate_size"]
    return h * router_width(cfg) + \
        (cfg["n_shared_experts"] + picks_held_per_token(cfg)) \
        * expert_params(cfg)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token multiplies, all layers held."""
    return sum(2 * hc_params(cfg)
               + (dsa_params(cfg) if mix == "dsa" else kda_params(cfg))
               + ffn_params_per_token(cfg, mlp)
               for mix, mlp in layer_kinds(cfg))


# -- what a query at a position attends to ------------------------------------

def selected(cfg: dict, t: int) -> int:
    """Positions a query at position ``t`` attends to: everything up to
    it while it has ``index_topk`` complete blocks or fewer, else its
    ``index_topk`` blocks and its own block up to ``t``."""
    pool, k = cfg["index_kpool"], cfg["index_topk"]
    if t // pool <= k:
        return t + 1
    return k * pool + t % pool + 1


def selected_sum(cfg: dict, start: int, n: int) -> int:
    """:func:`selected` summed over the positions ``start .. start + n -
    1``."""
    return _selected_upto(cfg, start + n) - _selected_upto(cfg, start)


def _selected_upto(cfg: dict, n: int) -> int:
    pool, k = cfg["index_kpool"], cfg["index_topk"]
    first = (k + 1) * pool  # the first position that selects
    m = min(n, first)
    total = m * (m + 1) // 2
    c = n - first
    if c > 0:
        r = c % pool
        total += c * (k * pool + 1) + (c // pool) * pool * (pool - 1) // 2 \
            + r * (r - 1) // 2
    return total


def scored_sum(cfg: dict, start: int, n: int) -> int:
    """Blocks the indexer scores for the queries ``start .. start + n -
    1``: every complete block before a query's own."""
    def upto(m):
        pool = cfg["index_kpool"]
        q, r = divmod(m, pool)
        return pool * q * (q - 1) // 2 + r * q
    return upto(start + n) - upto(start)


# -- operations ---------------------------------------------------------------

def recurrence_flops(cfg: dict, tokens: int) -> float:
    lin = cfg["linear_attn_config"]
    return 6.0 * lin["head_dim"] ** 2 * lin["num_heads"] * tokens \
        * count(cfg, "kda")


def index_flops(cfg: dict, start: int, n: int) -> float:
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"] \
        * scored_sum(cfg, start, n) * count(cfg, "dsa")


def sparse_prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A prompt's attention over the positions each query selects, in
    the expanded form: ``2 (qk + v)`` a head a key, the DSA layers
    held."""
    per_key = 2.0 * cfg["num_attention_heads"] * \
        (cfg["qk_head_dim"] + cfg["v_head_dim"])
    return per_key * selected_sum(cfg, 0, prompt_len) * count(cfg, "dsa")


def sparse_decode_flops(cfg: dict, context: int) -> float:
    """A decoded token (``context`` counts it) in the absorbed form."""
    per_key = 2.0 * cfg["num_attention_heads"] * 2 * cfg["kv_lora_rank"]
    return per_key * selected(cfg, context - 1) * count(cfg, "dsa")


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    n = prompt_len
    return (2.0 * matmul_params_per_token(cfg) * n
            + recurrence_flops(cfg, n) + index_flops(cfg, 0, n)
            + sparse_prefill_flops(cfg, n)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def decode_flops(cfg: dict, context_lens) -> float:
    """One token for each sequence; ``context`` counts the token."""
    per = 2.0 * matmul_params_per_token(cfg) + recurrence_flops(cfg, 1) \
        + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return sum(per + index_flops(cfg, int(c) - 1, 1)
               + sparse_decode_flops(cfg, int(c)) for c in context_lens)


# -- bytes --------------------------------------------------------------------

def latent_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What a position leaves in a DSA layer's latent pool: ``rank``
    values (no rotary part)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def sparse_decode_bytes(cfg: dict, context_lens, itemsize: int = 2) -> float:
    """The latent rows a decoded token's attention must read: its
    selected positions, the DSA layers held."""
    return float(sum(selected(cfg, int(c) - 1) for c in context_lens)) \
        * latent_row_bytes(cfg, itemsize) * count(cfg, "dsa")



def kda_decode_bytes(cfg: dict, slot_steps: int) -> float:
    """The states a decode step must read and write: ``slot_steps``
    live sequences (summed over steps), the KDA layers held."""
    return 2.0 * state_bytes(cfg) * count(cfg, "kda") * slot_steps


def kda_prefill_flops(cfg: dict, prompt_len: int) -> float:
    return recurrence_flops(cfg, prompt_len)


def kda_prefill_bytes(cfg: dict, prompt_len: int, itemsize: int = 2) -> float:
    """q, k, v read and o written in the activations' type, the decay
    (float32, a key channel) and beta (float32, a head) read, at the
    prompt's true length, and the final state written once: the KDA
    layers held."""
    lin = cfg["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    per_token = 4 * n * d * itemsize + 4 * n * d + 4 * n
    return float(per_token * prompt_len + state_bytes(cfg)) \
        * count(cfg, "kda")
