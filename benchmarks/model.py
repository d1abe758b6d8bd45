"""The program's GPT for a configuration, loaded with the benchmark's
weights: the one place that maps the configuration's keys onto
``paddle_tpu.models.GPTConfig``."""

from __future__ import annotations

from benchmarks import weights as wts


def build_gpt(cfg: dict, seed: int, **extra):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    gcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        max_seq_len=cfg["max_seq_len"],
        ffn_hidden_mult=cfg["ffn_hidden_size"] // cfg["hidden_size"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dropout=0.0, attn_dropout=0.0, dtype=cfg["dtype"]["weights"],
        **extra)
    model = GPTForCausalLM(gcfg)
    wts.load_into(model, wts.make_weights(cfg, seed))
    return model
